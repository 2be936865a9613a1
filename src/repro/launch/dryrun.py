import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           "--xla_allow_excess_precision=false")
# The lines above MUST run before any jax import: the dry-run is a CPU-only
# tool that never takes an accelerator, jax locks the device count on
# first init, and the production-mesh dry-run needs 512 host placeholder
# devices (2 pods x 16 x 16).  Everything below is ordinary.
"""Multi-pod dry-run: AOT-lower + compile every (architecture x input-shape
x mesh) combination against the production mesh, and extract the roofline
inputs (FLOPs, bytes, collective traffic, per-device memory) from the
compiled artifact.  No arrays are ever allocated — inputs are
ShapeDtypeStructs with NamedShardings attached.

  PYTHONPATH=src python -m repro.launch.dryrun --arch minicpm-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # full sweep
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Outputs one JSON per combination under --out (default experiments/dryrun/),
consumed by benchmarks/roofline.py and EXPERIMENTS.md.
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import firstorder
from repro.core import stats as statlib
from repro.core.mkor import MKORConfig, manifest_for, mkor, mkor_h
from repro.analysis import hlo as hlo_analysis
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib
from repro.models.config import INPUT_SHAPES, InputShape, ModelConfig
from repro.sharding import rules
from repro.training import loop as train_lib
from repro.training import serving as serve_lib


# --------------------------------------------------------------------- #
# Optimizers available to the train-mode dry-run
# --------------------------------------------------------------------- #
def make_optimizer(name: str, cfg: ModelConfig,
                   mcfg: MKORConfig = MKORConfig()) \
        -> firstorder.GradientTransformation:
    backend = firstorder.lamb(1e-3)
    if name == "mkor":
        return mkor(backend, mcfg)
    if name == "mkor_h":
        return mkor_h(backend, mcfg)
    if name == "lamb":
        return backend
    raise ValueError(f"unknown optimizer {name!r}")


# --------------------------------------------------------------------- #
# input_specs: ShapeDtypeStruct stand-ins for every model input
# --------------------------------------------------------------------- #
def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """Sharding-free ShapeDtypeStructs for one (arch, shape) pair."""
    if shape.mode in ("train", "prefill"):
        return train_lib.train_batch_shapes(cfg, shape.global_batch,
                                            shape.seq_len)
    # decode: one new token + a seq_len-context cache
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    cache = jax.eval_shape(partial(
        model_lib.init_decode_cache, cfg, shape.global_batch, shape.seq_len))
    return {"tokens": tokens, "cache": cache}


def factor_bucket_report(params_sds, mcfg: MKORConfig = MKORConfig(),
                         world_size: int = 1):
    """Per-bucket factor FLOPs/bytes + collective payload bytes for the
    MKOR bank layout (DESIGN.md §2/§10).  Works on ShapeDtypeStructs — no
    arrays are allocated.  ``world_size`` is the data-parallel degree the
    comm columns assume (rank-1 stat exchange per step, KFAC-style full
    factor payload per inversion, owner-sharded inverse gather per phase
    step)."""
    fbytes = statlib.factor_itemsize(mcfg.factor_dtype, mcfg.factor_quant)
    sbytes = jnp.dtype("bfloat16").itemsize   # rank-1 stat wire payload
    return [{**statlib.bucket_cost(b, fbytes, rank=mcfg.rank,
                                   staleness=mcfg.staleness,
                                   health=mcfg.health,
                                   factor_quant=mcfg.factor_quant),
             **statlib.bucket_comm_cost(b, world_size, fbytes, sbytes,
                                        rank=mcfg.rank,
                                        factor_quant=mcfg.factor_quant)}
            for b in manifest_for(params_sds, mcfg)]


def active_param_counts(cfg: ModelConfig, params_sds) -> Dict[str, int]:
    """(total, active, non-embedding-active) parameter counts; MoE expert
    tensors scaled by top_k/n_experts for the active count."""
    total = 0
    active = 0.0
    embed = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params_sds):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        frac = 1.0
        if cfg.moe is not None and "w" in keys[-1] and len(leaf.shape) >= 4 \
                and leaf.shape[-3] == cfg.moe.n_experts:
            frac = cfg.moe.top_k / cfg.moe.n_experts
        active += n * frac
        if "embed" in keys or "lm_head" in keys:
            embed += n
    return {"total": total, "active": int(active),
            "active_non_embed": int(active) - embed}


# --------------------------------------------------------------------- #
# One dry-run
# --------------------------------------------------------------------- #
def lower_one(cfg: ModelConfig, shape: InputShape, *, multi_pod: bool,
              optimizer: str = "mkor",
              mcfg: MKORConfig = MKORConfig(),
              collect_stats: bool = True,
              save_hlo: str = "") -> Dict[str, Any]:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    axes = mesh_lib.mesh_axes(mesh)
    n_chips = mesh.devices.size
    mode = shape.mode

    if mode == "decode":
        cfg = registry.long_context_variant(cfg) \
            if shape.name == "long_500k" else cfg

    params_sds = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    pspecs = rules.param_specs(params_sds, mesh, axes)
    params_in = rules.with_sharding(params_sds, pspecs, mesh)

    t0 = time.time()
    if mode == "train":
        opt = make_optimizer(optimizer, cfg, mcfg)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        ospecs = rules.opt_state_specs(opt_sds, mesh, axes)
        opt_in = rules.with_sharding(opt_sds, ospecs, mesh)
        batch_sds = input_specs(cfg, shape)
        bspecs = rules.batch_specs(batch_sds, mesh, axes)
        batch_in = rules.with_sharding(batch_sds, bspecs, mesh)
        step = train_lib.make_train_step(cfg, opt,
                                         collect_stats=collect_stats)
        with mesh, rules.activation_sharding(mesh, axes):
            lowered = jax.jit(step).lower(params_in, opt_in, batch_in)
    elif mode == "prefill":
        batch_sds = input_specs(cfg, shape)
        bspecs = rules.batch_specs(batch_sds, mesh, axes)
        batch_in = rules.with_sharding(batch_sds, bspecs, mesh)
        step = serve_lib.make_prefill_step(cfg, cache_extra=1)
        with mesh, rules.activation_sharding(mesh, axes):
            lowered = jax.jit(step).lower(params_in, batch_in)
    else:  # decode
        specs = input_specs(cfg, shape)
        cspecs = rules.cache_specs(specs["cache"], mesh, axes)
        cache_in = rules.with_sharding(specs["cache"], cspecs, mesh)
        tok_spec = rules.batch_specs({"tokens": specs["tokens"]}, mesh, axes)
        tok_in = rules.with_sharding({"tokens": specs["tokens"]},
                                     tok_spec, mesh)["tokens"]
        step = serve_lib.make_serve_step(cfg)
        with mesh, rules.activation_sharding(mesh, axes):
            lowered = jax.jit(step).lower(params_in, cache_in, tok_in)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):      # older jax: list of one dict
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception:
        mem_info = {}

    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    ana = hlo_analysis.analyze(hlo)          # trip-count aware, per chip
    roof = hlo_analysis.roofline(ana["flops"], ana["bytes"],
                                 ana["collective_total_bytes"])

    factor_buckets = factor_bucket_report(
        params_sds, mcfg, world_size=axes.data_size(mesh)) \
        if mode == "train" and optimizer in ("mkor", "mkor_h") else []

    counts = active_param_counts(cfg, params_sds)
    n_tokens = shape.global_batch * (shape.seq_len if mode != "decode" else 1)
    model_flops = hlo_analysis.model_flops_per_step(
        counts["active_non_embed"], n_tokens,
        "train" if mode == "train" else "infer")

    return {
        "arch": cfg.name,
        "shape": shape.name,
        "mode": mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "optimizer": optimizer if mode == "train" else None,
        "flops": ana["flops"],
        "dot_flops": ana["dot_flops"],
        "bytes_accessed": ana["bytes"],
        "collective_bytes": ana["collective_bytes"],
        "collective_total_bytes": ana["collective_total_bytes"],
        "collective_counts": ana["collective_counts"],
        "xla_cost_flops_per_partition": float(cost.get("flops", 0.0)),
        "memory": mem_info,
        "roofline": roof,
        "model_flops": model_flops,
        # analyzed flops are per-chip -> x n_chips for the global total
        "useful_flops_ratio": (model_flops / (ana["dot_flops"] * n_chips))
        if ana["dot_flops"] else None,
        "params": counts,
        "factor_buckets": factor_buckets,
        "t_lower_s": t_lower,
        "t_compile_s": t_compile,
    }


def format_row(r: Dict[str, Any]) -> str:
    roof = r["roofline"]
    fb = r.get("factor_buckets") or []
    fb_note = ""
    if fb:
        flops = sum(b["smw_flops_per_inv"] for b in fb)
        mem = sum(b["factor_bytes"] for b in fb)
        # per-step collective payload: rank-1 stats every step vs the
        # KFAC-style full-factor payload a broadcast design would ship
        # (amortized over the inversion window) — DESIGN.md §10
        r1 = sum(b["rank1_stats_bytes_per_step"] for b in fb)
        kfac = sum(b["kfac_factor_bytes_per_inv"] for b in fb)
        # health-sentinel state is 8 B/bucket and wire-free (DESIGN.md
        # §14) — surfaced so the dry-run documents the (negligible) cost
        hb = sum(b.get("health_state_bytes", 0) for b in fb)
        fb_note = (f"buckets={len(fb)} "
                   f"smw={flops:.2e}F factors={mem / 2**30:.2f}GiB "
                   f"r1comm={r1 / 2**20:.2f}MiB/step "
                   f"(kfac {kfac / 2**20:.0f}MiB/inv) "
                   + (f"health={hb}B " if hb else ""))
    return (f"{r['arch']:17s} {r['shape']:12s} {r['mesh']:8s} "
            f"{fb_note}"
            f"flops={r['flops']:.3e} bytes={r['bytes_accessed']:.3e} "
            f"coll={r['collective_total_bytes']:.3e} "
            f"compute={roof['compute_s']*1e3:8.2f}ms "
            f"memory={roof['memory_s']*1e3:8.2f}ms "
            f"coll={roof['collective_s']*1e3:8.2f}ms "
            f"dom={roof['dominant']:10s} "
            f"useful={r['useful_flops_ratio'] or 0:.2f} "
            f"[compile {r['t_compile_s']:.0f}s]")


def should_skip(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" \
            and cfg.name not in registry.long_context_archs():
        return ("pure full-attention architecture; long_500k needs "
                "sub-quadratic decode (DESIGN.md §5)")
    return None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all' (assigned pool)")
    ap.add_argument("--shape", default="all",
                    help="input shape id or 'all'")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (512-chip) mesh")
    ap.add_argument("--optimizer", default="mkor",
                    choices=["mkor", "mkor_h", "lamb"])
    ap.add_argument("--no-stats", action="store_true",
                    help="disable MKOR stat capture in the train step")
    ap.add_argument("--health", action="store_true",
                    help="plan with the numerical-health sentinel on "
                         "(DESIGN.md \u00a714): the traced step carries the "
                         "per-bucket quarantine state and the bucket "
                         "report gains its health-state bytes column")
    ap.add_argument("--quant", default="none",
                    choices=["none", "bf16", "int8"],
                    help="factor residency format (DESIGN.md \u00a716): "
                         "int8 shrinks the bank bytes and owner-gather "
                         "columns ~2x vs bf16 and adds the scale/EF rows")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", default="",
                    help="dump the optimized HLO text to this path")
    ap.add_argument("--all", action="store_true",
                    help="shorthand for --arch all --shape all")
    args = ap.parse_args()

    archs = registry.ASSIGNED if (args.all or args.arch == "all") \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape == "all") \
        else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        cfg = registry.get_config(arch)
        for shape_name in shapes:
            shape = INPUT_SHAPES[shape_name]
            tag = f"{arch}_{shape_name}_" \
                  f"{'2x16x16' if args.multi_pod else '16x16'}" \
                  + (f"_{args.optimizer}" if args.optimizer != "mkor" else "")
            skip = should_skip(cfg, shape)
            if skip:
                rec = {"arch": arch, "shape": shape_name, "skipped": skip,
                       "mesh": "2x16x16" if args.multi_pod else "16x16"}
                print(f"{arch:17s} {shape_name:12s} SKIP: {skip}")
            else:
                try:
                    rec = lower_one(cfg, shape, multi_pod=args.multi_pod,
                                    optimizer=args.optimizer,
                                    mcfg=MKORConfig(health=args.health,
                                                    factor_quant=args.quant),
                                    collect_stats=not args.no_stats,
                                    save_hlo=args.save_hlo)
                    print(format_row(rec))
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "error": f"{type(e).__name__}: {e}"}
                    failures.append(tag)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
    if failures:
        raise SystemExit(f"dry-run failures: {failures}")


if __name__ == "__main__":
    main()
