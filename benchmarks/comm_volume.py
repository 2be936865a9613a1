"""Per-step collective bytes: MKOR rank-1 vs KFAC-style full factors
(PAPER.md §3, DESIGN.md §10), measured on the 512-device dryrun topology.

MKOR's distribution ships the rank-1 statistics vectors ā (d_in,) and
ḡ (d_out,) every step — O(d) per layer — where KFAC/KAISA-style designs
all-reduce the d² Kronecker factors on every factor update.  This
benchmark compiles three small explicit-collective shard_map programs for
the *real* factor manifest of one architecture over 512 fake host devices
and runs launch/hlo_analysis.py's collective-byte accounting over the
compiled HLO (AOT only — no arrays are allocated):

* ``rank1_stats``   — per-step ā/ḡ mean exchange (bf16 payload, fp32 acc);
* ``kfac_factors``  — the O(d²) baseline: all-reduce of the full factor
  banks (KFAC's data-parallel covariance averaging / KAISA factor sync);
* ``owner_gather``  — the owner-sharded inversion schedule: each worker
  all-gathers only its owned 1/world bank-dim chunk of the updated
  inverses, on that bucket's phase step;
* ``owner_gather_int8`` — the same schedule under ``factor_quant=int8``
  (DESIGN.md §16): the chunk ships as int8 codes plus per-slice fp32
  scales through ``collectives.owner_sharded_map_quant`` — ~2x fewer
  payload bytes than the bf16 wire format.

Two byte accountings appear in BENCH_comm_volume.json: ``link_bytes``
(ring-model bytes crossing one chip's links, from hlo_analysis — every
worker must *receive* the full reduced state, so gathers of any flavor
converge to ~the payload size; note the CPU lowering upcasts the bf16
pmean operands to fp32, so measured link bytes run ~2x ring x ~2x dtype
above the bf16 payload column) and ``payload`` (bf16 bytes each worker
*sends* — the collective operand at the TPU-target width), which is where
the owner-sharding win lives: 1/min(world, slices) of the factor bytes
per phase step vs the full-factor baseline.

``--full`` additionally lowers the end-to-end train step both ways —
implicit GSPMD on the 2x16x16 production mesh (launch/dryrun.py path) and
the explicit shard_map step (training/loop.py make_dist_train_step) on a
512-way data mesh — and records their measured per-chip collective bytes.

  PYTHONPATH=src python -m benchmarks.comm_volume
  PYTHONPATH=src python -m benchmarks.comm_volume --full

It runs on the CPU only (it sets ``JAX_PLATFORMS=cpu`` before importing
jax) and refuses a process whose backend is an accelerator.  It re-execs
itself in a subprocess when jax already started the CPU backend with
fewer devices (e.g. under benchmarks/run.py), since the forced host
device count must be set before the backend starts.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ARCH = "bert-large"
DEVICES = 512
OUT = "BENCH_comm_volume.json"


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=ARCH)
    ap.add_argument("--devices", type=int, default=DEVICES)
    ap.add_argument("--inv-freq", type=int, default=10)
    ap.add_argument("--quant", default="none",
                    choices=("none", "bf16", "int8"),
                    help="factor_quant mode for the per-bucket analytic "
                         "rows (the int8 comparison rows are always "
                         "emitted)")
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--full", action="store_true",
                    help="also lower the end-to-end train step (implicit "
                         "GSPMD multi-pod + explicit shard_map) — slow")
    return ap.parse_args(argv)


def _measure(body, sds, mesh):
    """AOT-compile ``jax.shard_map(body)`` on ``mesh`` and return per-chip
    collective bytes/counts from the optimized HLO."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.launch import hlo_analysis

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    hlo = jax.jit(fn).lower(sds).compile().as_text()
    ana = hlo_analysis.analyze(hlo)
    return {"link_bytes": ana["collective_total_bytes"],
            "by_kind": {k: v for k, v in ana["collective_bytes"].items()
                        if v},
            "counts": {k: int(v) for k, v in
                       ana["collective_counts"].items() if v}}


def _micro(args):
    """Measured collective bytes for the three sync schedules over the
    arch's real factor manifest."""
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.core import stats as statlib
    from repro.core.mkor import MKORConfig, manifest_for
    from repro.models import model as model_lib
    from repro.sharding import collectives

    cfg = registry.get_config(args.arch)
    mcfg = MKORConfig(inv_freq=args.inv_freq, factor_quant=args.quant)
    params_sds = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    manifest = manifest_for(params_sds, mcfg)
    # resident/wire byte width is derived from the config — NEVER a
    # hard-coded 2 (core/stats.factor_itemsize is the single source)
    fbytes = statlib.factor_itemsize(mcfg.factor_dtype, mcfg.factor_quant)
    sbytes = jnp.dtype(collectives.RANK1_PAYLOAD_DTYPE).itemsize

    mesh = jax.make_mesh((args.devices,), ("data",))
    dist = (("data", args.devices),)
    bf16 = jnp.bfloat16

    stats_sds, bank_sds, bank_sds_q = {}, {}, {}
    int8 = jnp.int8
    f32 = jnp.float32
    for b in manifest:
        lead = (b.n_slots,) + b.stack
        stats_sds[b.bucket_id] = {
            "a": jax.ShapeDtypeStruct(lead + (b.d_in,), bf16),
            "g": jax.ShapeDtypeStruct(lead + (b.d_out,), bf16)}
        bank_sds[b.bucket_id] = {
            "l": jax.ShapeDtypeStruct(lead + (b.d_out, b.d_out), bf16),
            "r": jax.ShapeDtypeStruct(lead + (b.d_in, b.d_in), bf16)}
        # quantized banks: int8 codes + one fp32 scale per (d, d) slice
        bank_sds_q[b.bucket_id] = {
            "l": jax.ShapeDtypeStruct(lead + (b.d_out, b.d_out), int8),
            "l_scale": jax.ShapeDtypeStruct(lead, f32),
            "r": jax.ShapeDtypeStruct(lead + (b.d_in, b.d_in), int8),
            "r_scale": jax.ShapeDtypeStruct(lead, f32)}

    def pmean_body(tree):
        # same wire pattern for both schedules: a mean all-reduce of every
        # leaf — only the leaf shapes (O(d) vectors vs O(d²) banks) differ
        return {bid: {k: collectives.pmean(x, dist)
                      for k, x in v.items()} for bid, v in tree.items()}

    def make_owner_body(d):
        def owner_body(tree):
            out = {}
            for bid, v in tree.items():
                o = {}
                for k, x in v.items():
                    n = 1                     # flattened (slot x stack)
                    for s in x.shape[:-2]:
                        n *= s
                    xf = x.reshape((n,) + x.shape[-2:])
                    g = collectives.gather_shards(
                        collectives.owner_shard(xf, d), d, n)
                    o[k] = g.reshape(x.shape)
                out[bid] = o
            return out
        return owner_body

    def make_owner_body_quant(d):
        # the int8 wire format: per bucket, each worker ships its owned
        # chunk's codes + scales through owner_sharded_map_quant, which
        # type-checks the codes against QUANT_WIRE_DTYPE and recombines
        # both (codes move verbatim / as disjoint masked-psum terms)
        def owner_body(tree):
            out = {}
            for bid, v in tree.items():
                o = {}
                for k in ("l", "r"):
                    x, sc = v[k], v[k + "_scale"]
                    n = 1                     # flattened (slot x stack)
                    for s in x.shape[:-2]:
                        n *= s
                    xf = x.reshape((n,) + x.shape[-2:])
                    scf = sc.reshape((n,))
                    gq, gsc = collectives.owner_sharded_map_quant(
                        lambda c, s: (c, s), [xf, scf], d, n)
                    o[k] = gq.reshape(x.shape)
                    o[k + "_scale"] = gsc.reshape(sc.shape)
                out[bid] = o
            return out
        return owner_body

    measured = {
        "rank1_stats": _measure(pmean_body, stats_sds, mesh),
        "kfac_factors": _measure(pmean_body, bank_sds, mesh),
        "owner_gather": _measure(make_owner_body(dist), bank_sds, mesh),
        "owner_gather_int8": _measure(make_owner_body_quant(dist),
                                      bank_sds_q, mesh),
    }
    # a world size <= the per-bucket slice count shows the clean
    # ~world_size payload cut (512 >> slices on this arch caps the cut at
    # 1/slices and flips gather_shards to its masked-psum recombine)
    w_small = 16
    mesh_small = jax.make_mesh((w_small,), ("data",))
    dist_small = (("data", w_small),)
    measured["owner_gather_small_world"] = dict(
        _measure(make_owner_body(dist_small), bank_sds, mesh_small),
        world=w_small)

    # analytic payload accounting (exact; per worker, bytes *sent*)
    buckets = []
    phases = statlib.bucket_phases(manifest, args.inv_freq, True)
    phase_payload, phase_full = {}, {}
    r1_total = kfac_total = 0
    bf16_bytes = jnp.dtype(jnp.bfloat16).itemsize
    int8_bytes = statlib.factor_itemsize(mcfg.factor_dtype, "int8")
    gather_bf16 = gather_int8 = 0
    for b in manifest:
        c = statlib.bucket_comm_cost(b, args.devices, fbytes, sbytes,
                                     factor_quant=mcfg.factor_quant)
        # the bf16-vs-int8 wire comparison, independent of --quant
        c_bf16 = statlib.bucket_comm_cost(b, args.devices, bf16_bytes,
                                          sbytes)
        c_int8 = statlib.bucket_comm_cost(b, args.devices, int8_bytes,
                                          sbytes, factor_quant="int8")
        slices = b.n_slots
        for s in b.stack:
            slices *= s
        row = {"bucket_id": b.bucket_id, "d_in": b.d_in, "d_out": b.d_out,
               "n_slots": b.n_slots, "stack": list(b.stack),
               "slices": slices, "phase": phases[b.bucket_id], **c,
               "owner_gather_int8_bytes_per_phase_step":
                   c_int8["owner_gather_bytes_per_phase_step"]}
        buckets.append(row)
        r1_total += c["rank1_stats_bytes_per_step"]
        kfac_total += c["kfac_factor_bytes_per_inv"]
        gather_bf16 += c_bf16["owner_gather_bytes_per_phase_step"]
        gather_int8 += c_int8["owner_gather_bytes_per_phase_step"]
        p = phases[b.bucket_id]
        phase_payload[p] = phase_payload.get(p, 0) \
            + c["owner_gather_bytes_per_phase_step"]
        phase_full[p] = phase_full.get(p, 0) + c["kfac_factor_bytes_per_inv"]

    payload_max = max(phase_payload.values())
    full_max = max(phase_full[p] for p in phase_payload
                   if phase_payload[p] == payload_max)
    analytic = {
        "rank1_stats_bytes_per_step": r1_total,
        "kfac_factor_bytes_per_inv": kfac_total,
        "kfac_factor_bytes_per_step_amortized": kfac_total / args.inv_freq,
        # O(d) vs O(d²): the headline linear-communication gap
        "od2_over_od_per_step":
            (kfac_total / args.inv_freq) / max(r1_total, 1),
        "owner_gather_payload_bytes_per_phase_step_max": payload_max,
        "full_factor_payload_bytes_per_phase_step_max": full_max,
        # the payload cut is world_size until the bank runs out of slices
        # (slices = slots x stack); on this arch/world it saturates there
        "owner_vs_full_payload_ratio": full_max / max(payload_max, 1),
        # the real ceil-chunk cut at W=16 (matches the measured
        # owner_gather_small_world program): slices / ceil(slices / 16)
        "owner_vs_full_payload_ratio_small_world": min(
            b["slices"] / -(-b["slices"] // 16) for b in buckets),
        # int8 codes + fp32 scales vs the bf16 chunk, summed over all
        # buckets' phase-step gathers — ~2x (the per-slice scales shave
        # an O(1/d²) sliver off the exact 2x; DESIGN.md §16)
        "owner_gather_bf16_bytes_per_phase_step": gather_bf16,
        "owner_gather_int8_bytes_per_phase_step": gather_int8,
        "int8_vs_bf16_wire_ratio": gather_bf16 / max(gather_int8, 1),
    }
    return {"buckets": buckets, "analytic": analytic, "measured": measured}


def _full(args):
    """End-to-end train-step collective bytes, implicit vs explicit."""
    import jax

    from repro.configs import registry
    from repro.core import firstorder
    from repro.core.mkor import MKORConfig, mkor
    from repro.launch import dryrun as dryrun_lib
    from repro.launch import hlo_analysis
    from repro.models import model as model_lib
    from repro.models.config import INPUT_SHAPES
    from repro.sharding import collectives
    from repro.training import loop as train_lib

    cfg = registry.get_config(args.arch)
    shape = INPUT_SHAPES["train_4k"]

    # implicit: GSPMD on the production 2x16x16 mesh (dryrun path)
    rec = dryrun_lib.lower_one(cfg, shape, multi_pod=True)
    implicit = {
        "mesh": rec["mesh"],
        "collective_total_bytes": rec["collective_total_bytes"],
        "collective_bytes": rec["collective_bytes"],
        "collective_counts": rec["collective_counts"],
    }

    # explicit: shard_map data-parallel step on a 512-way data mesh
    mesh = jax.make_mesh((args.devices,), ("data",))
    dist = (("data", args.devices),)
    opt = mkor(firstorder.lamb(1e-3),
               MKORConfig(inv_freq=args.inv_freq, dist=dist))
    step = train_lib.make_dist_train_step(cfg, opt, mesh)
    params_sds = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    opt_sds = jax.eval_shape(opt.init, params_sds)
    batch_sds = train_lib.train_batch_shapes(cfg, args.devices,
                                             shape.seq_len)
    hlo = step.lower(params_sds, opt_sds, batch_sds).compile().as_text()
    ana = hlo_analysis.analyze(hlo)
    explicit = {
        "mesh": f"{args.devices} data",
        "collective_total_bytes": ana["collective_total_bytes"],
        "collective_bytes": {k: v for k, v in
                             ana["collective_bytes"].items() if v},
        "collective_counts": {k: int(v) for k, v in
                              ana["collective_counts"].items() if v},
    }
    return {"implicit_gspmd": implicit, "explicit_shard_map": explicit}


def run(args) -> None:
    from benchmarks.common import emit

    out = {"arch": args.arch, "devices": args.devices,
           "inv_freq": args.inv_freq, "factor_quant": args.quant}
    out.update(_micro(args))
    if args.full:
        out["full"] = _full(args)
    elif os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prev = json.load(f)
            if "full" in prev:
                out["full"] = prev["full"]      # keep the slow section
        except (OSError, ValueError):
            pass

    a, m = out["analytic"], out["measured"]
    emit([{"schedule": "rank1_stats (MKOR, per step)",
           "payload_bytes": a["rank1_stats_bytes_per_step"],
           "hlo_link_bytes": m["rank1_stats"]["link_bytes"]},
          {"schedule": "kfac_factors (baseline, per inv)",
           "payload_bytes": a["kfac_factor_bytes_per_inv"],
           "hlo_link_bytes": m["kfac_factors"]["link_bytes"]},
          {"schedule": "owner_gather (per phase step, all buckets)",
           "payload_bytes": sum(b["owner_gather_bytes_per_phase_step"]
                                for b in out["buckets"]),
           "hlo_link_bytes": m["owner_gather"]["link_bytes"]},
          {"schedule": "owner_gather_int8 (codes+scales, per phase step)",
           "payload_bytes": a["owner_gather_int8_bytes_per_phase_step"],
           "hlo_link_bytes": m["owner_gather_int8"]["link_bytes"]}],
         f"comm volume, {args.arch} @ {args.devices} workers")
    print(f"O(d²)/O(d) per-step gap: "
          f"{a['od2_over_od_per_step']:.0f}x; owner-sharded gather payload "
          f"= 1/{a['owner_vs_full_payload_ratio']} of factor bytes; "
          f"int8 wire = {a['int8_vs_bf16_wire_ratio']:.3f}x below bf16")

    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


def _strip_device_flag(flags: str) -> str:
    """Drop any --xla_force_host_platform_device_count=... from XLA_FLAGS.
    XLA honors the LAST occurrence, so prepending a bigger count in front
    of an existing smaller one would be ignored — and the re-exec below
    would loop forever re-seeing the old count."""
    return " ".join(
        f for f in flags.split()
        if not f.startswith("--xla_force_host_platform_device_count"))


def main(argv=None) -> None:
    args = _parse(argv if argv is not None else sys.argv[1:])
    need = max(args.devices, DEVICES if args.full else args.devices)
    # A CPU-only tool: it compiles for ``need`` fake host devices and never
    # takes an accelerator.  A fresh process settles both before JAX starts.
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={need} "
            + _strip_device_flag(os.environ.get("XLA_FLAGS", "")))
    import jax
    if jax.default_backend() != "cpu":
        raise SystemExit("benchmarks.comm_volume compiles for fake CPU "
                         "devices: run it in its own process with "
                         "JAX_PLATFORMS=cpu, not beside an accelerator")
    if jax.device_count() < need:
        # this CPU process started its backend with fewer devices (e.g.
        # under benchmarks/run.py) — re-exec with the forced count,
        # replacing any pre-set device-count flag
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={need} "
                            + _strip_device_flag(env.get("XLA_FLAGS", "")))
        cmd = [sys.executable, "-m", "benchmarks.comm_volume",
               "--arch", args.arch, "--devices", str(args.devices),
               "--inv-freq", str(args.inv_freq), "--quant", args.quant,
               "--out", args.out] \
            + (["--full"] if args.full else [])
        print(f"re-exec for {need} host devices: {' '.join(cmd)}")
        subprocess.run(cmd, check=True, env=env)
        return
    run(args)


if __name__ == "__main__":
    main()
