"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a training job on a reduced or full config with any of the
implemented optimizers, checkpointing and logging included.  On a TPU it
trains the full config (``chip_smoke.py`` drives bert-large at full width
through :func:`main`); on the CPU (``JAX_PLATFORMS=cpu``) the Pallas
kernels run in interpret mode, ``--dist`` runs over fake host devices,
and ``--reduced`` gives the per-arch smoke scale.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpointing
from repro.configs import registry
from repro.core import firstorder, schedule as sched_lib
from repro.core.mkor import MKORConfig, mkor, mkor_h
from repro.core.eva import EvaConfig, eva
from repro.data import pipeline
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib
from repro.sharding import collectives
from repro.sharding import rules
from repro.training import loop as train_lib


# --dist world size on the CPU, where the devices are fake (tests pin the
# same count in tests/conftest.py)
HOST_DIST_DEVICES = 8
# --profile-dir traces the chunks after this many: the first compiles the
# runner, and the trace should hold the steady state only
PROFILE_AFTER = 2


def build_optimizer(name: str, lr, *, inv_freq: int = 10, rank: int = 1,
                    staleness: int = 0, use_pallas: bool = False,
                    platform: str = "", dist=None, health: bool = False,
                    live=None, quant: str = "none"):
    """Returns ``(optimizer, mkor_cfg)`` — ``mkor_cfg`` is None for the
    non-MKOR baselines (the chaos harness needs the config to locate
    injection targets inside the state tree).  ``live`` is the elastic
    liveness mask (DESIGN.md §15): rebuilding with a new mask remaps the
    owner-sharded inversions over the survivors; the state tree is
    mask-independent, so the carried opt state transfers unchanged."""
    # Pallas interpret mode is a testing device, not an execution strategy:
    # a TPU runs the compiled kernels, the CPU interprets them, and any
    # other backend is refused.  The CPU must have been asked for
    # (JAX_PLATFORMS=cpu): JAX falls back to it when a TPU fails to start,
    # and such a run must not pass for one with the kernels on the chip.
    platform = platform or jax.default_backend()
    interpret = use_pallas and platform == "cpu"
    if use_pallas and platform not in ("tpu", "cpu"):
        raise SystemExit(f"--use-pallas: the kernels need a TPU, or the "
                         f"CPU in interpret mode; the backend is {platform}")
    if interpret and "cpu" not in (jax.config.jax_platforms or ""):
        raise SystemExit("--use-pallas on the CPU runs the kernels in "
                         "interpret mode, and only when JAX_PLATFORMS=cpu "
                         "asks for it: without that setting the CPU is "
                         "what JAX falls back to when the TPU fails")
    backend = firstorder.lamb(lr)
    if name == "mkor":
        mcfg = MKORConfig(
            inv_freq=inv_freq, rank=rank, staleness=staleness,
            use_pallas=use_pallas, interpret=interpret, dist=dist,
            health=health, live=live, factor_quant=quant)
        return mkor(backend, mcfg), mcfg
    if name == "mkor_h":
        mcfg = MKORConfig(inv_freq=inv_freq, rank=rank,
                          staleness=staleness, dist=dist, health=health,
                          live=live, factor_quant=quant)
        return mkor_h(backend, mcfg), mcfg
    if name == "eva":
        return eva(backend, EvaConfig()), None
    if name == "lamb":
        return backend, None
    if name == "sgd":
        return firstorder.sgd(lr, momentum=0.9), None
    if name == "adamw":
        return firstorder.adamw(lr), None
    raise ValueError(name)


def resolve_dist_devices(requested) -> int:
    """The --dist world size: every device of an accelerator by default,
    HOST_DIST_DEVICES fake devices on the CPU; never more than exist."""
    platform, have = jax.default_backend(), jax.device_count()
    n = requested or (HOST_DIST_DEVICES if platform == "cpu" else have)
    if n > have:
        raise SystemExit(f"--dist-devices {n}: only {have} {platform} "
                         f"device(s) are present")
    return n


def build_schedule(kind: str, peak: float, steps: int):
    if kind == "constant":
        return sched_lib.constant(peak)
    if kind == "wsd":
        return sched_lib.wsd(peak, max(steps // 10, 1),
                             max(steps * 7 // 10, 1), max(steps // 5, 1))
    if kind == "cosine":
        return sched_lib.warmup_cosine(peak, max(steps // 10, 1), steps)
    if kind == "linear":
        return sched_lib.warmup_linear(peak, max(steps // 10, 1), steps)
    raise ValueError(kind)


def main(argv=None) -> list:
    """Runs one training job; returns the logged metrics history (one dict
    per logged step).  ``argv`` defaults to the command line."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--optimizer", default="mkor",
                    choices=["mkor", "mkor_h", "eva", "lamb", "sgd", "adamw"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", default="cosine",
                    choices=["constant", "wsd", "cosine", "linear"])
    ap.add_argument("--inv-freq", type=int, default=10)
    ap.add_argument("--rank", type=int, default=1,
                    help="block rank-r updates (paper §4): buffer the last "
                         "r stat vectors per factor and consume the window "
                         "with one block-Woodbury update per phase step")
    ap.add_argument("--staleness", type=int, default=0,
                    help="1 = double-buffered inverse banks (DESIGN.md "
                         "§13): the phase-step inversions run one window "
                         "ahead against the pending bank, off the step's "
                         "critical path; 0 = synchronous schedule")
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant of the arch")
    ap.add_argument("--use-pallas", action="store_true",
                    help="MKOR via the Pallas kernels (interpret on CPU)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="steps per jitted lax.scan chunk (1 = legacy "
                         "per-step dispatch); log/ckpt cadence aligns to "
                         "chunk boundaries")
    ap.add_argument("--dist", action="store_true",
                    help="explicit-collective shard_map data-parallel step "
                         "with owner-sharded MKOR inversions (DESIGN.md "
                         "§10); on the CPU it runs over fake host devices")
    ap.add_argument("--dist-devices", type=int, default=None,
                    help="data-parallel world size for --dist (default: "
                         "every device of an accelerator, 8 fake devices "
                         "on the CPU; --global-batch must be a multiple "
                         "of it)")
    ap.add_argument("--quant", default="none",
                    choices=["none", "bf16", "int8"],
                    help="factor residency format (DESIGN.md \u00a716): "
                         "bf16 forces bfloat16 banks/windows; int8 stores "
                         "codes + per-slice scales with fp32 error "
                         "feedback, fused-dequant kernels, and the "
                         "quantized owner-gather wire format")
    ap.add_argument("--health", action="store_true",
                    help="numerical-health sentinel (DESIGN.md §14): "
                         "per-bucket quarantine/recovery of corrupted "
                         "factor state (MKOR optimizers only)")
    ap.add_argument("--chaos", default="",
                    help="deterministic fault injections, e.g. "
                         "'grad_nan@5,factor_inf@15[:bucket]' "
                         "(training/chaos.py; sites: "
                         "grad_nan, factor_inf, window_flip, "
                         "payload_corrupt); MKOR optimizers only. "
                         "Host sites (kill_shard, delay_shard, "
                         "drop_collective; site@step[:shard]) need "
                         "--elastic")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic fault tolerance (DESIGN.md §15; "
                         "training/resilience.py): retry/backoff around "
                         "dispatch, SIGTERM emergency checkpoint, "
                         "straggler EWMAs with owner demotion, and "
                         "kill-shard failover (owner remap + orphan "
                         "quarantine); MKOR optimizers only")
    ap.add_argument("--elastic-slow-factor", type=float, default=2.0,
                    help="straggler policy: demote a shard whose "
                         "step-time EWMA exceeds this multiple of the "
                         "median (--elastic)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-json", default="")
    ap.add_argument("--profile-dir", default="",
                    help="write a profiler trace (.xplane.pb, for xprof or "
                         "TensorBoard) of --profile-chunks chunks, after "
                         "the first two, under this directory")
    ap.add_argument("--profile-chunks", type=int, default=2)
    args = ap.parse_args(argv)
    if args.profile_dir and args.elastic:
        raise SystemExit("--profile-dir traces the plain chunk loop, not "
                         "the --elastic one")
    if args.profile_chunks < 1:
        raise SystemExit("--profile-chunks must be at least 1")

    if args.dist:
        # must precede the backend's start; only the CPU reads it
        mesh_lib.request_host_devices(args.dist_devices or HOST_DIST_DEVICES)
    compile_cache.enable()
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    lr = build_schedule(args.schedule, args.lr, args.steps)
    mesh = dist = None
    if args.dist:
        args.dist_devices = resolve_dist_devices(args.dist_devices)
        if args.global_batch % args.dist_devices:
            raise SystemExit(
                f"--global-batch {args.global_batch} must be a multiple "
                f"of --dist-devices {args.dist_devices}")
        mesh = mesh_lib.make_host_mesh(n_data=args.dist_devices)
        dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    plan = None
    if args.chaos:
        from repro.training import chaos as chaos_lib
        plan = chaos_lib.parse_chaos_spec(args.chaos)
        if plan.host_faults and not args.elastic:
            raise SystemExit("host chaos sites (kill_shard/delay_shard/"
                             "drop_collective) need --elastic")

    def make_optimizer(live=None):
        """(optimizer, mkor_cfg) for a liveness mask — the elastic remap
        rebuild path; the state tree is mask-independent."""
        opt_l, mcfg_l = build_optimizer(
            args.optimizer, lr, inv_freq=args.inv_freq, rank=args.rank,
            staleness=args.staleness, use_pallas=args.use_pallas,
            dist=dist, health=args.health, live=live, quant=args.quant)
        if plan is not None and plan.injections:
            if mcfg_l is None:
                raise SystemExit("--chaos needs an MKOR optimizer (the "
                                 "injection sites live in MKOR state)")
            opt_l = chaos_lib.chaotic(opt_l, plan, mcfg_l)
        return opt_l, mcfg_l

    opt, mcfg = make_optimizer()
    if args.health and mcfg is None:
        raise SystemExit("--health needs an MKOR optimizer")
    if args.elastic and mcfg is None:
        raise SystemExit("--elastic needs an MKOR optimizer (failover "
                         "quarantines MKOR factor state)")

    params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg)
    n_params = model_lib.param_count(params)
    print(f"arch={cfg.name} params={n_params:,} optimizer={args.optimizer} "
          f"steps={args.steps} batch={args.global_batch}x{args.seq_len}"
          + (f" dist={args.dist_devices}x data-parallel" if args.dist
             else ""))

    ds = pipeline.make_dataset(cfg, global_batch=args.global_batch,
                               seq_len=args.seq_len, seed=args.seed)

    def make_runner(live=None):
        """Chunk runner for a liveness mask — rebuilding with a new mask
        is the failover recompile (same state tree, remapped owners).
        Under --elastic the runner keeps its inputs (no donation): a
        retried dispatch must be able to re-present the same buffers."""
        opt_l, _ = make_optimizer(live)
        if args.dist:
            sf = train_lib.make_dist_train_step(cfg, opt_l, mesh)
        else:
            sf = train_lib.make_train_step(cfg, opt_l)
        return train_lib.make_chunk_runner(sf, donate=not args.elastic)

    runner = make_runner()
    opt_state = opt.init(params)

    start = 0
    if args.ckpt_dir:
        # newest VALID checkpoint: a crash mid-save (or corruption caught
        # by the manifest CRCs) rolls back to the previous one instead of
        # killing the restart (DESIGN.md §14).  The state tree is
        # replicated (world-independent), so a W-way owner-sharded
        # checkpoint restores into this run's W'-way world as-is: owner
        # maps re-derive at trace time (elastic resume, DESIGN.md §15).
        restored = checkpointing.restore_latest_valid(
            args.ckpt_dir, (params, opt_state))
        if restored is not None:
            (params, opt_state), meta, latest = restored
            cur = pipeline.cursor_from_metadata(
                meta, fallback_step=int(meta.get("step", latest)) + 1)
            start = cur.step
            from_world = meta.get("world")
            note = ""
            if from_world and from_world != (args.dist_devices
                                             if args.dist else 1):
                note = (f"; elastic resume from world {from_world} into "
                        f"{args.dist_devices if args.dist else 1}")
            print(f"restored checkpoint step {latest} "
                  f"(data cursor {start}{note})")
    if args.dist:
        # Commit the state to the mesh before the first chunk: the runner
        # returns it replicated, and state left on one device would make
        # the second chunk compile the runner a second time.
        params, opt_state = jax.device_put((params, opt_state),
                                           NamedSharding(mesh, P()))

    def make_batch(step: int):
        batch = pipeline.make_batch(ds, step)
        if cfg.is_encoder_decoder:
            batch["frontend_embeds"] = pipeline.encoder_frames(
                cfg, args.global_batch, step, args.seed)
        return batch

    def save_ckpt(next_step: int, p, s, extra=None):
        # metadata carries the data cursor (next UNconsumed batch), so a
        # resumed run never replays a chunk it already trained on
        meta = {"step": next_step - 1,
                "world": args.dist_devices if args.dist else 1,
                "cursor": pipeline.cursor_metadata(
                    pipeline.cursor_for_step(next_step))}
        meta.update(extra or {})
        checkpointing.save(args.ckpt_dir, next_step - 1, (p, s), meta)

    history = []
    t0 = time.time()

    def log_step(step: int, m, force=False):
        if step % args.log_every == 0 or step == args.steps - 1 or force:
            m = dict(m)
            m["step"] = step
            m.setdefault("wall_s", time.time() - t0)
            history.append(m)
            print(f"step {step:5d} loss={m['loss']:.4f} "
                  f"gnorm={m['grad_norm']:.3f} ({m['wall_s']:.1f}s)")

    preempted = False
    if args.elastic:
        from repro.training import resilience
        world = args.dist_devices if args.dist else 1
        supervisor = resilience.ElasticSupervisor(
            world=world,
            monitor=resilience.StragglerMonitor(
                world, slow_factor=args.elastic_slow_factor))
        with resilience.PreemptionGuard() as guard:
            params, opt_state, _, preempted = resilience.elastic_train(
                make_runner, params, opt_state,
                make_batch=make_batch,
                stack_batches=train_lib.stack_batches,
                start=start, steps=args.steps - start, chunk=args.chunk,
                supervisor=supervisor, plan=plan, mcfg=mcfg,
                save=save_ckpt if args.ckpt_dir else None,
                ckpt_every=args.ckpt_every, guard=guard,
                on_metrics=lambda step, hi, m: log_step(step, m))
    else:
        i = start
        # at most two distinct chunk lengths (full + one trailing
        # partial), so the runner compiles at most two traces
        # (train_lib.chunk_schedule)
        chunks = train_lib.chunk_schedule(args.steps - start, args.chunk)
        first = PROFILE_AFTER
        last = min(first + args.profile_chunks, len(chunks)) - 1
        if args.profile_dir and last < first:
            raise SystemExit(f"--profile-dir: the run has {len(chunks)} "
                             f"chunk(s); the trace starts after {first}")
        for c, n in enumerate(chunks):
            if args.profile_dir and c == first:
                jax.profiler.start_trace(args.profile_dir)
            params, opt_state, metrics = train_lib.run_chunk(
                runner, params, opt_state,
                [make_batch(i + k) for k in range(n)], i)
            if args.profile_dir and c == last:
                jax.profiler.stop_trace()
                print(f"profile: chunks {first}-{last} traced under "
                      f"{args.profile_dir}")
            for k in range(n):
                log_step(i + k,
                         {key: float(v[k]) for key, v in metrics.items()})
            prev, i = i, i + n
            if args.ckpt_dir and args.ckpt_every and i < args.steps \
                    and (i // args.ckpt_every) > (prev // args.ckpt_every):
                save_ckpt(i, params, opt_state,
                          {"loss": float(metrics["loss"][n - 1])})
    if args.ckpt_dir and not preempted:
        save_ckpt(args.steps, params, opt_state)
    if args.log_json:
        os.makedirs(os.path.dirname(args.log_json) or ".", exist_ok=True)
        with open(args.log_json, "w") as f:
            json.dump(history, f, indent=1)
    if preempted:
        print("preempted: emergency checkpoint taken, exiting cleanly")
        return history
    final = history[-1]["loss"] if history else float("nan")
    print(f"done: final loss {final:.4f}")
    if not np.isfinite(final):
        raise SystemExit("training diverged")
    return history


if __name__ == "__main__":
    main()
