"""mkor-lint CLI: ``python -m repro.analysis.lint --config NAME [--dist]``.

Traces the real train-step entry points for a registry config and runs
the static contract checkers (checkers.py); exits 1 iff any ERROR-level
diagnostic.  Everything is abstract (eval_shape + make_jaxpr + lowering)
— no parameters are allocated and no step runs, so linting bert-large
takes seconds.  ``--compile`` additionally compiles the dist step and
recounts collectives in the optimized (post-SPMD) HLO — slower, but it
catches anything the partitioner re-introduces.
"""
from __future__ import annotations

import argparse
import os
import sys

def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", required=True,
                    help="registry arch id (bert_large / bert-large)")
    ap.add_argument("--dist", action="store_true",
                    help="also lint the explicit-collective shard_map "
                         "step (comm-linearity runs only here)")
    ap.add_argument("--dist-devices", type=int, default=8)
    ap.add_argument("--reduced", action="store_true",
                    help="lint the smoke-scale variant of the arch")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=16,
                    help="small by default: the factor dims the lints "
                         "check are batch/seq independent")
    ap.add_argument("--rank", type=int, default=1)
    ap.add_argument("--inv-freq", type=int, default=10)
    ap.add_argument("--staleness", type=int, default=1,
                    help="also lint the async double-buffered step at "
                         "this staleness bound (0 skips the async "
                         "targets; the sync targets always run)")
    ap.add_argument("--health", type=int, default=1,
                    help="1 (default) also lints the numerical-health "
                         "sentinel twins (health-gating proves the "
                         "sentinel adds zero ungated wire traffic); "
                         "0 skips them")
    ap.add_argument("--elastic", type=int, default=1,
                    help="1 (default, needs --dist) also lints the "
                         "elastic-remapped dist step — one worker dead, "
                         "ownership re-split over survivors "
                         "(elastic-remap proves the remap adds zero "
                         "ungated factor bytes vs the static owner "
                         "map); 0 skips it")
    ap.add_argument("--quant", type=int, default=1,
                    help="1 (default) also lints the int8 factor-"
                         "residency twins (quant-discipline proves the "
                         "owner-gather wire payload is int8-origin and "
                         "accumulation stays fp32, DESIGN.md \u00a716); "
                         "0 skips them")
    ap.add_argument("--chunk", type=int, default=2)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--compile", action="store_true",
                    help="compile the dist step and recount collectives "
                         "in the optimized HLO (slow on CPU)")
    ap.add_argument("--checkers", nargs="*", default=None,
                    help="subset of checkers to run (default: all)")
    ap.add_argument("--json", default="",
                    help="also write the report as JSON to this path")
    args = ap.parse_args()

    # A CPU-only tool: pin the CPU, and give --dist its fake host devices,
    # before the deferred imports below start jax.
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.dist and "--xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.dist_devices} "
            + os.environ.get("XLA_FLAGS", ""))
    import dataclasses

    from repro.analysis import trace
    from repro.analysis.checkers import run_checkers
    from repro.core.mkor import MKORConfig

    mkor_cfg = MKORConfig(inv_freq=args.inv_freq, rank=args.rank)
    common = dict(mkor_cfg=mkor_cfg, global_batch=args.global_batch,
                  seq_len=args.seq_len, reduced=args.reduced)
    async_cfg = dataclasses.replace(mkor_cfg, staleness=args.staleness)
    async_common = dict(common, mkor_cfg=async_cfg)

    health_cfg = dataclasses.replace(mkor_cfg, health=True)
    health_common = dict(common, mkor_cfg=health_cfg)

    quant_cfg = dataclasses.replace(mkor_cfg, factor_quant="int8")
    quant_common = dict(common, mkor_cfg=quant_cfg)

    targets = []
    print(f"mkor-lint: tracing {args.config} (single + chunk"
          + (" + dist" if args.dist else "")
          + (f", sync + async staleness={args.staleness}"
             if args.staleness else "")
          + (", + health twins" if args.health else "")
          + (", + int8 quant twins" if args.quant else "")
          + (", + elastic remap twin"
             if args.elastic and args.dist else "") + ") ...",
          flush=True)
    targets.append(trace.single_target(args.config, **common))
    targets.append(trace.chunk_target(args.config, chunk=args.chunk,
                                      steps=args.steps, **common))
    if args.staleness:
        # async twins: staleness-bound runs on these, and the async chunk
        # runner must still donate its (now double-buffered) carry
        targets.append(trace.single_target(args.config, **async_common))
        targets.append(trace.chunk_target(args.config, chunk=args.chunk,
                                          steps=args.steps, **async_common))
    if args.health:
        # health twin: health-gating runs on this (single-program: proves
        # the sentinel stays collective-free; the dist twin below gets
        # the differential baseline)
        targets.append(trace.single_target(args.config, **health_common))
    if args.quant:
        # int8 twin: quant-discipline runs on this (and on the dist twin
        # below, where the owner-gather wire format is actually visible)
        targets.append(trace.single_target(args.config, **quant_common))
    if args.dist:
        sync_dist = trace.dist_target(
            args.config, world=args.dist_devices,
            compile_hlo=args.compile, **common)
        targets.append(sync_dist)
        if args.staleness:
            async_dist = trace.dist_target(
                args.config, world=args.dist_devices,
                compile_hlo=args.compile, **async_common)
            # differential baseline: async must add zero ungated bytes
            targets.append(trace.attach_sync_baseline(async_dist,
                                                      sync_dist))
        if args.health:
            health_dist = trace.dist_target(
                args.config, world=args.dist_devices,
                compile_hlo=args.compile, **health_common)
            # differential baseline: the sentinel must add zero ungated
            # collectives/bytes over the health-off step
            targets.append(trace.attach_health_baseline(health_dist,
                                                        sync_dist))
        if args.quant:
            targets.append(trace.dist_target(
                args.config, world=args.dist_devices,
                compile_hlo=args.compile, **quant_common))
        if args.elastic:
            # remap twin: last worker dead, ownership re-split over the
            # survivors; elastic-remap proves the failover step adds
            # zero ungated collectives/bytes vs the static owner map
            live = (True,) * (args.dist_devices - 1) + (False,)
            remap_dist = trace.dist_target(
                args.config, world=args.dist_devices, live=live,
                compile_hlo=args.compile, **common)
            targets.append(trace.attach_static_owner_baseline(remap_dist,
                                                              sync_dist))

    report = run_checkers(targets, names=args.checkers)
    print(report.render())
    if args.json:
        report.to_json(args.json)
        print(f"wrote {args.json}")
    return report.exit_code()


if __name__ == "__main__":
    sys.exit(main())
