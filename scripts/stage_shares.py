"""Device time of a benchmark cell's training step by stage, on the chip.

    python3 scripts/stage_shares.py --workload rwkv6-3b.c512 --seed 7 \
        [--chunks 2] [--out chiprun_out/stages.json]

Builds the cell as ``chipbench/run.py`` does (weights, optimizer and
batch pool from the seed; two warm chunks), traces ``--chunks`` chunks
of the launcher's loop, and attributes each device operation to the
stage its instruction's ``op_name`` names in the compiled chunk
(``repro.scopes.stage_of``: the innermost stage; a forward op under
``transpose(`` is backward; ops without one are "unscoped").  Loops and
calls are left out, their bodies counted.  Prints one JSON object: busy
and window seconds, each stage's seconds and share of busy time, and
every Pallas call by name with its seconds and its result and operand
shapes.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "chipbench"))
sys.path.insert(0, str(ROOT / "src"))

# an instruction of the compiled text and the op_name of its metadata
INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                   re.M)
# the result and operand shapes in an operation's HLO text
SHAPES = re.compile(r"\b(?:bf16|f32|s8|s32)\[[\d,]*\]")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import harness
    import tracefile
    from repro import scopes
    from repro.training import loop

    run = harness.Run(harness.cell_spec(args.workload), args.seed)
    jax = run.jax
    harness.enable_cache(jax)
    opt, _ = run.optimizer()
    params = run.weights()
    opt_state = jax.jit(opt.init, out_shardings=run.replicated())(params)
    drv = harness.Driver(run, run.runner(opt), params, opt_state,
                         run.pool())
    del params, opt_state
    drv.chunk()
    drv.chunk()
    stacked = loop.stack_batches(drv.pool[:run.chunk])
    text = drv.runner.lower(drv.params, drv.opt_state,
                            stacked).compile().as_text()
    op_names = dict(INSTR.findall(text))

    trace_dir = Path(tempfile.mkdtemp(prefix="stages"))
    jax.profiler.start_trace(str(trace_dir))
    for _ in range(args.chunks):
        drv.chunk()
    jax.profiler.stop_trace()
    events = tracefile.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)

    stages, pallas = {}, {}
    for n, _, d in events["devices"]["0"]:
        name, kind = tracefile.op_name(n)
        if kind in tracefile.CONTAINERS:
            continue
        stage = scopes.stage_of(op_names.get(name, "")) or "unscoped"
        stages[stage] = stages.get(stage, 0.0) + d * 1e-9
        if kind == "tpu_custom_call":
            shapes = SHAPES.findall(n.split(", custom_call_target")[0])
            call = pallas.setdefault(name, {"s": 0.0, "shapes": shapes})
            call["s"] += d * 1e-9
    busy, window = tracefile.busy_and_window(events)
    out = {"workload": args.workload, "seed": args.seed,
           "chunks": args.chunks, "steps": args.chunks * run.chunk,
           "device_kind": jax.devices()[0].device_kind,
           "busy_s": busy, "window_s": window,
           "stages": {k: {"s": v, "pct_of_busy": 100 * v / busy}
                      for k, v in sorted(stages.items(),
                                         key=lambda kv: -kv[1])},
           "pallas_calls": dict(sorted(pallas.items(),
                                       key=lambda kv: -kv[1]["s"]))}
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
