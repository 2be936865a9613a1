"""Records the fixture the stage attribution is tested on: one chunk of
the rwkv6-3b.c512 cell's program at a small width, traced on a TPU, with
the compiled chunk's text beside its trace.

    python3 chipbench/tests/record_stage_trace.py \
        [--out chipbench/tests/data/rwkv6-3b.small.stages.json.gz]

The file holds ``tracefile.load``'s event lists, ``"hlo"``, the text,
and ``"ctx"``, what the kernel readers take besides them: the traced
chunk's kernel work (``harness.kernel_work``), the chip's peaks and the
configuration's sizes (``harness.sizes``).  Needs a TPU.
"""
import argparse
import gzip
import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
OUT = BENCH / "tests" / "data" / "rwkv6-3b.small.stages.json.gz"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(OUT))
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import counts
    import harness
    import tracefile
    from repro.training import loop

    cell = harness.cell_spec("rwkv6-3b.c512")
    cell["model"].update(hidden_size=256, intermediate_size=512,
                         num_hidden_layers=2, vocab_size=2048)
    cell["load"].update(seq_len=64, chunk=2)
    # every bucket's phase step falls in the traced chunk
    cell["load"]["optimizer"]["inv_freq"] = 2
    run = harness.Run(cell, 2 ** 31 + 1)
    jax = run.jax
    opt, mcfg = run.optimizer()
    params = run.weights()
    drv = harness.Driver(run, run.runner(opt), params,
                         jax.jit(opt.init)(params), run.pool())
    del params
    drv.chunk()
    drv.chunk()
    text = drv.runner.lower(drv.params, drv.opt_state, loop.stack_batches(
        drv.pool[:run.chunk])).compile().as_text()
    ctx = {"work": harness.kernel_work(run, mcfg, drv.params,
                                       drv.next * run.chunk, run.chunk),
           "peaks": counts.peaks(jax.devices()[0].device_kind),
           "sz": run.sz}
    trace_dir = harness.TRACE_DIR
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    drv.chunk()
    jax.profiler.stop_trace()
    events = tracefile.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump({**events, "hlo": text, "ctx": ctx}, f)
    stages = tracefile.stage_seconds(events, tracefile.op_names(text))
    print(json.dumps({"out": args.out, "ops": len(events["devices"]["0"]),
                      "hlo_chars": len(text), "stages": stages,
                      "work": ctx["work"]}))


if __name__ == "__main__":
    main()
