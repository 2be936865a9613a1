"""Runs one benchmark cell once and prints its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

--trace 0 measures the cell's end-to-end metrics with the profiler off;
--trace 1 traces a few chunks and reports the per-layer metrics.  Both
check the first chunk against the plain reference (``correct``).  The
run needs a TPU with as many chips as the cell asks for; without one it
exits with code 3 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.cell_spec(args.workload)
    os.environ["JAX_PLATFORMS"] = "tpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # no logs in /tmp
    try:
        import jax
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no TPU: {e}", file=sys.stderr)
        return 3
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"the cell needs {cell['chips']} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    if not (BENCH.parent / "src" / "repro").is_dir():
        print("no program under src/repro next to the benchmark",
              file=sys.stderr)
        return 3

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = harness.execute(cell, args.seed, args.seconds,
                             bool(args.trace), T0, log=log)
    for name, row in result["check"].items():
        if not math.isfinite(row["value"]):
            row["value"] = None                 # JSON has no inf or nan
            result["correct"] = False
        log(f"check {name} {row['value']!r} limit {row['limit']!r}")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
