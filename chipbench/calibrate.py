"""Readings of the check's numbers over many seeds, in one process, for
setting a cell's limits (never run by the benchmark itself).

    python3 chipbench/calibrate.py --config rwkv6-3b --traffic c512 \
        --chips 1 --seeds 12 [--control fp8] [--fault half_batch] \
        [--out chiprun_out/calib.jsonl]

For each seed it builds the cell's run from the seed, drives its first
chunk through the timed path, reads the program's numbers, and compares
them with the plain reference over the same chunk (the reference runs
once per seed and is reused).  ``--control fp8`` puts the reference,
trained in fp8 (one step below the bfloat16 the configuration states;
``harness.CONTROLS``), in the program's place on the first seeds;
``--fault`` plants a fault of ``faults.py`` under the timed path.  One
JSON line per reading.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def cell_from_files(config: str, traffic: str, chips: int) -> dict:
    import harness
    name = f"{config}.{traffic}"
    limits = BENCH / "limits" / f"{name}.json"
    return {"name": name, "config": config, "traffic": traffic,
            "chips": chips,
            "model": harness.load_json(BENCH / "configs" / f"{config}.json"),
            "load": harness.load_json(BENCH / "traffic" / f"{traffic}.json"),
            "limits": harness.load_json(limits) if limits.exists() else {},
            "per_layer": [], "end_to_end": []}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_017)
    ap.add_argument("--control", default="")
    ap.add_argument("--skip-program", action="store_true")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        os.environ["JAX_PLATFORMS"] = "tpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import check
    import faults
    import harness

    cell = cell_from_files(args.config, args.traffic, args.chips)
    harness.enable_cache(jax)
    out = open(args.out, "a") if args.out else None
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    refs = {}

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def reference(run, seed, params0):
        if seed not in refs:
            t_ref = time.perf_counter()
            refs[seed] = harness.reference_numbers(
                run, params0, run.pool()[:run.chunk])
            refs["t_" + str(seed)] = time.perf_counter() - t_ref
        return refs[seed]

    def row(variant, seed, prog, **extra):
        g = check.gaps(prog, refs[seed])
        emit({"cell": cell["name"], "variant": variant, "seed": seed,
              **extra,
              "reference_s": refs["t_" + str(seed)],
              **{k: v[0] for k, v in g.items()},
              "at": {k: v[1] for k, v in g.items()},
              "loss0": prog["loss"][0]})

    def readings(variant, seeds, *, breakage=None):
        run = harness.Run(cell, seeds[0], breakage=breakage)
        opt, mcfg = run.optimizer()
        runner = run.runner(opt)
        for seed in seeds:
            t = time.perf_counter()
            run.reseed(seed)
            drv, prog, params0, setup_s = harness.start(
                run, opt, mcfg, runner, t, log=lambda m: None)
            drv.params = drv.opt_state = None
            del drv
            reference(run, seed, params0)
            row(variant, seed, prog, setup_s=setup_s)

    def control(name, seeds):
        run = harness.Run(cell, seeds[0])
        for seed in seeds:
            run.reseed(seed)
            params0 = jax.device_get(run.weights())
            reference(run, seed, params0)
            t = time.perf_counter()
            prog = harness.reference_numbers(
                run, params0, run.pool()[:run.chunk], control=name)
            row(f"control_{name}", seed, prog,
                control_s=time.perf_counter() - t)

    if not args.skip_program:
        readings("program", seeds)
    if args.control:
        control(args.control, seeds[:args.control_seeds])
    for name in args.fault:
        readings(f"fault_{name}", seeds[:args.control_seeds],
                 breakage=faults.FAULTS[name])
    if out:
        out.close()
    print(f"calibrate done in {time.perf_counter() - T0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
