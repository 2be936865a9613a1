"""Time RWKV-6's wkv recurrence for one layer at a cell's shapes.

    python3 scripts/wkv_timing.py [--batch 2] [--seq 512] [--heads 40] \
        [--chunks 64 128] [--iters 20] [--out chiprun_out/wkv.json]

Times the chunked recurrence (``models/ssm._wkv_chunked``) at each chunk
length, and the per-token scan over ``_wkv_step`` that it replaced: the
forward alone and the forward with the gradient of every input, each
the mean of ``--iters`` calls after a warm one.  Each is also checked
against the per-token scan run in float32 on the host's CPU: the
relative error (max |a - b| over max |b|) of the outputs, the final state
and the gradients, for log w log-uniform in magnitude over [1e-4, 30].
Prints one JSON line.  Needs a TPU (``--cpu`` runs it on the CPU, to
rehearse; its times are the CPU's).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--heads", type=int, default=40)
    ap.add_argument("--chunks", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if not args.cpu:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import ssm

    dev = jax.devices("cpu" if args.cpu else None)[0]
    if not args.cpu and dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX finds {dev.platform}")
    host = jax.devices("cpu")[0]
    b, s, h, n = args.batch, args.seq, args.heads, 64
    rng = np.random.default_rng(0)
    shape = (b, s, h, n)
    inputs = [rng.standard_normal(shape) for _ in range(3)]
    inputs.append(-np.exp(rng.uniform(np.log(1e-4), np.log(30.0), shape)))
    inputs.append(rng.uniform(-1.0, 1.0, (h, n)))
    inputs.append(rng.standard_normal((b, h, n, n)))
    cot = (rng.standard_normal(shape), rng.standard_normal((b, h, n, n)))
    f32 = lambda xs, d: [jax.device_put(np.asarray(x, np.float32), d)
                         for x in xs]

    def per_token(r, k, v, lw, bonus, state):
        seq = tuple(t.transpose(1, 0, 2, 3)
                    for t in (r, k, v, jnp.exp(lw)))
        state, ys = jax.lax.scan(lambda st, t: ssm._wkv_step(st, t, bonus),
                                 state, seq)
        return ys.transpose(1, 0, 2, 3), state

    def with_grads(fn):
        def run(args, cot):
            out, vjp = jax.vjp(fn, *args)
            return out, vjp(tuple(cot))
        return jax.jit(run)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t) / args.iters

    def rel(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    x_dev, c_dev = f32(inputs, dev), f32(cot, dev)
    want = jax.device_get(with_grads(per_token)(f32(inputs, host),
                                                f32(cot, host)))
    want = list(want[0]) + list(want[1])
    variants = {f"chunk{c}": (lambda c: lambda *a: ssm._wkv_chunked(
        *a, chunk=c))(c) for c in args.chunks}
    variants["per_token"] = per_token
    out = {"device_kind": dev.device_kind, "shape": list(shape),
           "iters": args.iters, "variants": {}}
    for name, fn in variants.items():
        fwd = jax.jit(fn)
        both = with_grads(fn)
        got = jax.device_get(both(x_dev, c_dev))
        got = list(got[0]) + list(got[1])
        errs = [rel(g, w) for g, w in zip(got, want)]
        mem = both.lower(x_dev, c_dev).compile().memory_analysis()
        out["variants"][name] = {
            "forward_ms": timed(fwd, *x_dev),
            "forward_backward_ms": timed(both, x_dev, c_dev),
            "rel_err_max": max(errs),
            "rel_err": dict(zip(("y", "state", "r", "k", "v", "log_w",
                                 "bonus", "state0"), errs)),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None)}
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
