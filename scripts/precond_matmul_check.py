"""Precision and time of MKOR's two-matmul precondition on the chip.

    python scripts/precond_matmul_check.py                  # on a TPU
    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/precond_matmul_check.py \
        --interpret --slices 256x384 384x256                # rehearsal

For each (d_in, d_out) slice it makes, from a seed, the inverse factors
R (d_in, d_in) and L (d_out, d_out) and the gradient G (d_in, d_out) in
bfloat16, as the rwkv6-3b cell stores them, and measures against float64
products on the host:

- ``chain``: ``ops.two_sided_precondition`` (R G L, in whichever order
  it picks) against the float64 R G L;
- each product the chain can be made of, through ``ops.pallas_matmul``:
  R G and G L (bfloat16 by bfloat16), T L with T = R G rounded to
  float32 (float32 left), and R U with U = G L rounded to float32
  (float32 right), each against the float64 product of its own inputs.

Errors are the relative Frobenius norm ``|got - want| / |want|`` and the
largest absolute entry of ``got - want`` (with ``max|want|`` beside it).
Times are per call: ``--reps`` calls queued back to back after a
warm-up and ended by ``block_until_ready``, the median of three such
rounds.  One JSON line per slice, then one summary line.

``--terms N ...`` measures once for each N, with an fp32 operand split
into N bf16 terms (``kernels/matmul.SPLIT_TERMS``) in place of the
kernel's own count.
``--blocks bm,bk,bn ...`` instead times ``ops.pallas_matmul`` with each
forced block triple on every product of the cell's slices that the
blocks divide, one JSON line per product and triple.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the rwkv6-3b cell's three slice shapes (time mix, channel-mix key,
# channel-mix value)
CELL_SLICES = ("2560x2560", "2560x8960", "8960x2560")


def _errors(got, want):
    import numpy as np
    diff = np.asarray(got, np.float64) - want
    return {"rel_fro": float(np.linalg.norm(diff) / np.linalg.norm(want)),
            "max_abs": float(np.max(np.abs(diff))),
            "ref_max_abs": float(np.max(np.abs(want)))}


def _time_ms(fn, reps, rounds=3):
    """Median over ``rounds`` of the mean time of ``reps`` calls queued
    back to back (so host dispatch overlaps the device)."""
    import jax
    jax.block_until_ready(fn())
    ts = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready([fn() for _ in range(reps)])
        ts.append((time.perf_counter() - t0) * 1e3 / reps)
    return statistics.median(ts)


def check_slice(d_in, d_out, *, seed, reps, interpret, terms=(0,)):
    """One JSON-able row per entry of ``terms`` (0: the kernel's own
    ``SPLIT_TERMS``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import matmul, ops

    k = jax.random.split(jax.random.PRNGKey(seed), 3)

    def factor(key, d):
        # symmetric, eigenvalues in about [0.7, 1.3], as chip_smoke.py's
        a = jax.random.normal(key, (d, d), jnp.float32) / np.sqrt(d)
        return (jnp.eye(d) + 0.1 * (a + a.T)).astype(jnp.bfloat16)

    r, l = factor(k[0], d_in), factor(k[1], d_out)
    g = jax.random.normal(k[2], (d_in, d_out), jnp.float32).astype(
        jnp.bfloat16)
    r64, l64, g64 = (np.asarray(x, np.float64) for x in (r, l, g))
    rg64, gl64 = r64 @ g64, g64 @ l64
    t32 = jnp.asarray(rg64.astype(np.float32))
    u32 = jnp.asarray(gl64.astype(np.float32))
    products = {
        "RG_bf16_bf16": (r, g, rg64),
        "GL_bf16_bf16": (g, l, gl64),
        "TL_f32_bf16": (t32, l, np.asarray(t32, np.float64) @ l64),
        "RU_bf16_f32": (r, u32, r64 @ np.asarray(u32, np.float64)),
    }
    want = r64 @ gl64
    own = matmul.SPLIT_TERMS
    rows = []
    for n in terms:
        matmul.SPLIT_TERMS = n or own
        mm = jax.jit(lambda a, b: ops.pallas_matmul(a, b,
                                                    interpret=interpret))
        chain = jax.jit(lambda ll, rr, gg: ops.two_sided_precondition(
            ll, rr, gg, interpret=interpret))
        out = {"slice": [d_in, d_out], "terms": matmul.SPLIT_TERMS}
        out["chain"] = _errors(chain(l, r, g), want)
        out["chain"]["ms"] = _time_ms(lambda: chain(l, r, g), reps)
        for name, (a, b, ref) in products.items():
            out[name] = _errors(mm(a, b), ref)
            out[name]["ms"] = _time_ms(lambda a=a, b=b: mm(a, b), reps)
        rows.append(out)
    matmul.SPLIT_TERMS = own
    return rows


def sweep_blocks(blocks, *, reps):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    bf, f32 = jnp.bfloat16, jnp.float32
    # (m, k, n, a dtype, b dtype): the two products of each cell slice,
    # in the order two_sided_precondition takes them
    shapes = []
    for s in CELL_SLICES:
        d_in, d_out = (int(x) for x in s.split("x"))
        if ops._right_first(d_in, d_out):
            shapes += [(d_in, d_out, d_out, bf, bf), (d_in, d_in, d_out, bf, f32)]
        else:
            shapes += [(d_in, d_in, d_out, bf, bf), (d_in, d_out, d_out, f32, bf)]
    key = jax.random.PRNGKey(0)
    for m, k, n, ad, bd in shapes:
        a = jax.random.normal(key, (m, k), f32).astype(ad)
        b = jax.random.normal(key, (k, n), f32).astype(bd)
        for blk in blocks:
            if any(d % x for d, x in zip((m, k, n), blk)):
                continue
            row = {"product": [m, k, n],
                   "dtypes": [jnp.dtype(ad).name, jnp.dtype(bd).name],
                   "block": list(blk)}
            fn = jax.jit(lambda a, b, blk=blk: ops.pallas_matmul(
                a, b, block=blk))
            try:
                row["ms"] = _time_ms(lambda: fn(a, b), reps)
                row["tflops"] = 2 * m * k * n / row["ms"] / 1e9
            except Exception as e:  # noqa: BLE001 - a refused tiling
                row["error"] = str(e)[-300:]
            print(json.dumps(row), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slices", nargs="+", default=list(CELL_SLICES),
                    help="d_inxd_out slices (default: the cell's three)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode (a CPU rehearsal)")
    ap.add_argument("--terms", type=int, nargs="+", default=[0],
                    help="bf16 terms of a split fp32 operand, one "
                         "measurement each (0: the kernel's own)")
    ap.add_argument("--blocks", nargs="+", default=(),
                    help="bm,bk,bn triples to time instead")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.interpret:
        raise SystemExit(f"precond_matmul_check: no TPU (platform "
                         f"{dev.platform}); pass --interpret on the CPU")
    if args.blocks:
        sweep_blocks([tuple(int(x) for x in b.split(","))
                      for b in args.blocks], reps=args.reps)
        return
    for s in args.slices:
        d_in, d_out = (int(x) for x in s.split("x"))
        for row in check_slice(d_in, d_out, seed=args.seed, reps=args.reps,
                               interpret=args.interpret, terms=args.terms):
            print(json.dumps(row), flush=True)
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "jax": jax.__version__, "slices": args.slices}))


if __name__ == "__main__":
    main()
