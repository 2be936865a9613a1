"""On-chip smoke run: MKOR's main training path on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the --dist step only

One chip runs four phases in this one process (a chip belongs to one
process at a time, so nothing here starts a child):

  (a) device:  platform, kind, count, JAX and libtpu versions;
  (b) kernels: the fused Pallas kernels on the chip against the pure-jnp
      oracles of kernels/ref.py, at bert-large widths, seeded inputs;
  (c) train:   bert-large at full width through launch/train.py's main,
      MKOR on the Pallas kernels: 8 steps of 16x512 tokens, scan chunk 4,
      inversions every 2 steps (so every stagger phase runs an SMW step);
  (d) twin:    the same run on the einsum path, the reference for (c).

``--chips 4`` runs only the ``--dist`` data-parallel step over four chips
(owner-sharded inversions, O(d) stat all-reduce) and the single-device
step on the same global batch, and compares their losses.

Every phase must pass.  The script exits non-zero and prints no result
when JAX finds no TPU, or outside a checkout of the repository.  The last
line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Compiles go to the persistent cache of launch/compile_cache.py.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# (b) max |kernel - ref| / max |ref|, the reference in fp32 at "highest"
# matmul precision.  bf16 outputs carry 2^-8 relative rounding, and an
# fp32 matmul on the MXU at default precision may take bf16 passes; an
# indexing or reduction fault in a kernel is O(1).
KERNEL_TOL = 1e-2
# (c) vs (d): step 0 is the same forward pass on the same parameters, so
# its loss agrees to float rounding.  Later steps differ by the kernels'
# SMW/precondition arithmetic (Mosaic vs XLA matmuls), seen through LAMB
# steps of lr <= 1e-3.
STEP0_RTOL = 1e-6
STEP_RTOL = 1e-2
# --chips 4: the shards' forward is compiled at batch 4 instead of 16 and
# the loss is a mean of four shard means; the rank-1 stats travel in bf16.
DIST_STEP0_RTOL = 1e-4
DIST_STEP_RTOL = 1e-2

GAMMA = 0.9
D_MODEL, D_FF = 1024, 4096                 # bert-large widths
TRAIN_ARGV = ["--arch", "bert-large", "--optimizer", "mkor",
              "--inv-freq", "2", "--global-batch", "16", "--seq-len", "512",
              "--steps", "8", "--chunk", "4", "--log-every", "1",
              "--seed", "0"]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds of XLA compilation (or persistent-cache loads), summed per
    phase from JAX's monitoring events."""

    def __init__(self):
        self.phase = "setup"
        self.seconds = {}

    def __call__(self, event, duration_secs, **kwargs):
        if event == COMPILE_EVENT:
            self.seconds[self.phase] = (self.seconds.get(self.phase, 0.0)
                                        + duration_secs)


def _gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def _peak_hbm() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported"
    return (f"{_gib(stats['peak_bytes_in_use'])} (largest allocation "
            f"{_gib(stats.get('largest_alloc_size', 0))}, limit "
            f"{_gib(stats.get('bytes_limit', 0))})")


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"(a) device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"({d.client.platform_version.strip()})")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform}); no result")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def phase_kernels(failures: list) -> None:
    """Each fused kernel entry of the main path against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.mkor import smw_block_update as einsum_block_update
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def factor(d):
        # symmetric, eigenvalues in about [0.7, 1.3]: a well-posed inverse
        a = jax.random.normal(next(keys), (d, d), jnp.float32) / np.sqrt(d)
        return (jnp.eye(d) + 0.1 * (a + a.T)).astype(jnp.bfloat16)

    def vecs(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(shape[-1]))

    def check(name, err, tol=KERNEL_TOL):
        ok = err <= tol
        print(f"(b) {name}: max|kernel-ref|/max|ref| = {err:.3e} "
              f"(tol {tol:.0e}) {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"kernel {name}: {err:.3e} > {tol:.0e}")

    highest = jax.default_matmul_precision("highest")
    for d in (D_MODEL, D_FF):
        j, v = factor(d), vecs(d)
        got = ops.smw_rank1_update(j, v, gamma=GAMMA)
        with highest:
            want = ref.smw_rank1_update_ref(j, v, GAMMA)
        check(f"smw_rank1_update d={d} bf16", _rel_err(got, want))

    # int8 resident: codes + per-slice scale, dequantized in the kernel
    jf = factor(D_FF).astype(jnp.float32)
    sc = jnp.max(jnp.abs(jf)) / 127.0
    q = jnp.round(jf / sc).astype(jnp.int8)
    v = vecs(D_FF)
    got = ops.smw_rank1_update(q, v, gamma=GAMMA, scale=sc)
    with highest:
        want = ref.smw_rank1_update_quant_ref(q, sc, v, GAMMA)
    check(f"smw_rank1_update d={D_FF} int8 scale=", _rel_err(got, want))

    for r in (2, 8):
        j, v = factor(D_FF), vecs(r, D_FF)
        got, piv = ops.smw_block_update(j, v, gamma=GAMMA, n_valid=r,
                                        with_pivot=True)
        with highest:
            want = ref.smw_block_update_ref(j, v, GAMMA, n_valid=r)
            _, piv_want = einsum_block_update(j, v, GAMMA, n_valid=r,
                                              with_pivot=True)
        # the kernel pads the rank to 8 sublanes; a zero pad row's pivot
        # is gm^2 (paper variant), gm = gamma^r
        if r % 8:
            piv_want = min(float(piv_want), GAMMA ** (2 * r))
        check(f"smw_block_update d={D_FF} r={r} with_pivot",
              _rel_err(got, want))
        check(f"smw_block_update d={D_FF} r={r} min pivot "
              f"{float(piv):.6f} vs {float(piv_want):.6f}",
              _rel_err(piv, piv_want))

    d = 896                                 # a slice the VMEM plan admits
    before = ops.fallback_counts()
    l_inv, r_inv = factor(d), factor(d)
    g = jax.random.normal(next(keys), (d, d), jnp.float32)
    got = ops.fused_precondition(l_inv, r_inv, g)
    if ops.fallback_counts() != before:
        failures.append("fused_precondition 896x896 fell back")
    with highest:
        want = ref.fused_precondition_ref(l_inv, r_inv, g)
    check(f"fused_precondition {d}x{d} fused", _rel_err(got, want))


def run_training(name: str, argv: list, clock: CompileClock) -> dict:
    """One launch/train.py run in this process; returns its history and
    the MKOR configs it built."""
    from repro.kernels import ops
    from repro.launch import train

    built = []
    build = train.build_optimizer

    def recording_build(*args, **kwargs):
        opt, mcfg = build(*args, **kwargs)
        built.append(mcfg)
        return opt, mcfg

    clock.phase = name
    ops.reset_fallback_counts()
    train.build_optimizer = recording_build
    try:
        history = train.main(argv)
    finally:
        train.build_optimizer = build
    losses = [h["loss"] for h in history]
    walls = [h["wall_s"] for h in history]
    chunk = int(argv[argv.index("--chunk") + 1])
    steady = ((walls[-1] - walls[chunk - 1]) / (len(walls) - chunk)
              if len(walls) > chunk else math.nan)
    fallbacks = {f"{k}/{why}": n
                 for (k, why), n in ops.fallback_counts().items()}
    print(f"{name}: {len(losses)} steps, compile "
          f"{clock.seconds.get(name, 0.0):.1f} s, steady step ~{steady:.3f} "
          f"s (host clock, rough), fallback_counts={fallbacks}, "
          f"interpret={[m.interpret for m in built if m is not None]}, "
          f"peak HBM so far {_peak_hbm()}")
    return {"losses": losses, "configs": [m for m in built if m is not None]}


def compare(name: str, got: dict, want: dict, rtol0: float, rtol: float,
            failures: list) -> None:
    a, b = got["losses"], want["losses"]
    if len(a) != len(b):
        failures.append(f"{name}: {len(a)} vs {len(b)} losses")
        return
    for step, (x, y) in enumerate(zip(a, b)):
        tol = rtol0 if step == 0 else rtol
        rel = abs(x - y) / abs(y) if math.isfinite(x * y) else math.inf
        ok = rel <= tol
        print(f"{name} step {step}: loss {x!r} vs {y!r}, rel diff "
              f"{rel:.3e} (tol {tol:.0e}) {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"{name} step {step}: rel diff {rel:.3e}")
    for run in (got, want):
        if not all(math.isfinite(x) for x in run["losses"]):
            failures.append(f"{name}: non-finite loss")
        if not run["configs"] or any(m.interpret for m in run["configs"]):
            failures.append(f"{name}: an MKOR config ran in interpret mode")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the --dist step over four chips against "
                         "the single-device step")
    args = ap.parse_args(argv)

    # JAX must meet the TPU or fail: without this JAX falls back to the
    # CPU when the TPU does not start
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {SRC}; run "
                         f"from a checkout of the repository")
    sys.path.insert(0, SRC)
    import jax
    from repro.launch import compile_cache

    device = phase_device(args.chips)
    print(f"compile cache: {compile_cache.enable()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    failures = []

    if args.chips == 4:
        common = TRAIN_ARGV + ["--use-pallas"]
        dist = run_training("(4) dist x4", common + ["--dist",
                                                     "--dist-devices", "4"],
                            clock)
        single = run_training("(4) single", common, clock)
        compare("(4) dist vs single", dist, single, DIST_STEP0_RTOL,
                DIST_STEP_RTOL, failures)
    else:
        clock.phase = "(b) kernels"
        phase_kernels(failures)
        print(f"(b) compile {clock.seconds.get('(b) kernels', 0.0):.1f} s, "
              f"peak HBM so far {_peak_hbm()}")
        pallas = run_training("(c) train pallas",
                              TRAIN_ARGV + ["--use-pallas"], clock)
        einsum = run_training("(d) twin einsum", TRAIN_ARGV, clock)
        compare("(c) vs (d)", pallas, einsum, STEP0_RTOL, STEP_RTOL,
                failures)

    if failures:
        print("FAILED:\n  " + "\n  ".join(failures))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
