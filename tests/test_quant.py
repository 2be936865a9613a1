"""Quantized factor storage (DESIGN.md §16): int8/bf16 bank residency,
error feedback, fused-dequant kernel parity, the quantized owner-gather
wire, checkpoint round-trip, and the §14 health interaction.

Contracts under test:
* the encode/decode/requantize primitives honour their error bounds and
  the EF reconstruction invariant;
* the fused kernels with in-kernel dequant (``scale=`` operands) match
  the decode-then-compute jnp oracle;
* factor_quant="bf16" is exactly the shipped bf16 default, and
  factor_quant="int8"+EF converges at ≥ half the fp32 log-loss slope on
  the Fig. 4 autoencoder (ISSUE 10 acceptance);
* the int8 owner-gather ships codes+scales that recombine bit-exactly
  to the local encode, and the wire/HBM byte accounting shows the ~2x
  cut vs bf16;
* checkpoints round-trip codes, scales, AND the EF accumulators
  exactly; a §14 quarantine resets codes to the exact identity, scales
  to 1/127, and zeroes the EF.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import checkpointing
from repro.core import baseline_net, firstorder
from repro.core import stats as statlib
from repro.core.mkor import MKORConfig, manifest_for, mkor
from repro.kernels import ops as kops
from repro.launch import mesh as mesh_lib
from repro.sharding import collectives
from repro.training import chaos

WORLD = 8


def _batch(step, d_in=96):
    rng = np.random.default_rng(step)
    basis = np.random.default_rng(0).standard_normal((8, d_in)) / 3
    x = (rng.standard_normal((64, 8)) @ basis).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(x)}


def _opt(quant, plan=None, **cfg_kw):
    cfg_kw.setdefault("inv_freq", 2)
    cfg = MKORConfig(exclude=(), factor_quant=quant, **cfg_kw)
    opt = mkor(firstorder.sgd(1e-2, momentum=0.9), cfg)
    if plan:
        opt = chaos.chaotic(opt, plan, cfg)
    return opt, cfg


def _jit_step(opt):
    @jax.jit
    def step(params, state, batch):
        loss, grads, stats = baseline_net.grads_and_full_stats(params,
                                                               batch)
        upd, state = opt.update(grads, state, params=params, stats=stats,
                                loss=loss)
        return firstorder.apply_updates(params, upd), state, loss
    return step


def _run(opt, params0, steps):
    step = _jit_step(opt)
    params, state = jax.tree.map(jnp.array, params0), opt.init(params0)
    losses = []
    for i in range(steps):
        params, state, loss = step(params, state, _batch(i))
        losses.append(float(loss))
    return params, state, losses


def _log_loss_slope(losses) -> float:
    y = np.log(np.maximum(np.asarray(losses, np.float64), 1e-30))
    return float(np.polyfit(np.arange(len(y)), y, 1)[0])


def _rand_bank(key, n, d):
    a = jax.random.normal(jax.random.key(key), (n, d, d)) / np.sqrt(d)
    return jax.vmap(lambda x: jnp.linalg.inv(jnp.eye(d) + x @ x.T))(a)


# --------------------------------------------------------------------- #
# Encode / decode / requantize primitives
# --------------------------------------------------------------------- #
def test_quant_encode_error_bounded_by_half_ulp(rng):
    x = jnp.asarray(rng.standard_normal((3, 16, 16)), jnp.float32)
    q, sc = statlib.quant_encode(x)
    assert q.dtype == jnp.int8 and sc.shape == (3,)
    err = jnp.abs(statlib.quant_decode(q, sc) - x)
    assert float(jnp.max(err - sc[:, None, None] / 2)) <= 1e-7


def test_quant_encode_zero_slice_is_exact_zeros():
    x = jnp.zeros((2, 8, 8), jnp.float32)
    q, sc = statlib.quant_encode(x)
    np.testing.assert_array_equal(np.asarray(q), 0)
    assert np.isfinite(np.asarray(sc)).all()
    np.testing.assert_array_equal(
        np.asarray(statlib.quant_decode(q, sc)), 0.0)


def test_quant_requantize_ef_reconstruction_invariant(rng):
    """decode(q', s') + ef' == x + ef exactly — the residual lives in the
    fp32 accumulator, nothing is lost across a requant."""
    x = jnp.asarray(rng.standard_normal((2, 12, 12)), jnp.float32)
    ef = jnp.asarray(rng.standard_normal((2, 12, 12)) * 1e-3, jnp.float32)
    q, sc, ef2 = statlib.quant_requantize(x, ef)
    np.testing.assert_array_equal(
        np.asarray(statlib.quant_decode(q, sc) + ef2), np.asarray(x + ef))
    assert float(jnp.max(jnp.abs(ef2) - sc[:, None, None] / 2)) <= 1e-7


# --------------------------------------------------------------------- #
# Fused-dequant kernel parity (interpret mode) vs the decode oracle
# --------------------------------------------------------------------- #
def test_rank1_kernel_int8_parity():
    bank = _rand_bank(0, 3, 24)
    v = jax.random.normal(jax.random.key(1), (3, 24))
    q, sc = statlib.quant_encode(bank)
    fused = kops.smw_rank1_update_banked(q, v, gamma=0.9, interpret=True,
                                         scale=sc)
    oracle = kops.smw_rank1_update_banked(statlib.quant_decode(q, sc), v,
                                          gamma=0.9, interpret=True)
    assert fused.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)


def test_block_kernel_int8_parity():
    bank = _rand_bank(2, 3, 24)
    win = jax.random.normal(jax.random.key(3), (3, 4, 24))
    nv = jnp.array([0, 2, 4])                       # partial windows too
    q, sc = statlib.quant_encode(bank)
    fused, piv = kops.smw_block_update_banked(
        q, win, nv, gamma=0.9, interpret=True, with_pivot=True, scale=sc)
    oracle, piv_o = kops.smw_block_update_banked(
        statlib.quant_decode(q, sc), win, nv, gamma=0.9, interpret=True,
        with_pivot=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(piv), float(piv_o), rtol=1e-5)


def test_precond_kernel_int8_parity():
    l_bank = _rand_bank(4, 3, 16)
    r_bank = _rand_bank(5, 3, 24)
    g = jax.random.normal(jax.random.key(6), (3, 24, 16))
    lq, lsc = statlib.quant_encode(l_bank)
    rq, rsc = statlib.quant_encode(r_bank)
    fused = kops.fused_precondition_banked(lq, rq, g, interpret=True,
                                           l_scale=lsc, r_scale=rsc)
    oracle = kops.fused_precondition_banked(
        statlib.quant_decode(lq, lsc), statlib.quant_decode(rq, rsc), g,
        interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# Optimizer-level: formats, convergence, state shape
# --------------------------------------------------------------------- #
def test_int8_requires_bank_layout():
    with pytest.raises(ValueError, match="layout='bank'"):
        mkor(firstorder.sgd(1e-2),
             MKORConfig(factor_quant="int8", layout="per_layer"))


def test_bf16_mode_equals_shipped_default(ae_params):
    """factor_quant='bf16' with the default factor_dtype (bfloat16) is the
    identical program — loss trajectories match exactly."""
    opt_none, _ = _opt("none")
    opt_bf16, _ = _opt("bf16")
    _, _, l_none = _run(opt_none, ae_params, 8)
    _, _, l_bf16 = _run(opt_bf16, ae_params, 8)
    np.testing.assert_array_equal(np.asarray(l_none), np.asarray(l_bf16))


def test_int8_state_carries_codes_scales_and_ef(ae_params):
    opt, cfg = _opt("int8")
    state = opt.init(ae_params)
    for bid, bank in state["factor_banks"].items():
        assert set(bank) == {"l_inv", "l_scale", "l_ef",
                             "r_inv", "r_scale", "r_ef"}
        assert bank["l_inv"].dtype == jnp.int8
        assert bank["l_scale"].dtype == jnp.float32
        assert bank["l_ef"].dtype == jnp.float32
        # exact identity init: 127*I codes at scale 1/127
        d = bank["l_inv"].shape[-1]
        dec = statlib.quant_decode(bank["l_inv"], bank["l_scale"])
        np.testing.assert_array_equal(
            np.asarray(dec),
            np.broadcast_to(np.eye(d, dtype=np.float32), dec.shape))
        np.testing.assert_array_equal(np.asarray(bank["l_ef"]), 0.0)


def test_int8_slope_at_least_half_of_fp32(ae_params):
    """ISSUE 10 acceptance: int8+EF keeps ≥ half the fp32 log-loss
    slope on the Fig. 4 autoencoder workload."""
    steps = 30
    opt32, _ = _opt("none", inv_freq=1, factor_dtype="float32")
    opt8, _ = _opt("int8", inv_freq=1)
    _, _, l32 = _run(opt32, ae_params, steps)
    _, state8, l8 = _run(opt8, ae_params, steps)
    assert np.isfinite(l8).all()
    s32, s8 = _log_loss_slope(l32), _log_loss_slope(l8)
    assert s8 <= 0.5 * s32, \
        f"int8 slope {s8:.4f}/step vs fp32 {s32:.4f}/step"
    # the EF accumulators actually engaged (nonzero after requants)
    ef_mag = max(float(jnp.max(jnp.abs(b["l_ef"])))
                 for b in state8["factor_banks"].values())
    assert ef_mag > 0.0


# --------------------------------------------------------------------- #
# Checkpoint round-trip and the §14 health interaction
# --------------------------------------------------------------------- #
def test_checkpoint_roundtrips_codes_scales_ef_exactly(ae_params,
                                                       tmp_path):
    opt, _ = _opt("int8", inv_freq=1)
    _, state, _ = _run(opt, ae_params, 3)
    checkpointing.save(str(tmp_path), 3, state)
    got, _ = checkpointing.restore(str(tmp_path), 3, state)

    def chk(a, b):
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jax.tree.map(chk, got, state)


def test_quarantine_resets_codes_scales_and_zeroes_ef(ae_params):
    """A §14 trip under int8 must land the bucket on the exact identity
    codes (127·I at scale 1/127) with a ZEROED error-feedback
    accumulator — a poisoned residual must not re-inject the corruption
    on the first post-recovery requant (DESIGN.md §16)."""
    inject_at = 5
    plan = chaos.ChaosPlan((chaos.Injection(site="grad_nan",
                                            step=inject_at),))
    opt, cfg = _opt("int8", plan=plan, health=True)
    target = next(iter(manifest_for(ae_params, cfg))).bucket_id

    step = _jit_step(opt)
    params, state = jax.tree.map(jnp.array, ae_params), opt.init(ae_params)
    for i in range(inject_at + 1):
        params, state, loss = step(params, state, _batch(i))
    assert np.isfinite(float(loss))
    assert int(state["health"][target]["trips"]) == 1
    bank = state["factor_banks"][target]
    for side in ("l", "r"):
        d = bank[f"{side}_inv"].shape[-1]
        codes = np.asarray(bank[f"{side}_inv"])
        eye = np.broadcast_to((np.eye(d) * 127).astype(np.int8),
                              codes.shape)
        np.testing.assert_array_equal(codes, eye)
        np.testing.assert_allclose(np.asarray(bank[f"{side}_scale"]),
                                   1.0 / 127.0, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(bank[f"{side}_ef"]), 0.0)


# --------------------------------------------------------------------- #
# Quantized owner-gather wire
# --------------------------------------------------------------------- #
needs_world = pytest.mark.skipif(
    jax.device_count() < WORLD,
    reason=f"needs {WORLD} devices (conftest forces them on the CPU "
           "backend only)")


@needs_world
@pytest.mark.parametrize("n", [8, 12])      # even split + padded chunks
def test_owner_gather_quant_recombines_exactly(rng, n):
    """Each owner encodes its chunk at the wire; the gathered codes and
    scales must equal the local per-slice encode bit-for-bit (wire quant
    IS storage quant — every replica stores identical banks)."""
    d = 16
    mesh = mesh_lib.make_host_mesh(WORLD)
    dist = (("data", WORLD),)
    x = jnp.asarray(rng.standard_normal((n, d, d)), jnp.float32)

    def body(xx):
        return collectives.owner_sharded_map_quant(
            statlib.quant_encode, [xx], dist, n)

    q, sc = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                  out_specs=P(), check_vma=False))(x)
    q_ref, sc_ref = statlib.quant_encode(x)
    assert q.dtype == jnp.dtype(collectives.QUANT_WIRE_DTYPE)
    np.testing.assert_array_equal(np.asarray(q)[:n], np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(sc)[:n], np.asarray(sc_ref),
                               rtol=1e-6)


@needs_world
def test_owner_gather_quant_rejects_wide_codes(rng):
    mesh = mesh_lib.make_host_mesh(WORLD)
    dist = (("data", WORLD),)
    x = jnp.asarray(rng.standard_normal((8, 8, 8)), jnp.float32)

    def body(xx):
        return collectives.owner_sharded_map_quant(
            lambda c: (c, jnp.ones(c.shape[0], jnp.float32)),
            [xx], dist, 8)

    with pytest.raises(TypeError, match="int8"):
        jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))(x)


# --------------------------------------------------------------------- #
# Byte accounting: the ≥~2x HBM and wire cuts
# --------------------------------------------------------------------- #
def test_factor_itemsize_is_config_derived():
    assert statlib.factor_itemsize("bfloat16") == 2
    assert statlib.factor_itemsize("float32", "none") == 4
    assert statlib.factor_itemsize("float32", "bf16") == 2
    assert statlib.factor_itemsize("bfloat16", "int8") == 1


def test_int8_halves_bank_hbm_and_wire_bytes(ae_manifest):
    b = max(ae_manifest, key=lambda bb: bb.d_in * bb.d_out)
    c16 = statlib.bucket_cost(b, statlib.factor_itemsize("bfloat16"))
    c8 = statlib.bucket_cost(b, statlib.factor_itemsize("bfloat16",
                                                        "int8"),
                             factor_quant="int8")
    assert c16["factor_bytes"] == 2 * c8["factor_bytes"]

    w16 = statlib.bucket_comm_cost(b, WORLD, 2, 2)
    w8 = statlib.bucket_comm_cost(b, WORLD, 1, 2, factor_quant="int8")
    ratio = (w16["owner_gather_bytes_per_phase_step"]
             / w8["owner_gather_bytes_per_phase_step"])
    assert ratio > 1.9, ratio     # 2x minus the tiny per-slice scales
    assert w8["owner_gather_scale_bytes_per_phase_step"] > 0
