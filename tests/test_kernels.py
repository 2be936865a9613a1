"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
kernels/ref.py, executed in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import matmul as mm
from repro.kernels import ops, ref
from repro.kernels import rank1_smw as rk


def _pd_matrix(key, d, dtype):
    a = jax.random.normal(key, (d, d), jnp.float32) / np.sqrt(d)
    j = jnp.eye(d) + a @ a.T
    return j.astype(dtype)


@pytest.mark.parametrize("d", [8, 64, 128, 256, 384])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matvec_matches_ref(d, dtype):
    j = _pd_matrix(jax.random.key(d), d, dtype)
    v = jax.random.normal(jax.random.key(d + 1), (d, 1), jnp.float32)
    blk = min(d, 128)
    if d % blk:
        pytest.skip("ops.py handles padding; raw kernel needs multiples")
    got = rk.matvec(j, v, block=blk, interpret=True)
    want = ref.matvec_ref(j, v)
    np.testing.assert_allclose(got, want, rtol=2e-2 if dtype == jnp.bfloat16
                               else 1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (64, 128, 32), (128, 64, 256),
                                   (256, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_matches_ref(m, k, n, dtype):
    a = jax.random.normal(jax.random.key(0), (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.key(1), (k, n), jnp.float32).astype(dtype)
    blk = min(m, k, n, 128)
    if m % blk or k % blk or n % blk:
        pytest.skip("raw kernel needs block multiples")
    got = mm.matmul(a, b, block_m=blk, block_n=blk, block_k=blk,
                    interpret=True)
    want = ref.matmul_ref(a, b)
    # fp32 accumulation order differs between the tiled kernel and the
    # reference einsum; bound the error relative to the reduction depth
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("d", [16, 100, 128, 200, 256, 500])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["paper", "exact_smw"])
def test_smw_rank1_update_matches_ref(d, dtype, variant):
    """ops.smw_rank1_update (with padding) vs the oracle, incl. ragged d."""
    j = _pd_matrix(jax.random.key(d), d, dtype)
    v = jax.random.normal(jax.random.key(2 * d), (d,), jnp.float32)
    got = ops.smw_rank1_update(j, v, gamma=0.9, variant=variant,
                               interpret=True)
    want = ref.smw_rank1_update_ref(j, v, 0.9, variant)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("gamma", [0.5, 0.9, 0.99])
def test_smw_rank_r_chaining(gamma):
    """rank-r (paper §4): chained updates == sequential rank-1 updates."""
    d, r = 64, 3
    j = _pd_matrix(jax.random.key(0), d, jnp.float32)
    vs = jax.random.normal(jax.random.key(1), (r, d), jnp.float32)
    got = ops.smw_rank1_update(j, vs, gamma=gamma, interpret=True)
    want = j
    for i in range(r):
        want = ref.smw_rank1_update_ref(want, vs[i], gamma, "paper")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("din,dout", [(32, 48), (100, 64), (128, 128),
                                      (300, 200)])
def test_two_sided_precondition(din, dout):
    g = jax.random.normal(jax.random.key(0), (din, dout), jnp.float32)
    l = _pd_matrix(jax.random.key(1), dout, jnp.float32)
    r = _pd_matrix(jax.random.key(2), din, jnp.float32)
    got = ops.two_sided_precondition(l, r, g, interpret=True)
    want = ref.two_sided_precondition_ref(l, r, g)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_two_sided_precondition_expert_broadcast():
    """Shared factors broadcast over a leading expert dim (MoE, DESIGN §4)."""
    e, din, dout = 4, 32, 48
    g = jax.random.normal(jax.random.key(0), (e, din, dout), jnp.float32)
    l = _pd_matrix(jax.random.key(1), dout, jnp.float32)
    r = _pd_matrix(jax.random.key(2), din, jnp.float32)
    got = ops.two_sided_precondition(l, r, g, interpret=True)
    want = ref.two_sided_precondition_ref(l, r, g)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------- #
# The tiled matmul at its operands' precision (kernels/matmul.py)
# ---------------------------------------------------------------------- #
def _rel_fro(got, want) -> float:
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _operands(m, k, n, a_dtype, b_dtype):
    a = jax.random.normal(jax.random.key(3), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.key(4), (k, n), jnp.float32)
    return a.astype(a_dtype), b.astype(b_dtype)


MATMUL_CASES = [((256, 384, 128), 0), ((300, 200, 100), 0),
                ((256, 384, 256), (128, 128, 128))]


@pytest.mark.parametrize("shape,block", MATMUL_CASES)
def test_matmul_bf16_operands_one_exact_pass(shape, block):
    """bf16 x bf16 goes to the MXU as bf16 (one pass): the products are
    exact, so it matches the fp32 product of the upcast values to fp32
    accumulation."""
    a, b = _operands(*shape, jnp.bfloat16, jnp.bfloat16)
    assert mm.mxu_terms(a.dtype, b.dtype) == 1
    got = ops.pallas_matmul(a, b, block=block, interpret=True)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert _rel_fro(got, want) < 1e-6
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("shape,block", MATMUL_CASES)
@pytest.mark.parametrize("f32_side", ["left", "right"])
def test_matmul_split_f32_operand_keeps_f32_precision(shape, block,
                                                      f32_side):
    """fp32 against bf16: the fp32 tile is split into bf16 terms in the
    kernel.  Against float64 it stays at fp32-level error, about a
    thousand times under one bf16 rounding of the fp32 operand (what
    Mosaic's fp32 dot does on the chip)."""
    dts = ((jnp.float32, jnp.bfloat16) if f32_side == "left"
           else (jnp.bfloat16, jnp.float32))
    a, b = _operands(*shape, *dts)
    assert mm.mxu_terms(a.dtype, b.dtype) == mm.SPLIT_TERMS >= 2
    got = ops.pallas_matmul(a, b, block=block, interpret=True)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert _rel_fro(got, want) < 1e-5
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    one_pass = (np.asarray(a.astype(jnp.bfloat16), np.float64)
                @ np.asarray(b.astype(jnp.bfloat16), np.float64))
    assert _rel_fro(got, want) < _rel_fro(one_pass, want) / 100


@pytest.mark.parametrize("shape,block", MATMUL_CASES)
def test_matmul_f32_operands_keep_f32_dot(shape, block):
    """fp32 x fp32 (fp32 factor configurations, int8 banks dequantized on
    the fallback path) keeps the fp32 dot, unchanged."""
    a, b = _operands(*shape, jnp.float32, jnp.float32)
    assert mm.mxu_terms(a.dtype, b.dtype) == 0
    got = ops.pallas_matmul(a, b, block=block, interpret=True)
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("din,dout", [(128, 384), (384, 128), (256, 256)],
                         ids=["din_lt_dout", "din_gt_dout", "din_eq_dout"])
def test_two_sided_precondition_bf16_both_orders(din, dout):
    """bf16 factors and gradient, as the cells store them: R (G L) when
    d_in < d_out, else (R G) L, both against the fp32 reference."""
    g = jax.random.normal(jax.random.key(0), (din, dout),
                          jnp.float32).astype(jnp.bfloat16)
    l = _pd_matrix(jax.random.key(1), dout, jnp.bfloat16)
    r = _pd_matrix(jax.random.key(2), din, jnp.bfloat16)
    first, second = ops.precondition_matmul_plans(din, dout)
    if din < dout:
        assert (first.dims, second.dims) == ((din, dout, dout),
                                             (din, din, dout))
    else:
        assert (first.dims, second.dims) == ((din, din, dout),
                                             (din, dout, dout))
    got = ops.two_sided_precondition(l, r, g, interpret=True)
    want = ref.two_sided_precondition_ref(l, r, g)
    assert _rel_fro(got, np.asarray(want, np.float64)) < 1e-5
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("din,dout", [(2560, 8960), (8960, 2560),
                                      (2560, 2560)])
def test_precondition_matmul_plans_at_rwkv6_slices(din, dout):
    """At the rwkv6-3b slices each fallback matmul plan takes lane-aligned
    blocks that divide the dims (no padding) and fits its VMEM budget and
    the call's limit; checked on the plans alone."""
    for p in ops.precondition_matmul_plans(din, dout):
        assert p.kernel == "matmul" and p.lane_aligned and p.fits
        assert p.padded == p.dims
        assert all(d % blk == 0 for d, blk in zip(p.dims, p.block))
        assert p.block == (1280, 1280, 1280)
        assert p.vmem_bytes <= ops._vmem_limit(p) < 128 * 2**20
        m, k, n = p.dims
        assert p.grid == (m // 1280, n // 1280, k // 1280)


@pytest.mark.parametrize("variant", ["paper", "exact_smw"])
def test_pallas_path_matches_jnp_path_in_mkor(variant):
    """MKOR with use_pallas=True produces the same update as the jnp path
    in core/mkor.py — for the paper variant AND the beyond-paper exact-SMW
    (the coef/scale pair differs between them)."""
    from repro.core.mkor import smw_rank1_update as jnp_smw
    d = 96
    j = _pd_matrix(jax.random.key(5), d, jnp.float32)
    v = jax.random.normal(jax.random.key(6), (d,), jnp.float32)
    got = ops.smw_rank1_update(j, v, gamma=0.9, variant=variant,
                               interpret=True)
    want = jnp_smw(j, v, 0.9, variant=variant)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------- #
# Fused SMW kernel + factor-bank entry points
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("d,blk", [(64, 64), (256, 128), (256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["paper", "exact_smw"])
def test_fused_smw_kernel_matches_ref(d, blk, dtype, variant):
    """Raw fused kernel (single pallas_call: matvec + s + rank-1 write)
    vs the oracle, at block-multiple dims."""
    j = _pd_matrix(jax.random.key(d), d, dtype)
    v = jax.random.normal(jax.random.key(d + 7), (d, 1), jnp.float32)
    got = rk.fused_smw(j, v, gamma=0.9, variant=variant, block=blk,
                       interpret=True)
    want = ref.smw_rank1_update_ref(j, v[:, 0], 0.9, variant)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("lead", [(3,), (2, 3)])
@pytest.mark.parametrize("variant", ["paper", "exact_smw"])
def test_banked_smw_matches_ref(lead, variant):
    """Bank-dim batched entry (vmapped fused kernel) vs the banked oracle,
    with stacked leading dims and a non-block-multiple d."""
    d = 100
    n = int(np.prod(lead))
    j = jnp.stack([_pd_matrix(jax.random.key(i), d, jnp.float32)
                   for i in range(n)]).reshape(lead + (d, d))
    v = jax.random.normal(jax.random.key(99), lead + (d,), jnp.float32)
    got = ops.smw_rank1_update_banked(j, v, gamma=0.9, variant=variant,
                                      interpret=True)
    want = ref.smw_rank1_update_banked_ref(j, v, 0.9, variant)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_banked_smw_rank_r():
    """Banked entry chains rank-r stats per slice (paper §4)."""
    lead, r, d = (4,), 2, 64
    j = jnp.stack([_pd_matrix(jax.random.key(i), d, jnp.float32)
                   for i in range(4)])
    v = jax.random.normal(jax.random.key(5), lead + (r, d), jnp.float32)
    got = ops.smw_rank1_update_banked(j, v, gamma=0.9, interpret=True)
    want = ref.smw_rank1_update_banked_ref(j, v, 0.9)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------- #
# Fused block rank-r Woodbury kernel (paper §4, DESIGN.md §11)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("d,r", [(64, 2), (100, 3), (128, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", ["paper", "exact_smw"])
def test_fused_block_smw_matches_ref(d, r, dtype, variant):
    """ops.smw_block_update (one pallas_call: r matvecs + r×r Gauss-Jordan
    solve + rank-r axpy, with rank/dim padding) vs the dense oracle."""
    j = _pd_matrix(jax.random.key(d), d, dtype)
    v = jax.random.normal(jax.random.key(d + r), (r, d), jnp.float32)
    got = ops.smw_block_update(j, v, gamma=0.9, variant=variant,
                               interpret=True)
    want = ref.smw_block_update_ref(j, v, 0.9, variant)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


def test_fused_block_smw_equals_chained_rank1():
    """The exact_smw block kernel == r chained rank-1 exact updates — the
    fused dispatch replaces the chain without changing the math."""
    d, r = 64, 4
    j = _pd_matrix(jax.random.key(0), d, jnp.float32)
    v = jax.random.normal(jax.random.key(1), (r, d), jnp.float32)
    got = ops.smw_block_update(j, v, gamma=0.9, variant="exact_smw",
                               interpret=True)
    want = j
    for i in range(r):
        want = ref.smw_rank1_update_ref(want, v[i], 0.9, "exact_smw")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_valid", [0, 1, 2])
def test_fused_block_smw_partial_window(n_valid):
    """Runtime n_valid masks stale ring rows; n_valid=0 is an exact no-op
    (the zero-window edge case, core/mkor.py)."""
    d, r = 64, 3
    j = _pd_matrix(jax.random.key(5), d, jnp.float32)
    v = jax.random.normal(jax.random.key(6), (r, d), jnp.float32)
    got = ops.smw_block_update(j, v, gamma=0.9, variant="exact_smw",
                               n_valid=jnp.asarray(n_valid), interpret=True)
    want = ref.smw_block_update_ref(j, v, 0.9, "exact_smw", n_valid=n_valid)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if n_valid == 0:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(j))


@pytest.mark.parametrize("lead", [(3,), (2, 2)])
def test_fused_block_smw_banked(lead):
    """Banked entry: flattened lead dims vmapped over ONE fused kernel with
    per-slice n_valid — one batched dispatch per bucket per phase step."""
    d, r = 100, 2
    n = int(np.prod(lead))
    j = jnp.stack([_pd_matrix(jax.random.key(i), d, jnp.float32)
                   for i in range(n)]).reshape(lead + (d, d))
    v = jax.random.normal(jax.random.key(50), lead + (r, d), jnp.float32)
    nv = (jnp.arange(n) % (r + 1)).reshape(lead)
    got = ops.smw_block_update_banked(j, v, nv, gamma=0.9,
                                      variant="paper", interpret=True)
    jf = j.reshape((n, d, d))
    vf = v.reshape((n, r, d))
    nf = nv.reshape((n,))
    for i in range(n):
        want = ref.smw_block_update_ref(jf[i], vf[i], 0.9, "paper",
                                        n_valid=int(nf[i]))
        np.testing.assert_allclose(got.reshape((n, d, d))[i], want,
                                   rtol=1e-4, atol=1e-4)
    # one pallas dispatch for the whole bank, r-independent
    jaxpr = str(jax.make_jaxpr(
        lambda a, b, c: ops.smw_block_update_banked(
            a, b, c, gamma=0.9, interpret=True))(j, v, nv))
    assert jaxpr.count("pallas_call") == 1


def test_fused_block_smw_banked_empty_owner_chunk():
    """Owner-sharded dist path hands locally-sliced (possibly empty) bank
    chunks to the banked entry — an empty chunk returns unchanged."""
    d, r = 32, 2
    j = jnp.zeros((0, d, d), jnp.float32)
    v = jnp.zeros((0, r, d), jnp.float32)
    out = ops.smw_block_update_banked(j, v, jnp.zeros((0,), jnp.int32),
                                      gamma=0.9, interpret=True)
    assert out.shape == j.shape


# ---------------------------------------------------------------------- #
# Fused two-sided precondition + rescale kernel (Alg. 1 lines 9-10)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("din,dout", [(32, 48), (64, 64), (100, 64),
                                      (128, 128), (300, 200)])
@pytest.mark.parametrize("rescale", [True, False])
def test_fused_precondition_matches_einsum_reference(din, dout, rescale):
    """ops.fused_precondition (padding wrapper over the 3-pass fused
    kernel) vs core.mkor.precondition + rescale_update — both rescale
    variants, including non-block-multiple dims."""
    from repro.core.mkor import precondition, rescale_update
    g = jax.random.normal(jax.random.key(0), (din, dout), jnp.float32)
    l = _pd_matrix(jax.random.key(1), dout, jnp.float32)
    r = _pd_matrix(jax.random.key(2), din, jnp.float32)
    got = ops.fused_precondition(l, r, g, rescale=rescale, interpret=True)
    want = precondition(l, r, g)
    if rescale:
        want = rescale_update(want, g)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, ref.fused_precondition_ref(
        l, r, g, rescale=rescale), rtol=1e-4, atol=1e-4)


def test_fused_precondition_bf16_factors():
    """bf16 factors (the paper's half precision) through the fused kernel."""
    from repro.core.mkor import precondition, rescale_update
    din, dout = 96, 72
    g = jax.random.normal(jax.random.key(0), (din, dout), jnp.float32)
    l = _pd_matrix(jax.random.key(1), dout, jnp.bfloat16)
    r = _pd_matrix(jax.random.key(2), din, jnp.bfloat16)
    got = ops.fused_precondition(l, r, g, interpret=True)
    want = rescale_update(precondition(l, r, g), g)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_fused_precondition_expert_fallback():
    """Extra leading dims (shared-factor experts) take the fallback path;
    the rescale still spans the whole slice (all dims jointly)."""
    from repro.core.mkor import precondition, rescale_update
    e, din, dout = 3, 32, 48
    g = jax.random.normal(jax.random.key(0), (e, din, dout), jnp.float32)
    l = _pd_matrix(jax.random.key(1), dout, jnp.float32)
    r = _pd_matrix(jax.random.key(2), din, jnp.float32)
    got = ops.fused_precondition(l, r, g, interpret=True)
    want = rescale_update(precondition(l, r, g), g)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fused_precondition_banked():
    """Banked entry: flattened lead dims vmapped over the fused kernel,
    per-slice rescale."""
    from repro.core.mkor import precondition, rescale_update
    n, din, dout = 3, 40, 24
    g = jax.random.normal(jax.random.key(0), (n, din, dout), jnp.float32)
    l = jnp.stack([_pd_matrix(jax.random.key(i), dout, jnp.float32)
                   for i in range(n)])
    r = jnp.stack([_pd_matrix(jax.random.key(10 + i), din, jnp.float32)
                   for i in range(n)])
    got = ops.fused_precondition_banked(l, r, g, interpret=True)
    for i in range(n):
        want = rescale_update(precondition(l[i], r[i], g[i]), g[i])
        np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-4)


def test_fused_precondition_zero_gradient_is_zero():
    """All-zero G: the ε guard in the rescale must return exact zeros
    (no 0/0 NaN), matching rescale_update's documented guard path."""
    din, dout = 32, 32
    g = jnp.zeros((din, dout), jnp.float32)
    l = _pd_matrix(jax.random.key(1), dout, jnp.float32)
    r = _pd_matrix(jax.random.key(2), din, jnp.float32)
    got = ops.fused_precondition(l, r, g, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_pick_block_minimizes_padding():
    """_pick_block picks the MXU-aligned block with the least padded size
    (ties to the larger block), never the old any-block-smaller-than-d
    rule; sub-128 blocks are only allowed for d <= 128 (TPU lane floor)."""
    cases = {
        300: 128,   # old rule: 256 -> pad 512 (~2.9x FLOPs); now 384
        384: 128,   # divides exactly at 128
        512: 256,   # every candidate divides -> largest wins
        1000: 256,  # 1024 either way -> larger block wins the tie
        100: 8,     # old rule: 64 -> pad 128; now 104
        128: 128,
        8: 8,
        260: 128,
    }
    for d, want in cases.items():
        got = ops._pick_block(d)
        assert got == want, (d, got, want)
        padded = -(-d // got) * got
        aligned = (256, 128) if d > 128 else (128, 64, 32, 16, 8)
        for b in aligned:
            assert padded <= -(-d // b) * b, \
                f"d={d}: block {got} pads to {padded}, {b} is tighter"
