"""Pallas TPU kernels for MKOR's O(d²) hot loop (Alg. 1 lines 7-8).

The SM rank-1 inverse update

    u = J⁻¹ v;   s = vᵀu;   J⁻¹ ← γ J⁻¹ + coef(s) · u uᵀ

is re-blocked for the TPU memory hierarchy (DESIGN.md §3):

* ``fused_smw``: the whole update in ONE ``pallas_call`` with a two-pass
  grid ``(2, d/B, d/B)``.  Pass 0 accumulates  u  into a persistent VMEM
  scratch and the scalar  s  into SMEM tile-by-tile; pass 1 re-streams each
  J tile and writes  scale·J + coef(s)·u_i u_kᵀ.  u and s never round-trip
  through HBM and there is a single kernel dispatch per factor (the
  separate matvec + rank1_update pair costs two dispatches plus an HBM
  round-trip for u).
* ``fused_block_smw``: the rank-r generalization (paper §4, DESIGN.md
  §11) on the same grid — pass 0 accumulates  U = JṼᵀ (d, r)  and the
  Gram matrix  S = ṼJṼᵀ (r, r)  in VMEM, the first pass-1 tile inverts
  the r×r mid matrix in-register (unrolled Gauss–Jordan; PD by the block
  Lemma 3.1, so no pivoting), and every pass-1 tile writes the rank-r
  axpy.  One dispatch per factor regardless of r, vs r chained
  ``fused_smw`` dispatches.
* ``matvec``: row-tiled mat-vec with fp32 accumulation across the column
  grid — each (BR, BC) tile of J streams HBM→VMEM once; u lives in VMEM.
* ``rank1_update``: writes  γ·J_tile + coef·u_r u_cᵀ  tile-by-tile; the
  d×d outer product is never materialised in HBM as a separate array, and
  J stays in bf16 end-to-end (the paper's half-precision factors).

Tiles are 128-aligned for the MXU/VPU; callers pad to multiples of the
block size (kernels/ops.py).  Validated against kernels/ref.py in
interpret mode on CPU (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes

DEFAULT_BLOCK = 256


def _matvec_kernel(j_ref, v_ref, u_ref):
    """Grid (rows, cols): u[rows] += J[rows, cols] @ v[cols]."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    u_ref[...] += jnp.dot(
        j_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)


def matvec(j: jnp.ndarray, v: jnp.ndarray, *, block: int = DEFAULT_BLOCK,
           interpret: bool = False) -> jnp.ndarray:
    """u = J @ v.  J: (d, d) any dtype; v: (d, 1) fp32 → u (d, 1) fp32."""
    d = j.shape[0]
    assert d % block == 0, f"pad to block multiple ({d} % {block})"
    grid = (d // block, d // block)
    return pl.pallas_call(
        _matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, block), lambda i, k: (i, k)),
            pl.BlockSpec((block, 1), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
        interpret=interpret,
    )(j, v)


def _rank1_update_kernel(j_ref, ur_ref, uc_ref, coef_ref, out_ref, *,
                         gamma: float):
    """out_tile = γ·J_tile + coef · u_r u_cᵀ  (coef in SMEM-style (1,1))."""
    coef = coef_ref[0, 0]
    outer = jnp.dot(ur_ref[...].astype(jnp.float32),
                    uc_ref[...].astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)
    out_ref[...] = (gamma * j_ref[...].astype(jnp.float32)
                    + coef * outer).astype(out_ref.dtype)


def rank1_update(j: jnp.ndarray, u: jnp.ndarray, coef: jnp.ndarray, *,
                 gamma: float, block: int = DEFAULT_BLOCK,
                 interpret: bool = False) -> jnp.ndarray:
    """J ← γJ + coef·uuᵀ without materialising uuᵀ in HBM."""
    d = j.shape[0]
    assert d % block == 0
    grid = (d // block, d // block)
    coef = jnp.asarray(coef, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_rank1_update_kernel, gamma=gamma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, block), lambda i, k: (i, k)),
            pl.BlockSpec((block, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((block, 1), lambda i, k: (k, 0)),
            pl.BlockSpec((1, 1), lambda i, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, k: (i, k)),
        out_shape=jax.ShapeDtypeStruct((d, d), j.dtype),
        interpret=interpret,
    )(j, u, u, coef)


def smw_vectors(j: jnp.ndarray, v: jnp.ndarray, *, block: int = DEFAULT_BLOCK,
                interpret: bool = False):
    """(u, s) = (J v, vᵀ J v) — the two O(d²)/O(d) pieces of Eq. 5/6."""
    u = matvec(j, v, block=block, interpret=interpret)
    s = jnp.vdot(v[:, 0], u[:, 0])
    return u, s


# ----------------------------------------------------------------------- #
# Fused SMW: matvec + scalar + rank-1 write in one pallas_call
# ----------------------------------------------------------------------- #
def _fused_smw_kernel(j_ref, vr_ref, vc_ref, *refs,
                      gamma: float, variant: str, block: int,
                      quant: bool = False):
    """Two-pass grid (pass, rows, cols).

    Pass 0: u[rows] += J[rows, cols] @ v[cols]  into the persistent VMEM
    scratch, and  s += v[rows]ᵀ (J[rows, cols] v[cols])  into SMEM — the
    tile-local partials of  s = vᵀJv  sum to the exact total because the
    grid covers every tile exactly once.
    Pass 1: out[rows, cols] = scale·J + coef(s)·u_rows u_colsᵀ, with the
    coefficient math (Lemma 3.1 positive denominator) done in fp32 on the
    scalar unit.  u lives in VMEM for the whole grid; only J tiles stream.

    ``quant`` adds a (1, 1) fp32 per-slice scale input after the v pair
    (DESIGN.md §16): J arrives int8 and every tile load dequantizes in
    VMEM — the fp32 factor never exists in HBM.
    """
    refs = list(refs)
    sc_ref = refs.pop(0) if quant else None
    out_ref, u_ref, s_ref = refs
    p, i, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def _j_tile():
        jf = j_ref[...].astype(jnp.float32)
        return jf * sc_ref[0, 0] if quant else jf

    @pl.when(p == 0)
    def _accumulate():
        t = jnp.dot(_j_tile(), vc_ref[...],
                    preferred_element_type=jnp.float32)

        @pl.when(k == 0)
        def _init_u():
            u_ref[pl.ds(i * block, block), :] = jnp.zeros_like(t)

        u_ref[pl.ds(i * block, block), :] += t

        @pl.when((i == 0) & (k == 0))
        def _init_s():
            s_ref[0, 0] = 0.0

        s_ref[0, 0] += jnp.sum(vr_ref[...] * t)

    @pl.when(p == 1)
    def _write():
        s = s_ref[0, 0]
        if variant == "paper":
            scale = gamma
            coef = (1.0 - gamma) / (
                gamma ** 2 * (1.0 + gamma * (1.0 - gamma) * s))
        elif variant == "exact_smw":
            scale = 1.0 / gamma
            coef = -(1.0 - gamma) / (gamma * (gamma + (1.0 - gamma) * s))
        else:
            raise ValueError(variant)
        outer = jnp.dot(u_ref[pl.ds(i * block, block), :],
                        u_ref[pl.ds(k * block, block), :].T,
                        preferred_element_type=jnp.float32)
        out_ref[...] = (scale * _j_tile()
                        + coef * outer).astype(out_ref.dtype)


def _fused_block_smw_kernel(j_ref, vr_ref, vc_ref, gm_ref,
                            *refs, variant: str, block: int, rank: int,
                            with_pivot: bool = False, quant: bool = False):
    """Two-pass grid (pass, rows, cols) — the block rank-r SMW update
    (DESIGN.md §11) in ONE dispatch.

    Pass 0 accumulates the r matvecs  U = J Ṽᵀ (d, r)  into a persistent
    VMEM scratch and the Gram matrix  S = Ṽ J Ṽᵀ (r, r)  tile-by-tile
    (Ṽ rows arrive pre-weighted by √w_i — ops.py).  At the first pass-1
    tile the r×r mid matrix  A(gm, S)  is inverted in-register with an
    unrolled Gauss–Jordan (A is PD by Lemma 3.1's block generalization, so
    no pivoting; rank is tiny and static) into m_ref; every pass-1 tile
    then re-streams its J tile and writes the rank-r axpy

        paper:      out = gm·J + U_i M U_kᵀ,   A = gm²I + gm³S
        exact_smw:  out = (J − U_i M U_kᵀ)/gm, A = gm·I + S

    U, S, and M never round-trip through HBM; gm = γ^m is a runtime scalar
    (the window may be partially filled).

    ``with_pivot`` adds a second (1, 1) fp32 output: the minimum |pivot|
    across the Gauss–Jordan elimination — the in-kernel conditioning
    signal the numerical-health sentinel consumes (DESIGN.md §14).  A
    near-zero or NaN pivot means the mid matrix lost positive
    definiteness (only possible through rounding/corruption; Lemma 3.1
    guarantees PD in exact arithmetic), i.e. the factor update that was
    just written is untrustworthy.

    ``quant`` adds a (1, 1) fp32 per-slice scale input after gm (DESIGN.md
    §16): J arrives int8 and every tile load dequantizes in VMEM."""
    refs = list(refs)
    sc_ref = refs.pop(0) if quant else None
    out_ref = refs.pop(0)
    piv_ref = refs.pop(0) if with_pivot else None
    u_ref, s_ref, m_ref = refs
    p, i, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def _j_tile():
        jf = j_ref[...].astype(jnp.float32)
        return jf * sc_ref[0, 0] if quant else jf

    @pl.when(p == 0)
    def _accumulate():
        t = jnp.dot(_j_tile(), vc_ref[...].T,
                    preferred_element_type=jnp.float32)        # (B, r)

        @pl.when(k == 0)
        def _init_u():
            u_ref[pl.ds(i * block, block), :] = jnp.zeros_like(t)

        u_ref[pl.ds(i * block, block), :] += t

        @pl.when((i == 0) & (k == 0))
        def _init_s():
            s_ref[...] = jnp.zeros_like(s_ref)

        s_ref[...] += jnp.dot(vr_ref[...], t,
                              preferred_element_type=jnp.float32)

    @pl.when(p == 1)
    def _write():
        gm = gm_ref[0, 0]

        @pl.when((i == 0) & (k == 0))
        def _invert_mid():
            rows = jax.lax.broadcasted_iota(jnp.int32, (rank, rank), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (rank, rank), 1)
            eye = (rows == cols).astype(jnp.float32)
            s = s_ref[...]
            if variant == "paper":
                a = gm * gm * eye + gm * gm * gm * s
            elif variant == "exact_smw":
                a = gm * eye + s
            else:
                raise ValueError(variant)
            minv = eye
            pmin = jnp.float32(jnp.inf)
            for kk in range(rank):          # unrolled: rank is static+tiny
                piv = jnp.sum(jnp.where((rows == kk) & (cols == kk), a, 0.0))
                # NaN-propagating min: a non-finite pivot must surface
                pmin = jnp.minimum(pmin, jnp.abs(piv))
                arow = jnp.sum(jnp.where(rows == kk, a, 0.0),
                               axis=0, keepdims=True) / piv
                mrow = jnp.sum(jnp.where(rows == kk, minv, 0.0),
                               axis=0, keepdims=True) / piv
                col = jnp.sum(jnp.where(cols == kk, a, 0.0),
                              axis=1, keepdims=True)
                col = jnp.where(rows[:, :1] == kk, 0.0, col)
                a = a - jnp.dot(col, arow,
                                preferred_element_type=jnp.float32)
                minv = minv - jnp.dot(col, mrow,
                                      preferred_element_type=jnp.float32)
                a = jnp.where(rows == kk, arow, a)
                minv = jnp.where(rows == kk, mrow, minv)
            m_ref[...] = minv
            if with_pivot:
                # a (1, 1) vector store: Mosaic refuses scalar stores
                # into a VMEM block
                piv_ref[...] = jnp.broadcast_to(pmin, (1, 1))

        ui = u_ref[pl.ds(i * block, block), :]
        uk = u_ref[pl.ds(k * block, block), :]
        term = jnp.dot(
            jnp.dot(ui, m_ref[...], preferred_element_type=jnp.float32),
            uk.T, preferred_element_type=jnp.float32)
        jf = _j_tile()
        if variant == "paper":
            outv = gm * jf + term
        else:
            outv = (jf - term) / gm
        out_ref[...] = outv.astype(out_ref.dtype)


def fused_block_smw(j: jnp.ndarray, vt: jnp.ndarray, gm: jnp.ndarray, *,
                    variant: str = "paper", block: int = DEFAULT_BLOCK,
                    interpret: bool = False, with_pivot: bool = False,
                    scale: jnp.ndarray = None):
    """One-dispatch block rank-r SMW inverse update (DESIGN.md §11).

    J: (d, d) any dtype; vt: (r, d) fp32 PRE-WEIGHTED window rows
    (√w_i · v_i, ops.py computes the weights); gm: (1, 1) fp32 scalar γ^m.
    d must be a block multiple and zero rows of vt are inert, so callers
    pad both dims freely (kernels/ops.py).

    ``with_pivot=True`` additionally returns a (1, 1) fp32 array holding
    the minimum |Gauss–Jordan pivot| of the r×r mid-matrix solve — the
    conditioning signal the health sentinel trips on (DESIGN.md §14).
    The factor update itself is bit-identical with or without it.

    ``scale`` (a (1, 1) fp32 per-slice quant scale, DESIGN.md §16) marks J
    as int8 resident: tiles dequantize at the VMEM load and the update is
    returned in fp32 for the caller to requantize — the fp32 factor never
    materializes in HBM."""
    d = j.shape[0]
    r = vt.shape[0]
    assert d % block == 0, f"pad to block multiple ({d} % {block})"
    assert vt.shape == (r, d), (vt.shape, j.shape)
    quant = scale is not None
    g = d // block
    out_dtype = jnp.float32 if quant else j.dtype
    out_shape = jax.ShapeDtypeStruct((d, d), out_dtype)
    out_spec = pl.BlockSpec((block, block), lambda p, i, k: (i, k))
    if with_pivot:
        # the (1, 1) pivot block is revisited by every grid step and
        # written once at the first pass-1 tile (same pattern as the
        # persistent scratches); it flushes to HBM after the last step
        out_shape = (out_shape, jax.ShapeDtypeStruct((1, 1), jnp.float32))
        out_spec = (out_spec,
                    pl.BlockSpec((1, 1), lambda p, i, k: (0, 0)))
    in_specs = [
        pl.BlockSpec((block, block), lambda p, i, k: (i, k)),
        pl.BlockSpec((r, block), lambda p, i, k: (0, i)),
        pl.BlockSpec((r, block), lambda p, i, k: (0, k)),
        pl.BlockSpec((1, 1), lambda p, i, k: (0, 0)),
    ]
    operands = [j, vt, vt, gm]
    if quant:
        in_specs.append(pl.BlockSpec((1, 1), lambda p, i, k: (0, 0)))
        operands.append(jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return pl.pallas_call(
        functools.partial(_fused_block_smw_kernel, variant=variant,
                          block=block, rank=r, with_pivot=with_pivot,
                          quant=quant),
        grid=(2, g, g),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d, r), jnp.float32),
                        pltpu.VMEM((r, r), jnp.float32),
                        pltpu.VMEM((r, r), jnp.float32)],
        interpret=interpret,
        name=scopes.BLOCK_SMW_KERNEL,
    )(*operands)


def fused_smw(j: jnp.ndarray, v: jnp.ndarray, *, gamma: float,
              variant: str = "paper", block: int = DEFAULT_BLOCK,
              interpret: bool = False,
              scale: jnp.ndarray = None) -> jnp.ndarray:
    """One-dispatch SMW inverse update (Alg. 1 line 7/8, Eq. 5/6).

    J: (d, d) any dtype, v: (d, 1) fp32, d a block multiple (ops.py pads).
    Returns  scale·J + coef(vᵀJv)·(Jv)(Jv)ᵀ  in J's dtype.

    ``scale`` (a (1, 1) fp32 per-slice quant scale, DESIGN.md §16) marks J
    as int8 resident: tiles dequantize at the VMEM load and the update is
    returned in fp32 for the caller to requantize.
    """
    d = j.shape[0]
    assert d % block == 0, f"pad to block multiple ({d} % {block})"
    quant = scale is not None
    g = d // block
    in_specs = [
        pl.BlockSpec((block, block), lambda p, i, k: (i, k)),
        pl.BlockSpec((block, 1), lambda p, i, k: (i, 0)),
        pl.BlockSpec((block, 1), lambda p, i, k: (k, 0)),
    ]
    operands = [j, v, v]
    if quant:
        in_specs.append(pl.BlockSpec((1, 1), lambda p, i, k: (0, 0)))
        operands.append(jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return pl.pallas_call(
        functools.partial(_fused_smw_kernel, gamma=gamma, variant=variant,
                          block=block, quant=quant),
        grid=(2, g, g),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block, block), lambda p, i, k: (i, k)),
        out_shape=jax.ShapeDtypeStruct(
            (d, d), jnp.float32 if quant else j.dtype),
        scratch_shapes=[pltpu.VMEM((d, 1), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.float32)],
        interpret=interpret,
        name=scopes.SMW_KERNEL,
    )(*operands)
