"""Jit'd wrappers around the Pallas kernels: padding to MXU-aligned block
multiples, scalar SMW coefficient math (fp32, Lemma 3.1 positivity), and
broadcast handling for expert/stack dims.  These are the entry points the
MKOR optimizer uses when ``use_pallas=True``."""
from __future__ import annotations

import warnings
from collections import Counter
from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import stats as statlib
from repro.kernels import matmul as mm
from repro.kernels import precond as pc
from repro.kernels import rank1_smw as rk
from repro.kernels import ref

# fused_precondition falls back to the two-matmul path above this
# footprint; the constant lives next to the kernel it budgets
# (kernels/precond.py docstring derives it)
_FUSED_PRECOND_VMEM_BUDGET = pc.FUSED_PRECOND_VMEM_BUDGET


class PallasFallbackWarning(UserWarning):
    """A fused Pallas entry point fell back to its unfused path."""


# (kernel, reason) -> trace-time fallback count; queryable in tests and
# cross-checked by the static kernel lint (repro.analysis, pallas checker)
_FALLBACK_COUNTS: Counter = Counter()


def fallback_counts() -> dict:
    return dict(_FALLBACK_COUNTS)


def reset_fallback_counts() -> None:
    _FALLBACK_COUNTS.clear()


def _note_fallback(kernel: str, reason: str, detail: str) -> None:
    _FALLBACK_COUNTS[(kernel, reason)] += 1
    warnings.warn(
        f"{kernel}: falling back to the unfused path ({reason}): {detail}",
        PallasFallbackWarning, stacklevel=3)


def _pad_to(x: jnp.ndarray, block: int, dims) -> jnp.ndarray:
    pads = [(0, 0)] * x.ndim
    for d in dims:
        rem = (-x.shape[d]) % block
        pads[d] = (0, rem)
    if any(p != (0, 0) for p in pads):
        x = jnp.pad(x, pads)
    return x


def _padded_size(d: int, block: int) -> int:
    return -(-d // block) * block


def _pick_block(d: int, preferred: int = 256) -> int:
    """Block minimizing the padded size; larger block wins ties (MXU
    utilisation).  The old rule returned ``preferred`` whenever d > b,
    so d=300 picked 256 and padded to 512 — ~2.9x wasted factor FLOPs.

    For d > 128 only MXU/lane-aligned blocks (128, 256) are candidates:
    a sub-128 block would drop below the TPU (8, 128) minimum tile and
    explode the grid (d=1000 at block 8 is ~15k grid steps of 16x-wasted
    lanes vs 16 steps at block 256 with 2.4% padding)."""
    if d > 128:
        cands = (preferred, 128) if preferred > 128 else (preferred,)
    else:
        cands = (128, 64, 32, 16, 8)
    return min(cands, key=lambda b: (_padded_size(d, b), -b))


# ----------------------------------------------------------------------- #
# Static dispatch plans (repro.analysis, pallas checker)
#
# Each fused entry point's padding/block/VMEM decision is a pure function
# of the factor shapes + config, so the linter can check the 12MB budget,
# tile alignment, and Gauss-Jordan rank bounds BEFORE anything dispatches.
# The runtime paths below consume the same plans, so the lint and the
# kernels agree by construction.
# ----------------------------------------------------------------------- #
@dataclass(frozen=True)
class KernelPlan:
    kernel: str                     # fused_precond | fused_smw | ...
    dims: Tuple[int, ...]           # logical factor dims
    padded: Tuple[int, ...]         # after block padding
    block: Tuple[int, ...]          # chosen block sizes
    grid: Tuple[int, ...]
    rank: int                       # padded window rank (1 for rank-1)
    vmem_bytes: int                 # scratch + resident + streaming tiles
    vmem_budget: int
    fits: bool
    falls_back: bool                # True: runtime degrades gracefully
                                    # when !fits; False: it would dispatch
                                    # an over-budget kernel

    @property
    def sublane_aligned(self) -> bool:
        return all(b % 8 == 0 for b in self.block)

    @property
    def lane_aligned(self) -> bool:
        return all(b % 128 == 0 for b in self.block)


def fused_precond_plan(d_in: int, d_out: int, *, block: int = 0,
                       factor_dtype="bfloat16",
                       factor_quant: str = "none") -> KernelPlan:
    """What :func:`fused_precondition` will do for a (d_in, d_out) slice:
    two (d_in_p, d_out_p) fp32 scratches + both factors VMEM-resident
    (kernels/precond.py); over budget it falls back to two matmuls.

    ``factor_quant`` resolves the *storage* dtype of the resident factors
    (DESIGN.md §16): int8 residents shrink the VMEM footprint 2x vs bf16
    and ride two (1, 1) fp32 scale inputs."""
    bi = block or _pick_block(d_in)
    bj = block or _pick_block(d_out)
    dip, dop = _padded_size(d_in, bi), _padded_size(d_out, bj)
    item = statlib.factor_itemsize(factor_dtype, factor_quant)
    scales = 2 * 4 if factor_quant == "int8" else 0
    vmem = (2 * dip * dop * 4                     # T + delta scratches
            + dip * dip * item + dop * dop * item  # resident factors
            + dip * bj * item + bi * bj * 4        # streaming G/out tiles
            + scales)                              # (1, 1) dequant scales
    return KernelPlan(
        kernel="fused_precond", dims=(d_in, d_out), padded=(dip, dop),
        block=(bi, bj), grid=(3, dip // bi, dop // bj), rank=1,
        vmem_bytes=int(vmem), vmem_budget=_FUSED_PRECOND_VMEM_BUDGET,
        fits=vmem <= _FUSED_PRECOND_VMEM_BUDGET, falls_back=True)


def fused_smw_plan(d: int, *, block: int = 0,
                   factor_dtype="bfloat16",
                   factor_quant: str = "none") -> KernelPlan:
    """Rank-1 fused SMW (kernels/rank1_smw.fused_smw): persistent (d, 1)
    fp32 u scratch + streaming J/out/v tiles.  No fallback path.  With
    int8 ``factor_quant`` the streaming J tile is int8 (dequant fused at
    the load site) but the out tile is written fp32."""
    blk = block or _pick_block(d)
    dp = _padded_size(d, blk)
    item = statlib.factor_itemsize(factor_dtype, factor_quant)
    out_item = 4 if factor_quant == "int8" else item
    vmem = (dp * 4 + blk * blk * (item + out_item) + 2 * blk * 4
            + (4 if factor_quant == "int8" else 0))
    return KernelPlan(
        kernel="fused_smw", dims=(d,), padded=(dp,), block=(blk,),
        grid=(2, dp // blk, dp // blk), rank=1, vmem_bytes=int(vmem),
        vmem_budget=_FUSED_PRECOND_VMEM_BUDGET,
        fits=vmem <= _FUSED_PRECOND_VMEM_BUDGET, falls_back=False)


def fused_block_smw_plan(d: int, rank: int, *, block: int = 0,
                         factor_dtype="bfloat16",
                         factor_quant: str = "none") -> KernelPlan:
    """Block rank-r fused SMW (kernels/rank1_smw.fused_block_smw):
    persistent (d, rpad) fp32 U scratch + two (rpad, rpad) fp32 Gram/mid
    scratches + streaming tiles, rank sublane-padded to a multiple of 8.
    No fallback path — an over-budget plan means the dispatch itself
    would blow VMEM (the lint's pallas.vmem-over-budget ERROR)."""
    blk = block or _pick_block(d)
    dp = _padded_size(d, blk)
    rpad = -(-max(rank, 1) // 8) * 8
    item = statlib.factor_itemsize(factor_dtype, factor_quant)
    out_item = 4 if factor_quant == "int8" else item
    vmem = (dp * rpad * 4 + 2 * rpad * rpad * 4
            + blk * blk * (item + out_item) + 2 * rpad * blk * 4
            + (4 if factor_quant == "int8" else 0))
    return KernelPlan(
        kernel="fused_block_smw", dims=(d,), padded=(dp,), block=(blk,),
        grid=(2, dp // blk, dp // blk), rank=rpad, vmem_bytes=int(vmem),
        vmem_budget=_FUSED_PRECOND_VMEM_BUDGET,
        fits=vmem <= _FUSED_PRECOND_VMEM_BUDGET, falls_back=False)


# The tiled matmul's largest (block_m, block_k, block_n) and its VMEM
# budget (a v5e core has 128 MiB of VMEM; Mosaic's default scoped limit is
# 16 MiB, so the call raises it to what the plan needs).  On a v5e at the
# rwkv6-3b slices 1280-wide blocks ran the 411 GFLOP bf16 products at
# 179-181 TFLOP/s, 640-wide ones at 164; the split products varied by 2%
# across the tilings tried (PERF.md).
MATMUL_BLOCK_CAP = (1280, 1280, 1280)
MATMUL_VMEM_BUDGET = 64 * 2**20


def _lane_blocks(d: int, cap: int) -> Tuple[int, ...]:
    """Blocks for a matmul dim, largest first: multiples of 128 up to
    ``cap`` that divide d padded to 128, or one block for a dim ≤ 128."""
    if d <= 128:
        return (_pick_block(d),)
    dp = _padded_size(d, 128)
    return tuple(b for b in range(min(cap, dp) // 128 * 128, 0, -128)
                 if dp % b == 0)


def _matmul_vmem(bm: int, bk: int, bn: int, ia: int, ib: int,
                 terms: int) -> int:
    """VMEM of one tiled-matmul grid step: double-buffered A, B and fp32
    out tiles (the out tile is the accumulator), the fp32 tile product,
    and the split's working copies of an fp32 operand tile (or the fp32
    upcasts of Mosaic's fp32 dot)."""
    vmem = 2 * (bm * bk * ia + bk * bn * ib + bm * bn * 4) + bm * bn * 4
    if terms == 0:
        vmem += bm * bk * 4 * (ia != 4) + bk * bn * 4 * (ib != 4)
    elif terms > 1:
        split = bm * bk if ia == 4 else bk * bn
        vmem += split * (4 + 2 * terms) + bm * bn * 4
    return vmem


def matmul_plan(m: int, k: int, n: int, *, a_dtype="bfloat16",
                b_dtype="bfloat16", block=0) -> KernelPlan:
    """What :func:`pallas_matmul` will do for (m, k) @ (k, n): per dim
    the largest lane-aligned block under ``MATMUL_BLOCK_CAP`` that divides
    the dim padded to 128; the largest block (K first on a tie) steps
    down until the VMEM plan fits ``MATMUL_VMEM_BUDGET``.  ``block`` (an
    int, or (bm, bk, bn)) forces the blocks."""
    ia, ib = jnp.dtype(a_dtype).itemsize, jnp.dtype(b_dtype).itemsize
    terms = mm.mxu_terms(a_dtype, b_dtype)
    if block:
        bm, bk, bn = (block,) * 3 if isinstance(block, int) else block
    else:
        cands = [list(_lane_blocks(d, c))
                 for d, c in zip((m, k, n), MATMUL_BLOCK_CAP)]
        while (_matmul_vmem(*(c[0] for c in cands), ia, ib, terms)
               > MATMUL_VMEM_BUDGET):
            shrink = [i for i in (1, 0, 2) if len(cands[i]) > 1]
            if not shrink:
                break
            cands[max(shrink, key=lambda i: cands[i][0])].pop(0)
        bm, bk, bn = (c[0] for c in cands)
    mp, kp, np_ = (_padded_size(d, b) for d, b in ((m, bm), (k, bk),
                                                     (n, bn)))
    vmem = _matmul_vmem(bm, bk, bn, ia, ib, terms)
    return KernelPlan(
        kernel="matmul", dims=(m, k, n), padded=(mp, kp, np_),
        block=(bm, bk, bn), grid=(mp // bm, np_ // bn, kp // bk), rank=1,
        vmem_bytes=int(vmem), vmem_budget=MATMUL_VMEM_BUDGET,
        fits=vmem <= MATMUL_VMEM_BUDGET, falls_back=False)


def _vmem_limit(plan: KernelPlan) -> int:
    """The call's scoped VMEM limit: the plan's bytes and half again for
    what Mosaic adds, never under its 16 MiB default nor over 112 MiB."""
    return max(16 * 2**20, min(plan.vmem_bytes * 3 // 2, 112 * 2**20))


def precondition_matmul_plans(d_in: int, d_out: int, *,
                              factor_dtype="bfloat16",
                              factor_quant: str = "none",
                              grad_dtype="bfloat16",
                              block: int = 0) -> Tuple[KernelPlan, ...]:
    """The two :func:`matmul_plan` s of :func:`two_sided_precondition`
    for one (d_in, d_out) slice, in dispatch order: what
    :func:`fused_precondition` runs when its own plan does not fit.  The
    intermediate is fp32, and so are int8 factors, dequantized first."""
    f = statlib.factor_storage_dtype(factor_dtype, factor_quant)
    f, g, t = "float32" if f == "int8" else f, grad_dtype, "float32"
    if _right_first(d_in, d_out):
        return (matmul_plan(d_in, d_out, d_out, a_dtype=g, b_dtype=f,
                            block=block),
                matmul_plan(d_in, d_in, d_out, a_dtype=f, b_dtype=t,
                            block=block))
    return (matmul_plan(d_in, d_in, d_out, a_dtype=f, b_dtype=g,
                        block=block),
            matmul_plan(d_in, d_out, d_out, a_dtype=t, b_dtype=f,
                        block=block))


def bucket_kernel_plans(d_in: int, d_out: int, *, rank: int = 1,
                        factor_dtype="bfloat16", factor_quant: str = "none",
                        block: int = 0) -> Tuple[KernelPlan, ...]:
    """Every kernel dispatch one factor bucket implies per inversion /
    step, in dispatch order: one SMW update per factor dim + the fused
    precondition over the (d_in, d_out) slice."""
    if rank > 1:
        smw = tuple(fused_block_smw_plan(d, rank, block=block,
                                         factor_dtype=factor_dtype,
                                         factor_quant=factor_quant)
                    for d in (d_in, d_out))
    else:
        smw = tuple(fused_smw_plan(d, block=block,
                                   factor_dtype=factor_dtype,
                                   factor_quant=factor_quant)
                    for d in (d_in, d_out))
    return smw + (fused_precond_plan(d_in, d_out, block=block,
                                     factor_dtype=factor_dtype,
                                     factor_quant=factor_quant),)


def smw_rank1_update(j_inv: jnp.ndarray, v: jnp.ndarray, *, gamma: float,
                     variant: str = "paper", block: int = 0,
                     interpret: bool = False,
                     scale: jnp.ndarray = None) -> jnp.ndarray:
    """Fused-Pallas Alg. 1 line 7/8.  v: (d,) or (r, d) chained.

    One ``pallas_call`` per rank-1 update (kernels/rank1_smw.fused_smw):
    matvec, scalar s, and the rank-1 write share a single grid, so u and s
    never leave VMEM/SMEM and there is no per-piece dispatch.

    ``scale`` (scalar fp32, DESIGN.md §16) marks ``j_inv`` as an int8
    resident: the kernel dequantizes it at the VMEM load site and the
    updated inverse comes back fp32 (the caller requantizes — computing
    the new scale needs a global max-abs the grid cannot see)."""
    if v.ndim == 2:
        for i in range(v.shape[0]):
            j_inv = smw_rank1_update(j_inv, v[i], gamma=gamma,
                                     variant=variant, block=block,
                                     interpret=interpret, scale=scale)
            scale = None                    # chained updates are fp32
        return j_inv
    d = j_inv.shape[0]
    blk = block or _pick_block(d)
    jp = _pad_to(j_inv, blk, (0, 1))
    vp = _pad_to(v.reshape(-1, 1).astype(jnp.float32), blk, (0,))
    out = rk.fused_smw(jp, vp, gamma=gamma, variant=variant, block=blk,
                       interpret=interpret, scale=scale)
    return out[:d, :d]


def smw_rank1_update_banked(j: jnp.ndarray, v: jnp.ndarray, *, gamma: float,
                            variant: str = "paper", block: int = 0,
                            interpret: bool = False,
                            scale: jnp.ndarray = None) -> jnp.ndarray:
    """Batched fused SMW over factor-bank leading dims (DESIGN.md §2).

    j: (*lead, d, d) — lead = (n_bucket_layers, *stack); v: (*lead, d) or
    (*lead, r, d) for chained rank-r stats.  The lead dims are flattened
    and vmapped over the fused kernel, producing one batched dispatch per
    bucket instead of one per layer.

    Under the owner-sharded inversion schedule (DESIGN.md §10) the entry
    receives a *locally-sliced* bank: lead[0] is this worker's owned chunk
    (possibly zero-padded) rather than the full bucket — any lead extent
    works, including an empty chunk, which is returned untouched.

    ``scale`` (``lead``-shaped fp32, DESIGN.md §16) marks ``j`` as an int8
    bank with per-slice dequant scales; the updated bank comes back fp32
    for the caller to requantize."""
    d = j.shape[-1]
    lead = j.shape[:-2]
    assert v.shape[:len(lead)] == lead, (v.shape, j.shape)
    rank = v.shape[len(lead):-1]                    # () or (r,)
    fn = partial(smw_rank1_update, gamma=gamma, variant=variant,
                 block=block, interpret=interpret)
    if not lead:
        return fn(j, v, scale=scale)
    if 0 in lead:                                   # empty owner slice
        return j.astype(jnp.float32) if scale is not None else j
    if scale is not None:
        assert scale.shape == lead, (scale.shape, j.shape)
        out = jax.vmap(lambda jj, vv, ss: fn(jj, vv, scale=ss))(
            j.reshape((-1, d, d)), v.reshape((-1,) + rank + (d,)),
            scale.reshape((-1,)))
        return out.reshape(lead + (d, d))
    out = jax.vmap(fn)(j.reshape((-1, d, d)),
                       v.reshape((-1,) + rank + (d,)))
    return out.reshape(j.shape)


def smw_block_update(j_inv: jnp.ndarray, v: jnp.ndarray, *, gamma: float,
                     variant: str = "paper", n_valid=None, block: int = 0,
                     interpret: bool = False, with_pivot: bool = False,
                     scale: jnp.ndarray = None):
    """Fused-Pallas block rank-r Woodbury update (DESIGN.md §11).

    v: (r, d) window rows oldest-first.  The √w_i row weights and the γ^m
    base scale (core.mkor.block_weights — ``n_valid`` masks a partially
    filled window) are applied here in fp32; the r matvecs, the r×r solve,
    and the rank-r axpy then run in ONE ``pallas_call``
    (kernels/rank1_smw.fused_block_smw) — vs r dispatches for the chained
    rank-1 path.  The rank dim is sublane-padded with zero (inert) rows.

    ``with_pivot=True`` returns ``(new, min_pivot)`` with the scalar
    minimum |Gauss–Jordan pivot| of the in-kernel r×r solve (fp32) —
    the conditioning signal the health sentinel trips on (DESIGN.md
    §14).  The zero padding rows contribute pivots of gm² (paper) / gm
    (exact_smw), never zero, so padding cannot mask a real collapse.

    ``scale`` (scalar fp32, DESIGN.md §16) marks ``j_inv`` as an int8
    resident — dequant fused at the load site, fp32 output."""
    from repro.core.mkor import block_weights
    r, d = v.shape
    assert j_inv.shape == (d, d), (j_inv.shape, v.shape)
    sq, gm = block_weights(r if n_valid is None else n_valid, r, gamma)
    vt = v.astype(jnp.float32) * sq[:, None]
    blk = block or _pick_block(d)
    rpad = -(-r // 8) * 8
    jp = _pad_to(j_inv, blk, (0, 1))
    vp = _pad_to(vt, blk, (1,))
    if rpad != r:
        vp = jnp.pad(vp, ((0, rpad - r), (0, 0)))
    out = rk.fused_block_smw(
        jp, vp, jnp.asarray(gm, jnp.float32).reshape(1, 1),
        variant=variant, block=blk, interpret=interpret,
        with_pivot=with_pivot, scale=scale)
    if with_pivot:
        out, piv = out
        return out[:d, :d], piv[0, 0]
    return out[:d, :d]


def smw_block_update_banked(j: jnp.ndarray, v: jnp.ndarray, n_valid, *,
                            gamma: float, variant: str = "paper",
                            block: int = 0, interpret: bool = False,
                            with_pivot: bool = False,
                            scale: jnp.ndarray = None):
    """Banked fused block update: ONE batched dispatch per bucket per phase
    step (DESIGN.md §11).

    j: (*lead, d, d); v: (*lead, r, d) ring windows ordered oldest-first
    (core/stats.py window_ordered); n_valid: int broadcastable to ``lead``
    — per-slice window fill counts (0 slices are exact no-ops).  As with
    the rank-1 entry, lead may be a locally-sliced owner chunk, including
    an empty one.  ``with_pivot=True`` returns ``(new, min_pivot)`` with
    the minimum in-kernel Gauss–Jordan pivot across every slice of the
    bank (a scalar — per-bucket is the sentinel's quarantine unit).
    ``scale`` (``lead``-shaped fp32, DESIGN.md §16) marks ``j`` as an
    int8 bank; the updated bank comes back fp32."""
    d = j.shape[-1]
    lead = j.shape[:-2]
    r = v.shape[-2]
    assert v.shape[:len(lead)] == lead, (v.shape, j.shape)
    fn = partial(smw_block_update, gamma=gamma, variant=variant,
                 block=block, interpret=interpret, with_pivot=with_pivot)
    if not lead:
        return fn(j, v, n_valid=n_valid, scale=scale)
    if 0 in lead:                                   # empty owner slice
        jf = j.astype(jnp.float32) if scale is not None else j
        return (jf, jnp.float32(jnp.inf)) if with_pivot else jf
    nv = jnp.broadcast_to(jnp.asarray(n_valid), lead).reshape((-1,))
    jf = j.reshape((-1, d, d))
    vf = v.reshape((-1, r, d))
    if scale is not None:
        assert scale.shape == lead, (scale.shape, j.shape)
        out = jax.vmap(lambda jj, vv, nn, ss: fn(jj, vv, n_valid=nn,
                                                 scale=ss))(
            jf, vf, nv, scale.reshape((-1,)))
        out_shape = lead + (d, d)
    else:
        out = jax.vmap(lambda jj, vv, nn: fn(jj, vv, n_valid=nn))(
            jf, vf, nv)
        out_shape = j.shape
    if with_pivot:
        out, pivs = out
        return out.reshape(out_shape), jnp.min(pivs)
    return out.reshape(out_shape)


def pallas_matmul(a: jnp.ndarray, b: jnp.ndarray, *, block=0,
                  interpret: bool = False):
    """(M, K) @ (K, N) → fp32 through the tiled kernel, on the blocks and
    VMEM limit of :func:`matmul_plan`; ``block`` forces the blocks."""
    m, k = a.shape
    _, n = b.shape
    plan = matmul_plan(m, k, n, a_dtype=a.dtype, b_dtype=b.dtype,
                       block=block)
    bm, bk, bn = plan.block
    ap = _pad_to(_pad_to(a, bm, (0,)), bk, (1,))
    bp = _pad_to(_pad_to(b, bk, (0,)), bn, (1,))
    out = mm.matmul(ap, bp, block_m=bm, block_n=bn, block_k=bk,
                    vmem_limit_bytes=_vmem_limit(plan), interpret=interpret)
    return out[:m, :n]


def _right_first(d_in: int, d_out: int) -> bool:
    """R (G L) when d_in < d_out, else (R G) L: the product of G with its
    larger factor comes first, so it multiplies the stored (bf16) G and
    the fp32 intermediate rides the smaller product."""
    return d_in < d_out


def two_sided_precondition(l_inv: jnp.ndarray, r_inv: jnp.ndarray,
                           g_w: jnp.ndarray, *, block: int = 0,
                           interpret: bool = False) -> jnp.ndarray:
    """ΔW = R⁻¹ G L⁻¹ via two tiled Pallas matmuls, in the order of
    :func:`_right_first`.  Extra leading dims of ``g_w`` (experts under
    shared factors) are vmapped."""
    if g_w.ndim > 2:
        fn = partial(two_sided_precondition, l_inv, r_inv, block=block,
                     interpret=interpret)
        return jax.vmap(fn)(g_w)
    mm_ = partial(pallas_matmul, block=block, interpret=interpret)
    if _right_first(*g_w.shape):
        return mm_(r_inv, mm_(g_w, l_inv))
    return mm_(mm_(r_inv, g_w), l_inv)


def _fused_precond_fits(d_in: int, d_out: int, r_inv, l_inv,
                        block: int = 0) -> bool:
    item = max(r_inv.dtype.itemsize, l_inv.dtype.itemsize)
    return fused_precond_plan(d_in, d_out, block=block,
                              factor_dtype=r_inv.dtype
                              if r_inv.dtype.itemsize == item
                              else l_inv.dtype).fits


def fused_precondition(l_inv: jnp.ndarray, r_inv: jnp.ndarray,
                       g_w: jnp.ndarray, *, rescale: bool = True,
                       block: int = 0, interpret: bool = False,
                       l_scale: jnp.ndarray = None,
                       r_scale: jnp.ndarray = None) -> jnp.ndarray:
    """Alg. 1 lines 9-10 in one dispatch: ΔW = R⁻¹ G L⁻¹ with the Frobenius
    rescale reduction accumulated in the same kernel (kernels/precond.py).

    g_w: (d_in, d_out) for the fused kernel.  Extra leading dims (experts
    under shared factors) and VMEM-budget-exceeding shapes fall back to the
    two-matmul path plus a jnp rescale; either way the rescale spans every
    dim of the slice (the line-10 contract of core.mkor.rescale_update).
    The fallback is not silent: it emits a :class:`PallasFallbackWarning`
    at trace time and bumps :func:`fallback_counts` — the same decision the
    static kernel lint (repro.analysis) reports per bucket.

    ``l_scale``/``r_scale`` (scalar fp32, both or neither — DESIGN.md §16)
    mark the inverse factors as int8 residents.  The fused path dequantizes
    at the VMEM load sites; the fallback path dequantizes into fp32 matmul
    inputs (registers/VMEM under jit, no resident HBM copy survives).
    """
    assert (l_scale is None) == (r_scale is None), \
        "quantized precondition needs both factor scales"
    if g_w.ndim > 2 or not _fused_precond_fits(
            g_w.shape[-2], g_w.shape[-1], r_inv, l_inv, block):
        reason = "extra_dims" if g_w.ndim > 2 else "vmem_budget"
        plan = fused_precond_plan(g_w.shape[-2], g_w.shape[-1], block=block,
                                  factor_dtype=r_inv.dtype)
        _note_fallback(
            "fused_precond", reason,
            f"g_w shape {tuple(g_w.shape)}, plan VMEM "
            f"{plan.vmem_bytes / 2**20:.1f}MB vs budget "
            f"{plan.vmem_budget / 2**20:.0f}MB")
        if l_scale is not None:
            l_inv = ref.dequant_ref(l_inv, l_scale)
            r_inv = ref.dequant_ref(r_inv, r_scale)
        delta = two_sided_precondition(l_inv, r_inv, g_w, block=block,
                                       interpret=interpret)
        if rescale:
            gf = g_w.astype(jnp.float32)
            gn = jnp.sqrt(jnp.sum(gf * gf))
            dn = jnp.sqrt(jnp.sum(delta * delta))
            delta = delta * (gn / jnp.maximum(dn, pc.RESCALE_EPS))
        return delta
    d_in, d_out = g_w.shape
    bi = block or _pick_block(d_in)
    bj = block or _pick_block(d_out)
    rp = _pad_to(r_inv, bi, (0, 1))
    lp = _pad_to(l_inv, bj, (0, 1))
    gp = _pad_to(_pad_to(g_w, bi, (0,)), bj, (1,))
    out = pc.fused_precond(rp, gp, lp, rescale=rescale, block_i=bi,
                           block_j=bj, interpret=interpret,
                           r_scale=r_scale, l_scale=l_scale)
    return out[:d_in, :d_out]


def fused_precondition_banked(l_inv: jnp.ndarray, r_inv: jnp.ndarray,
                              g_w: jnp.ndarray, *, rescale: bool = True,
                              block: int = 0, interpret: bool = False,
                              l_scale: jnp.ndarray = None,
                              r_scale: jnp.ndarray = None) -> jnp.ndarray:
    """Banked entry for the fused precondition kernel (DESIGN.md §9).

    l_inv: (*lead, d_out, d_out), r_inv: (*lead, d_in, d_in), g_w:
    (*lead, *extra, d_in, d_out) — lead = (n_bucket_layers, *stack).  Lead
    dims are flattened and vmapped, one batched dispatch per bucket; the
    per-slice Frobenius rescale spans the slice's extra dims (matching
    core.mkor.rescale_update under ``_vmap_over_stack``).  As with the SMW
    entry, lead may be a locally-sliced chunk of the full bank.
    ``l_scale``/``r_scale`` (``lead``-shaped fp32, both or neither) mark
    the banks as int8 residents with per-slice dequant scales.
    """
    lead = l_inv.shape[:-2]
    assert r_inv.shape[:len(lead)] == lead, (r_inv.shape, l_inv.shape)
    assert g_w.shape[:len(lead)] == lead, (g_w.shape, l_inv.shape)
    assert (l_scale is None) == (r_scale is None), \
        "quantized precondition needs both factor scales"
    fn = partial(fused_precondition, rescale=rescale, block=block,
                 interpret=interpret)
    if not lead:
        return fn(l_inv, r_inv, g_w, l_scale=l_scale, r_scale=r_scale)
    if 0 in lead:                                   # empty owner slice
        return jnp.zeros(g_w.shape, g_w.dtype)
    lf = l_inv.reshape((-1,) + l_inv.shape[len(lead):])
    rf = r_inv.reshape((-1,) + r_inv.shape[len(lead):])
    gf = g_w.reshape((-1,) + g_w.shape[len(lead):])
    if l_scale is not None:
        assert l_scale.shape == lead, (l_scale.shape, l_inv.shape)
        assert r_scale.shape == lead, (r_scale.shape, r_inv.shape)
        out = jax.vmap(lambda ll, rr, gg, ls, rs:
                       fn(ll, rr, gg, l_scale=ls, r_scale=rs))(
            lf, rf, gf, l_scale.reshape((-1,)), r_scale.reshape((-1,)))
    else:
        out = jax.vmap(fn)(lf, rf, gf)
    return out.reshape(lead + out.shape[1:])
