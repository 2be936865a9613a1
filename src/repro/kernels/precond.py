"""Fused two-sided precondition + rescale Pallas kernel (Alg. 1 lines 9-10).

The per-bucket steady-state work of MKOR's line 9/10 is

    ΔW = R⁻¹ G L⁻¹;   ΔW ← ΔW · ‖G‖_F / ‖ΔW‖_F

previously two separate tiled matmul dispatches per bucket plus a jnp
reduction for the rescale.  ``fused_precond`` runs the whole pipeline in ONE
``pallas_call`` with a three-pass grid ``(3, d_in/BI, d_out/BJ)``
(DESIGN.md §9):

* Pass 0: T[i, j] = R⁻¹[i-rows, :] @ G[:, j-cols] into a persistent VMEM
  scratch ``(d_in, d_out)`` fp32; the Frobenius partials  Σ G²  accumulate
  into SMEM (once per j panel, at i == 0 — the grid covers each G panel
  exactly once per i).
* Pass 1: Δ[i, j] = T[i-rows, :] @ L⁻¹[:, j-cols] into a second VMEM
  scratch, accumulating  Σ Δ²  into SMEM.
* Pass 2: out[i, j] = Δ[i, j] · √(ΣG²) / max(√(ΣΔ²), ε)  — the rescale is
  a tile-local multiply once both reductions are complete (ε = 1e-30,
  matching ``core.mkor.rescale_update``); with ``rescale=False`` pass 2
  writes Δ unscaled.

T and Δ never round-trip through HBM and the Frobenius reduction needs no
extra dispatch.  The factor matrices ride along as unblocked VMEM residents
(index map pinned to (0, 0)); with the two (d_in, d_out) fp32 scratches the
kernel's VMEM footprint is roughly ``2·d_in·d_out·4 + d_in² + d_out²``
bytes — callers (kernels/ops.py) fall back to the two-matmul path when that
exceeds the VMEM budget.  Zero padding is safe end-to-end: padded G rows /
cols are zero, so padded T and Δ regions are zero and neither Frobenius sum
is perturbed.

Validated against ``core.mkor.precondition`` + ``rescale_update`` in
interpret mode on CPU, including non-block-multiple dims and rescale
on/off (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes

DEFAULT_BLOCK = 256
RESCALE_EPS = 1e-30          # same guard as core.mkor.rescale_update

# fused_precond keeps two (d_in, d_out) fp32 scratches plus both factor
# matrices VMEM-resident; TPU VMEM is ~16 MB/core, and 12 MB leaves room
# for the streaming G/out tiles.  kernels/ops.py falls back to the
# two-matmul path above this footprint, and repro.analysis's Pallas lint
# checks the same bound statically (ops.fused_precond_plan).
FUSED_PRECOND_VMEM_BUDGET = 12 * 2 ** 20


def _fused_precond_kernel(r_ref, g_ref, l_ref, *refs, rescale: bool,
                          block_i: int, block_j: int, quant: bool = False):
    # ``quant`` (DESIGN.md §16) appends two (1, 1) fp32 per-slice scale
    # inputs after l_ref: both factors arrive int8 and dequantize at
    # their VMEM load sites — no fp32 factor copy in HBM.
    refs = list(refs)
    if quant:
        rs_ref, ls_ref = refs.pop(0), refs.pop(0)
    out_ref, t_ref, d_ref, gn_ref, dn_ref = refs
    p, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows = pl.ds(i * block_i, block_i)
    cols = pl.ds(j * block_j, block_j)

    @pl.when(p == 0)
    def _t_and_gnorm():
        @pl.when((i == 0) & (j == 0))
        def _init():
            gn_ref[0, 0] = 0.0
            dn_ref[0, 0] = 0.0

        g_panel = g_ref[...].astype(jnp.float32)
        r_panel = r_ref[rows, :].astype(jnp.float32)
        if quant:
            r_panel = r_panel * rs_ref[0, 0]
        t_ref[rows, cols] = jnp.dot(r_panel, g_panel,
                                    preferred_element_type=jnp.float32)

        # each G column panel appears once per i — count it once
        @pl.when(i == 0)
        def _gnorm():
            gn_ref[0, 0] += jnp.sum(g_panel * g_panel)

    @pl.when(p == 1)
    def _delta_and_dnorm():
        l_panel = l_ref[:, cols].astype(jnp.float32)
        if quant:
            l_panel = l_panel * ls_ref[0, 0]
        d_tile = jnp.dot(t_ref[rows, :], l_panel,
                         preferred_element_type=jnp.float32)
        d_ref[rows, cols] = d_tile
        dn_ref[0, 0] += jnp.sum(d_tile * d_tile)

    @pl.when(p == 2)
    def _write():
        d_tile = d_ref[rows, cols]
        if rescale:
            scale = jnp.sqrt(gn_ref[0, 0]) / jnp.maximum(
                jnp.sqrt(dn_ref[0, 0]), RESCALE_EPS)
            d_tile = d_tile * scale
        out_ref[...] = d_tile.astype(out_ref.dtype)


def fused_precond(r_inv: jnp.ndarray, g: jnp.ndarray, l_inv: jnp.ndarray, *,
                  rescale: bool = True, block_i: int = DEFAULT_BLOCK,
                  block_j: int = DEFAULT_BLOCK,
                  interpret: bool = False,
                  r_scale: jnp.ndarray = None,
                  l_scale: jnp.ndarray = None) -> jnp.ndarray:
    """One-dispatch  ΔW = rescale(R⁻¹ G L⁻¹)  (Alg. 1 lines 9-10).

    r_inv: (d_in, d_in), g: (d_in, d_out), l_inv: (d_out, d_out); d_in a
    multiple of ``block_i`` and d_out of ``block_j`` (kernels/ops.py pads).
    Returns fp32, like the einsum reference ``core.mkor.precondition``.

    ``r_scale``/``l_scale`` ((1, 1) fp32 per-slice quant scales, both or
    neither — DESIGN.md §16) mark the factors as int8 residents that
    dequantize at the VMEM load sites.
    """
    d_in, d_out = g.shape
    assert r_inv.shape == (d_in, d_in), (r_inv.shape, g.shape)
    assert l_inv.shape == (d_out, d_out), (l_inv.shape, g.shape)
    assert d_in % block_i == 0 and d_out % block_j == 0, \
        f"pad to block multiples ({g.shape} % ({block_i}, {block_j}))"
    assert (r_scale is None) == (l_scale is None), \
        "quantized precondition needs both factor scales"
    quant = r_scale is not None
    grid = (3, d_in // block_i, d_out // block_j)
    in_specs = [
        # factors stay VMEM-resident across the whole grid
        pl.BlockSpec((d_in, d_in), lambda p, i, j: (0, 0)),
        pl.BlockSpec((d_in, block_j), lambda p, i, j: (0, j)),
        pl.BlockSpec((d_out, d_out), lambda p, i, j: (0, 0)),
    ]
    operands = [r_inv, g, l_inv]
    if quant:
        for s in (r_scale, l_scale):
            in_specs.append(pl.BlockSpec((1, 1), lambda p, i, j: (0, 0)))
            operands.append(jnp.asarray(s, jnp.float32).reshape(1, 1))
    return pl.pallas_call(
        functools.partial(_fused_precond_kernel, rescale=rescale,
                          block_i=block_i, block_j=block_j, quant=quant),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_i, block_j), lambda p, i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d_in, d_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d_in, d_out), jnp.float32),
                        pltpu.VMEM((d_in, d_out), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.float32)],
        interpret=interpret,
        name=scopes.PRECOND_KERNEL,
    )(*operands)
