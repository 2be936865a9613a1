"""Tiled Pallas matmul — backbone of the two-sided preconditioning
ΔW = R⁻¹ G L⁻¹ (Alg. 1 line 9).

Grid (M/BM, N/BN, K/BK); A/B tiles stream HBM→VMEM, MXU-aligned (blocks
are multiples of 128 or whole small dims).  The K grid dim is innermost
so the fp32 output tile, the accumulator, stays resident in VMEM across
the whole reduction.

The MXU multiplies bfloat16.  What it is fed follows the operands'
stored dtypes (:func:`mxu_terms`):

- bf16 × bf16: one bf16 pass.  Products of two bf16 values are exact in
  fp32, so this is the same as the fp32 product of the upcast values.
- fp32 × bf16 (either side): the fp32 tile is split in-kernel into
  ``SPLIT_TERMS`` bf16 terms, hi = bf16(x), mid = bf16(x - hi), ...; each
  term takes one bf16 pass against the bf16 tile, into the same fp32
  accumulator.  Two terms carry 16 of fp32's 24 mantissa bits.  On a
  TPU v5e Mosaic's fp32 dot rounds its operands to bf16 (one pass): at
  the rwkv6-3b slices the precondition read a relative error of 1.66e-3
  against float64 through it, as with one term; two terms read 2.46e-6,
  three 1.5e-7 (PERF.md).  Two is the fewest below the fp32 dot.
- anything else: both tiles in fp32, Mosaic's fp32 dot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes

DEFAULT_BLOCK = 256
SPLIT_TERMS = 2


def mxu_terms(a_dtype, b_dtype) -> int:
    """bf16 MXU passes per tile product: 1 for bf16 × bf16,
    ``SPLIT_TERMS`` for fp32 against bf16, 0 for Mosaic's fp32 dot."""
    dts = {jnp.dtype(a_dtype), jnp.dtype(b_dtype)}
    if dts == {jnp.dtype(jnp.bfloat16)}:
        return 1
    if dts == {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}:
        return SPLIT_TERMS
    return 0


def _split(x, terms: int):
    """fp32 ``x`` as ``terms`` bf16 terms, largest first."""
    parts = []
    for _ in range(terms):
        hi = x.astype(jnp.bfloat16)
        parts.append(hi)
        x = x - hi.astype(jnp.float32)
    return parts


def _tile_product(a, b, terms: int):
    if terms == 0:
        return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    if a.dtype == jnp.float32:
        parts = [(p, b) for p in _split(a, terms)]
    elif b.dtype == jnp.float32:
        parts = [(a, p) for p in _split(b, terms)]
    else:
        parts = [(a, b)]
    out = None
    for x, y in reversed(parts):            # smallest term first
        p = jnp.dot(x, y, preferred_element_type=jnp.float32)
        out = p if out is None else out + p
    return out


def _matmul_kernel(a_ref, b_ref, o_ref, *, terms: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _tile_product(a_ref[...], b_ref[...], terms)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *,
           block_m: int = DEFAULT_BLOCK, block_n: int = DEFAULT_BLOCK,
           block_k: int = DEFAULT_BLOCK, vmem_limit_bytes: int = None,
           interpret: bool = False) -> jnp.ndarray:
    """(M, K) @ (K, N) → fp32 (M, N); dims must be block multiples
    (ops.py pads and plans the blocks and ``vmem_limit_bytes``)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    return pl.pallas_call(
        functools.partial(_matmul_kernel, terms=mxu_terms(a.dtype, b.dtype)),
        grid=(m // block_m, n // block_n, k // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name=scopes.MATMUL_KERNEL,
    )(a, b)
