"""Tiled Pallas matmul — backbone of the two-sided preconditioning
ΔW = R⁻¹ G L⁻¹ (Alg. 1 line 9).

Grid (M/BM, N/BN, K/BK) with an fp32 VMEM accumulator scratch; A/B tiles
stream HBM→VMEM, MXU-aligned (blocks are multiples of 128).  The K grid
dim is innermost so the accumulator tile stays resident in VMEM across the
whole reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes

DEFAULT_BLOCK = 256


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *,
           block_m: int = DEFAULT_BLOCK, block_n: int = DEFAULT_BLOCK,
           block_k: int = DEFAULT_BLOCK, out_dtype=jnp.float32,
           interpret: bool = False) -> jnp.ndarray:
    """(M, K) @ (K, N) → (M, N); dims must be block multiples (ops.py pads)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    k_steps = k // block_k
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(m // block_m, n // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name=scopes.MATMUL_KERNEL,
    )(a, b)
