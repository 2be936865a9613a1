"""The fused Pallas kernels compile for a TPU v5e at bert-large widths.

Interpret mode on the CPU runs the kernels' arithmetic but never shows
them to Mosaic, the chip's kernel compiler, which refuses what the
interpreter accepts (an unaligned slice, a scalar store into a VMEM
block, more VMEM than a kernel may hold).  Each test here compiles one
main-path kernel entry for a described ``v5e:2x2`` chip: nothing runs and
no chip is needed.  One more compiles RWKV-6's time mixing with its
gradient at rwkv6-3b's widths, to see the loops the chip's compiler keeps.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file.  Keep these
tests in this one file, so that a single worker loads the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro import scopes
from repro.configs import registry
from repro.kernels import ops
from repro.models import ssm

D_MODEL, D_FF = 1024, 4096           # bert-large (configs/bert_large.py)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes, kernel):
    """Compiled text of ``fn`` for the described chip; asserts that a
    Pallas kernel (a ``tpu_custom_call``) is in it, and that every such
    call is an instruction named after ``kernel``, the name the kernel
    gives its ``pallas_call`` (so the device trace names it too)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert calls
    assert all(re.match(rf"(vmap_)?{kernel}[_.]", c) for c in calls), calls
    return text


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_fused_smw_compiles(one_chip, quant):
    d = D_FF
    if quant:
        def fn(j, v, sc):
            return ops.smw_rank1_update(j, v, gamma=0.9, scale=sc)
        shapes = [((d, d), jnp.int8), ((d,), jnp.float32),
                  ((), jnp.float32)]
    else:
        def fn(j, v):
            return ops.smw_rank1_update(j, v, gamma=0.9)
        shapes = [((d, d), jnp.bfloat16), ((d,), jnp.float32)]
    _compile(fn, one_chip, *shapes, kernel=scopes.SMW_KERNEL)


@pytest.mark.parametrize("with_pivot", [False, True],
                         ids=["no_pivot", "pivot"])
@pytest.mark.parametrize("rank", [2, 8])
def test_fused_block_smw_compiles(one_chip, rank, with_pivot):
    """The sub-(8, 128) r×r Gauss–Jordan tiles compile at r=2 (padded to
    8 sublanes) and r=8; with the pivot, the (1, 1) min-pivot output is a
    vector store (Mosaic refuses a scalar store into a VMEM block)."""
    def fn(j, v):
        return ops.smw_block_update(j, v, gamma=0.9, n_valid=rank,
                                    with_pivot=with_pivot)
    _compile(fn, one_chip, ((D_FF, D_FF), jnp.bfloat16),
             ((rank, D_FF), jnp.float32), kernel=scopes.BLOCK_SMW_KERNEL)


def test_fused_precondition_compiles(one_chip):
    """896×896 is a slice the fused kernel's VMEM plan admits."""
    d = 896
    assert ops.fused_precond_plan(d, d).fits
    before = ops.fallback_counts()

    def fn(l_inv, r_inv, g):
        return ops.fused_precondition(l_inv, r_inv, g)
    _compile(fn, one_chip, ((d, d), jnp.bfloat16), ((d, d), jnp.bfloat16),
             ((d, d), jnp.float32), kernel=scopes.PRECOND_KERNEL)
    assert ops.fallback_counts() == before


def test_fused_precondition_fallback_compiles(one_chip):
    """At bert-large's FFN slice the plan is over budget: the entry warns,
    counts the fallback, and compiles two Pallas matmuls instead."""
    d_in, d_out = D_MODEL, D_FF
    assert not ops.fused_precond_plan(d_in, d_out).fits
    key = ("fused_precond", "vmem_budget")
    before = ops.fallback_counts().get(key, 0)

    def fn(l_inv, r_inv, g):
        return ops.fused_precondition(l_inv, r_inv, g)
    with pytest.warns(ops.PallasFallbackWarning):
        _compile(fn, one_chip, ((d_out, d_out), jnp.bfloat16),
                 ((d_in, d_in), jnp.bfloat16), ((d_in, d_out), jnp.float32),
                 kernel=scopes.MATMUL_KERNEL)
    assert ops.fallback_counts().get(key, 0) == before + 1


@pytest.mark.parametrize("d_in,d_out", [(2560, 8960), (8960, 2560)])
def test_fused_precondition_fallback_compiles_at_rwkv6_slices(
        one_chip, d_in, d_out):
    """rwkv6-3b's wide slices, bf16 factors and a bf16 gradient, as the
    cell runs them: the fallback's two matmuls compile on the 1280-wide
    tiles of their plans, with the VMEM limit the plans raise."""
    plans = ops.precondition_matmul_plans(d_in, d_out)
    assert all(p.block == (1280, 1280, 1280) for p in plans)

    def fn(l_inv, r_inv, g):
        return ops.fused_precondition(l_inv, r_inv, g)
    with pytest.warns(ops.PallasFallbackWarning):
        text = _compile(fn, one_chip, ((d_out, d_out), jnp.bfloat16),
                        ((d_in, d_in), jnp.bfloat16),
                        ((d_in, d_out), jnp.bfloat16),
                        kernel=scopes.MATMUL_KERNEL)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 2


def test_rwkv_time_mix_gradient_loops_over_chunks_on_v5e(one_chip):
    """RWKV-6 time mixing and its gradient at rwkv6-3b's widths and the
    cell's 2 x 512 tokens, compiled for the chip: the wkv runs as one loop
    over the 512 / WKV_CHUNK chunks forward and one backward, and no
    buffer holds a state per token."""
    cfg = registry.get_config("rwkv6-3b")
    b, s = 2, 512
    p = jax.eval_shape(lambda: ssm.rwkv_init(jax.random.key(0), cfg,
                                             dtype=jnp.bfloat16))
    p = jax.tree.map(lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                                    sharding=one_chip), p)
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        y, st = ssm.rwkv_time_mix(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(st["wkv"])

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile() \
        .as_text()
    conds = re.findall(r" while\(.*?condition=%([\w.\-]+)", text)
    trips = []
    for cond in conds:
        body = re.search(rf"^%{re.escape(cond)} .*?^}}", text,
                         re.M | re.S).group(0)
        trips += [int(n) for n in re.findall(r"constant\((\d+)\)", body)]
    assert len(conds) == 2 and trips == [s // ssm.WKV_CHUNK] * 2, trips
    heads = cfg.d_model // cfg.rwkv_head_dim
    state = f"{b},{heads},{cfg.rwkv_head_dim},{cfg.rwkv_head_dim}"
    assert not re.search(rf"\[{s},{state}\]", text)
