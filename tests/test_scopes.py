"""Named stages of the training step (repro/scopes.py): every stage's
scope reaches the compiled step's HLO ``op_name`` metadata, on the einsum
and the Pallas (interpret) paths and in the data-parallel step, and the
chunk loop's host spans are one callable (training/loop.run_chunk)."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.core import firstorder
from repro.core.mkor import MKORConfig, mkor
from repro.models import model as model_lib
from repro.training import loop

STEP_STAGES = (scopes.FORWARD, scopes.BACKWARD, scopes.MKOR_STATS,
               scopes.MKOR_SMW, scopes.MKOR_PRECONDITION, scopes.BACKEND,
               scopes.APPLY)


def op_names(compiled_text):
    return re.findall(r'op_name="([^"]+)"', compiled_text)


def stages(compiled_text):
    return collections.Counter(scopes.stage_of(n)
                               for n in op_names(compiled_text))


@pytest.mark.parametrize("op_name,stage", [
    ("jit(run_chunk)/while/body/closed_call/forward/dot_general", "forward"),
    ("jit(step)/transpose(jvp(forward))/blocks/mkor_stats/reduce_sum",
     "mkor_stats"),
    ("jit(step)/transpose(jvp(forward))/dot_general", "backward"),
    ("jit(step)/jvp(forward)/tanh", "forward"),
    ("jit(step)/forward/transpose", "forward"),
    ("jit(s)/cond/branch_1_fun/mkor_smw/vmap(mkor_smw)/pallas_call",
     "mkor_smw"),
    ("jit(s)/mkor_precondition/vmap(mkor_matmul)/pallas_call",
     "mkor_precondition"),
    ("jit(s)/mkor_smw/owner_gather/all_gather", "owner_gather"),
    ("jit(s)/backend/sqrt", "backend"),
    ("jit(s)/while/body/closed_call/eq", ""),
])
def test_stage_of_is_the_innermost_stage(op_name, stage):
    assert scopes.stage_of(op_name) == stage


def _tiny_runner(cfg, use_pallas):
    opt = mkor(firstorder.lamb(1e-3),
               MKORConfig(inv_freq=1, use_pallas=use_pallas,
                          interpret=use_pallas))
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    runner = loop.make_chunk_runner(loop.make_train_step(cfg, opt))
    return runner, params, opt.init(params)


def _batches(n, steps=1, batch=2, seq=8, vocab=64):
    rng = np.random.default_rng(n)
    return [{"tokens": rng.integers(0, vocab, (batch, seq), np.int32),
             "labels": rng.integers(0, vocab, (batch, seq), np.int32)}
            for _ in range(steps)]


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["einsum", "pallas"])
def test_chunk_runner_carries_every_stage(tiny_model_cfg, use_pallas):
    """The compiled one-step chunk runner (MKOR bank layout over LAMB)
    labels ops of every stage, the backward pass by ``transpose(`` around
    ``forward``; on the Pallas path the kernels carry their names."""
    runner, params, state = _tiny_runner(tiny_model_cfg, use_pallas)
    text = runner.lower(params, state, loop.stack_batches(_batches(0))) \
        .compile().as_text()
    seen = stages(text)
    assert all(seen[s] for s in STEP_STAGES), seen
    names = op_names(text)
    assert any("transpose(jvp(forward))" in n for n in names)
    if use_pallas:
        for kernel in (scopes.SMW_KERNEL, scopes.PRECOND_KERNEL):
            assert any(f"({kernel})" in n or f"/{kernel}/" in n
                       for n in names), kernel


def test_dist_step_carries_collective_scopes():
    """The data-parallel step on the 8 fake CPU devices labels its
    gradient and statistic all-reduces and the owner-sharded gather."""
    from repro.launch import mesh as mesh_lib
    from repro.models.config import ModelConfig
    from repro.sharding import collectives
    if jax.device_count() < 8:
        pytest.skip("needs the 8 fake CPU devices of tests/conftest.py")
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32", scan_layers=False, remat=False,
                      vocab_pad_multiple=1)
    mesh = mesh_lib.make_host_mesh(n_data=8)
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    opt = mkor(firstorder.lamb(1e-3), MKORConfig(inv_freq=1, dist=dist))
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    step = loop.make_dist_train_step(cfg, opt, mesh)
    batch = _batches(1, batch=8)[0]
    seen = stages(step.lower(params, opt.init(params), batch)
                  .compile().as_text())
    for s in (scopes.GRAD_ALLREDUCE, scopes.STAT_ALLREDUCE,
              scopes.OWNER_GATHER, scopes.FORWARD, scopes.BACKEND):
        assert seen[s], (s, seen)


def test_run_chunk_matches_the_runner(tiny_model_cfg):
    """run_chunk is the runner on the stacked batches, with the metrics
    fetched to the host: the same losses and parameters to the bit."""
    runner, params, state = _tiny_runner(tiny_model_cfg, False)
    batches = _batches(2, steps=3)

    def copy(tree):
        return jax.tree.map(jnp.array, tree)

    p_ref, _, m_ref = runner(copy(params), copy(state),
                             loop.stack_batches(batches))
    p, _, metrics = loop.run_chunk(runner, copy(params), copy(state),
                                   batches, 0)
    assert isinstance(metrics["loss"], np.ndarray)
    np.testing.assert_array_equal(metrics["loss"],
                                  np.asarray(m_ref["loss"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p, p_ref)
