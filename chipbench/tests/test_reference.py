"""MKOR's plain reference over every leading dimension of a leaf: each
(layer, expert) slice of a weight (depth, experts, d_in, d_out) has its
own factor pair, its own stabilize and its own rescale."""
import jax
import jax.numpy as jnp
import numpy as np

import check
import harness
from reference import mkor_lamb
from test_check import small_cell


def test_two_leading_dimensions_are_a_loop_over_the_one_dimension_path():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    # slices of very different sizes: stabilize blends and caps some of
    # them and leaves others alone, each by its own max
    size = jnp.asarray([[0.1, 30.0, 90.0], [2.0, 60.0, 0.5]])[..., None, None]
    f = jnp.eye(6) + size * jax.random.normal(ks[0], (2, 3, 6, 6))
    v = jax.random.normal(ks[1], (2, 3, 6))
    l = jnp.eye(4) + jax.random.normal(ks[2], (2, 3, 4, 4))
    r = jnp.eye(6) + size * jax.random.normal(ks[3], (2, 3, 6, 6))
    g = size * jax.random.normal(ks[4], (2, 3, 6, 4))

    def update(f, v):
        return mkor_lamb._smw(mkor_lamb._stabilize(f, 50.0, 0.95), v, 0.9)

    both = mkor_lamb.per_slice(update, (2, 3), f, v)
    pre = mkor_lamb.per_slice(mkor_lamb._precondition, (2, 3), l, r, g)
    for e in range(3):
        np.testing.assert_allclose(both[:, e], jax.vmap(update)(
            f[:, e], v[:, e]), rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(pre[:, e], jax.vmap(
            mkor_lamb._precondition)(l[:, e], r[:, e], g[:, e]),
            rtol=1e-6, atol=1e-5)
    # a slice's rescale is its own: every slice keeps its gradient's norm
    np.testing.assert_allclose(jnp.linalg.norm(pre, axis=(-2, -1)),
                               jnp.linalg.norm(g, axis=(-2, -1)), rtol=1e-5)


def test_one_leading_dimension_keeps_the_arithmetic(monkeypatch):
    """Leaves stacked over depth alone, as every leaf of rwkv6-3b is, go
    through the one vmap over depth the reference had before it took
    more leading dimensions: the numbers are equal, not close."""
    run = harness.Run(small_cell(), 2 ** 31 + 99)
    w = jax.device_get(run.weights())
    batches = run.pool()[:3]
    now = harness.reference_numbers(run, w, batches)
    monkeypatch.setattr(mkor_lamb, "per_slice",
                        lambda fn, lead, *xs: jax.vmap(fn)(*xs))
    was = harness.reference_numbers(run, w, batches)
    assert now["loss"] == was["loss"]
    assert now["grad"] == was["grad"] and now["update"] == was["update"]
    for k in was["factor"]:
        np.testing.assert_array_equal(now["factor"][k], was["factor"][k])


class ProgramModel:
    """The program's own loss as a reference module, so that the two
    optimizers are compared on the same gradients and statistics."""

    def __init__(self, cfg):
        from repro.training import loop
        self.loss_fn = loop.make_loss_fn(cfg)

    def loss_and_stats(self, params, batch, sz):
        def means(node):
            if isinstance(node, dict):
                return node["a"] if set(node) == {"a"} else {
                    k: means(v) for k, v in node.items()}
            if isinstance(node, (list, tuple)):
                return [means(v) for v in node]
            return node
        loss, aux = self.loss_fn(params, batch)
        return loss, means(aux["stats"])


def test_reference_is_the_programs_per_expert_mkor(data_cell):
    """Three steps of a small qwen2-moe with one factor pair per expert:
    every bucket takes a phase step (inv_freq 2), and the first bucket
    (the experts' out, d_in 48) a second one, at which the threshold 1.1
    lies among its slices' maxima (1.05 to 1.25 with norms scaled by 2):
    stabilize acts on some experts and not on others."""
    from repro.core import firstorder
    from repro.core.mkor import MKORConfig, mkor
    cell = data_cell("qwen2-moe-a2.7b.small", chunk=3, pool_chunks=1,
                     inv_freq=2, threshold=1.1)
    cell["model"]["init"]["scale"] = ["const", 2.0]
    run = harness.Run(cell, 2 ** 31 + 11)
    o = run.opt_spec
    mcfg = MKORConfig(gamma=o["gamma"], inv_freq=o["inv_freq"],
                      stabilizer_threshold=o["threshold"], zeta=o["zeta"],
                      factor_dtype="float32")
    opt = mkor(firstorder.lamb(o["lr"]), mcfg)
    params = run.weights()
    params0 = jax.device_get(params)
    batches = run.pool()
    drv = harness.Driver(run, run.runner(opt), params,
                         jax.jit(opt.init)(params), batches)
    first = drv.chunk()
    prog = harness.program_numbers(run, mcfg, params0, drv.params,
                                   drv.opt_state, first)
    experts = [k for k, v in prog["factor"].items() if np.ndim(v) == 2]
    assert experts and all(np.shape(prog["factor"][k]) == (2, 4)
                           for k in experts)
    model = ProgramModel(run.cfg)
    gaps = check.gaps(prog, mkor_lamb.run(model, params0, batches, run.sz,
                                          o))
    for number in ("loss", "grad", "update", "factor", "grad_worst_leaf",
                   "update_worst_leaf"):
        assert gaps[number][0] < 1e-4, (number, gaps[number])
    # and LAMB's second moment of each expert's weights, slice by slice:
    # the squares of the preconditioned gradients of its three steps, each
    # rescaled to its own slice's gradient norm
    p = jax.tree.map(jnp.asarray, params0)
    st = mkor_lamb.init_state(p, o)
    step = mkor_lamb.make_step(model.loss_and_stats, run.sz, o)
    for batch in batches:
        p, st, _ = step(p, st, jax.device_put(batch))
    for side in ("in", "gate", "out"):
        want = np.asarray(st["v"]["blocks"][0]["mlp"][side]["w"])
        have = np.asarray(drv.opt_state["backend"]["v"]["blocks"][0]["mlp"]
                          [side]["w"])
        slice_max = np.max(want, axis=(-2, -1), keepdims=True)
        assert np.max(np.abs(have - want) / slice_max) < 1e-4, side

