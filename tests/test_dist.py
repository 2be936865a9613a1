"""Distributed MKOR (DESIGN.md §10): explicit collectives under shard_map
on fake CPU devices (tests/conftest.py pins 8), owner-sharded inversions,
and allclose-equivalence with the single-device banked path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import checkpointing
from repro.core import baseline_net, firstorder
from repro.core import stats as statlib
from repro.core.mkor import MKORConfig, manifest_for, mkor
from repro.launch import mesh as mesh_lib
from repro.sharding import collectives
from repro.training import loop as train_lib

WORLD = 8
pytestmark = pytest.mark.skipif(
    jax.device_count() < WORLD,
    reason=f"needs {WORLD} devices (conftest forces them on the CPU "
           "backend only)")


def _mesh(n_data=WORLD, **kw):
    return mesh_lib.make_host_mesh(n_data, **kw)


def _batch(step, d_in=96, n=64):
    rng = np.random.default_rng(step)
    basis = np.random.default_rng(0).standard_normal((8, d_in)) / 3
    x = (rng.standard_normal((n, 8)) @ basis).astype(np.float32)
    return {"x": x, "y": x}


def _copy(tree):
    return jax.tree.map(jnp.array, tree)


def _grads_fn(params, batch):
    return baseline_net.grads_and_full_stats(params, batch)


def _run_single(opt, params0, steps):
    """Per-step jitted single-device reference."""
    def step_fn(params, state, batch):
        loss, grads, stats = baseline_net.grads_and_full_stats(params, batch)
        upd, state = opt.update(grads, state, params=params, stats=stats,
                                loss=loss)
        return firstorder.apply_updates(params, upd), state, {"loss": loss}

    params, state = _copy(params0), opt.init(params0)
    jit_step = jax.jit(step_fn)
    losses = []
    for i in range(steps):
        params, state, m = jit_step(params, state, _batch(i))
        losses.append(float(m["loss"]))
    return params, state, losses


def _assert_trees_close(a, b, rtol=2e-4, atol=1e-5):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=atol), a, b)


# --------------------------------------------------------------------- #
# Collective primitives
# --------------------------------------------------------------------- #
def test_flat_all_reduce_matches_psum_mean(rng):
    mesh = _mesh()
    dist = (("data", WORLD),)
    tree = {"w": rng.standard_normal((WORLD, 5, 3)).astype(np.float32),
            "b": rng.standard_normal((WORLD, 7)).astype(np.float32)}

    def body(t):
        got = collectives.all_reduce_mean_tree(t, dist)
        want = jax.tree.map(
            lambda x: jax.lax.pmean(x, "data"), t)
        return got, want

    got, want = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False))(tree)
    _assert_trees_close(got, want, rtol=1e-6, atol=1e-7)


def test_pmean_rank1_stats_reduces_a_and_drops_full_stats(rng):
    mesh = _mesh()
    dist = (("data", WORLD),)
    stats = {"layers": [{"a": rng.standard_normal((WORLD, 6))
                         .astype(np.float32),
                         "A": rng.standard_normal((WORLD, 4, 6))
                         .astype(np.float32)}]}

    def body(s):
        local = jax.tree.map(lambda x: x[0], s)   # per-worker local stats
        return collectives.pmean_rank1_stats(local, dist,
                                             payload_dtype=None)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False))(stats)
    node = out["layers"][0]
    assert set(node) == {"a"}                 # O(d) contract: means only
    np.testing.assert_allclose(np.asarray(node["a"]),
                               stats["layers"][0]["a"].mean(0), rtol=1e-6)


def test_owner_shard_gather_roundtrip_is_identity():
    """owner_shard + per-chunk compute + gather_shards == full compute, for
    bank dims that do and do not divide the world size."""
    mesh = _mesh()
    dist = (("data", WORLD),)
    for n_slots in (3, 8, 11):
        x = jnp.arange(n_slots * 4, dtype=jnp.float32).reshape(n_slots, 4)

        def body(v):
            mine = collectives.owner_shard(v, dist)
            return collectives.gather_shards(2.0 * mine, dist, v.shape[0])

        out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                    out_specs=P(), check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(2.0 * x))


def test_bucket_owner_map_covers_every_slice_once():
    params = baseline_net.init_autoencoder(jax.random.key(0), 96,
                                           (48, 48, 12, 48))
    manifest = manifest_for(params, MKORConfig(exclude=()))
    for world in (1, 3, 8):
        owners = statlib.bucket_owner_map(manifest, world)
        for b in manifest:
            n = statlib.bucket_slices(b)
            ranges = owners[b.bucket_id]
            assert len(ranges) == world
            covered = [s for start, stop in ranges
                       for s in range(start, stop)]
            assert covered == list(range(n))
            # same static chunk rule the optimizer's sharding applies
            chunk = collectives.owner_chunk(n, world)
            assert all(stop - start <= chunk for start, stop in ranges)


def test_bucket_comm_cost_is_linear_vs_quadratic():
    b = statlib.FactorBucket(bucket_id="1024x4096", stack=(), extra=(),
                             d_in=1024, d_out=4096,
                             paths=(("x",), ("y",)), index=0)
    c = statlib.bucket_comm_cost(b, 8, 2, 2)
    assert c["rank1_stats_bytes_per_step"] == 2 * (1024 + 4096) * 2
    assert c["kfac_factor_bytes_per_inv"] == \
        2 * (1024 ** 2 + 4096 ** 2) * 2
    # owner-sharded gather ships 1/world of the factor bytes (2 slots over
    # 8 workers -> chunk 1 of 2 slots = 1/2; with slots >= world it is ~1/W)
    assert c["owner_gather_bytes_per_phase_step"] == \
        c["kfac_factor_bytes_per_inv"] // 2


# --------------------------------------------------------------------- #
# Acceptance: dist step == single-device banked path
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("stagger", [True, False])
def test_dist_step_matches_single_device(stagger):
    """8-worker shard_map step (flat grad reduce + rank-1 stat pmean +
    owner-sharded inversions) reproduces the single-device banked run:
    same params and opt_state after N steps, stagger on and off."""
    steps = 6
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=2, stagger=stagger, exclude=())
    params0 = baseline_net.init_autoencoder(jax.random.key(0), 96,
                                            (48, 12, 48))

    p_ref, s_ref, ref_losses = _run_single(
        mkor(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        params0, steps)

    opt_d = mkor(firstorder.sgd(1e-2, momentum=0.9),
                 MKORConfig(dist=dist, **common))
    step = train_lib.make_dist_step_fn(_grads_fn, opt_d, mesh, ("data",),
                                       stats_payload_dtype=None)
    p, s = _copy(params0), opt_d.init(params0)
    losses = []
    for i in range(steps):
        p, s, m = step(p, s, _batch(i))
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    _assert_trees_close(p, p_ref)
    _assert_trees_close(s, s_ref)


def test_dist_step_composes_with_chunk_runner():
    """The dist step slots into train_epoch's jitted lax.scan chunk runner
    unchanged (the tentpole's 'composed with the existing chunk runner')."""
    steps = 4
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=2, exclude=())
    params0 = baseline_net.init_autoencoder(jax.random.key(0), 96,
                                            (48, 12, 48))
    p_ref, s_ref, _ = _run_single(
        mkor(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        params0, steps)

    opt_d = mkor(firstorder.sgd(1e-2, momentum=0.9),
                 MKORConfig(dist=dist, **common))
    step = train_lib.make_dist_step_fn(_grads_fn, opt_d, mesh, ("data",),
                                       stats_payload_dtype=None)
    p, s, hist = train_lib.train_epoch(
        step, _copy(params0), opt_d.init(params0),
        [_batch(i) for i in range(steps)], chunk=2)
    assert len(hist) == steps
    assert np.isfinite([h["loss"] for h in hist]).all()
    _assert_trees_close(p, p_ref)
    _assert_trees_close(s, s_ref)


def test_dist_step_multi_pod_axes():
    """Owner sharding + collectives across the composite ("pod", "data")
    axis: worker_index/all_gather ordering must agree across axes."""
    steps = 5
    mesh = _mesh(2, n_pod=2)                  # (2, 2, 1) = 4 devices
    axes = mesh_lib.mesh_axes(mesh)
    assert axes.data == ("pod", "data")
    dist = collectives.dist_axes(mesh, axes)
    assert collectives.world_size(dist) == 4
    common = dict(inv_freq=2, stagger=True, exclude=())
    params0 = baseline_net.init_autoencoder(jax.random.key(1), 96,
                                            (48, 12, 48))
    p_ref, s_ref, _ = _run_single(
        mkor(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        params0, steps)

    opt_d = mkor(firstorder.sgd(1e-2, momentum=0.9),
                 MKORConfig(dist=dist, **common))
    step = train_lib.make_dist_step_fn(_grads_fn, opt_d, mesh,
                                       ("pod", "data"),
                                       stats_payload_dtype=None)
    p, s = _copy(params0), opt_d.init(params0)
    for i in range(steps):
        p, s, _ = step(p, s, _batch(i))
    _assert_trees_close(p, p_ref)
    _assert_trees_close(s, s_ref)


def test_dist_step_bf16_payload_default_stays_close():
    """The default bf16 stat payload (Lemma 3.2 precision) tracks the fp32
    run within bf16 tolerance and keeps training finite."""
    steps = 6
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=2, exclude=())
    params0 = baseline_net.init_autoencoder(jax.random.key(0), 96,
                                            (48, 12, 48))
    p_ref, _, _ = _run_single(
        mkor(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        params0, steps)

    opt_d = mkor(firstorder.sgd(1e-2, momentum=0.9),
                 MKORConfig(dist=dist, **common))
    step = train_lib.make_dist_step_fn(_grads_fn, opt_d, mesh, ("data",))
    p, s = _copy(params0), opt_d.init(params0)
    for i in range(steps):
        p, s, m = step(p, s, _batch(i))
        assert np.isfinite(float(m["loss"]))
    _assert_trees_close(p, p_ref, rtol=3e-2, atol=3e-3)


def test_dist_owner_sharded_pallas_matches_jnp():
    """use_pallas (interpret) under the dist step: the banked kernels accept
    the locally-sliced owner chunks and match the jnp dist path."""
    steps = 3
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=1, exclude=(), dist=dist)
    params0 = baseline_net.init_autoencoder(jax.random.key(2), 24, (16, 16))

    outs = {}
    for use_pallas in (False, True):
        opt = mkor(firstorder.sgd(1e-2, momentum=0.9),
                   MKORConfig(use_pallas=use_pallas, interpret=use_pallas,
                              **common))
        step = train_lib.make_dist_step_fn(_grads_fn, opt, mesh, ("data",),
                                           stats_payload_dtype=None)
        p, s = _copy(params0), opt.init(params0)
        for i in range(steps):
            p, s, _ = step(p, s, _batch(i, 24))
        outs[use_pallas] = p
    _assert_trees_close(outs[True], outs[False], rtol=2e-4, atol=5e-5)


def test_dist_rank_r_matches_single_device(ae_params):
    """Block rank-r under the dist step: windows are rebuilt identically on
    every worker from the synced per-step stats (zero extra wire bytes) and
    the owner-sharded block inversions reproduce the single-device run —
    params, factors, AND window state (counts included)."""
    steps = 5
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=2, rank=2, stagger=True, exclude=())
    params0 = ae_params
    p_ref, s_ref, _ = _run_single(
        mkor(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        params0, steps)

    opt_d = mkor(firstorder.sgd(1e-2, momentum=0.9),
                 MKORConfig(dist=dist, **common))
    step = train_lib.make_dist_step_fn(_grads_fn, opt_d, mesh, ("data",),
                                       stats_payload_dtype=None)
    p, s = _copy(params0), opt_d.init(params0)
    for i in range(steps):
        p, s, _ = step(p, s, _batch(i))
    _assert_trees_close(p, p_ref)
    _assert_trees_close(s, s_ref)
    assert "stat_windows" in s


def test_dist_async_step_matches_single_device(ae_params):
    """staleness=1 under the 8-worker shard_map step: the precompute tick
    (owner-sharded pending inversions inside the phase cond) overlaps the
    split grad reduce-scatter/all-gather, and must still reproduce the
    single-device async run — params, both banks, and window state."""
    steps = 6
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=2, stagger=True, staleness=1, exclude=())
    params0 = ae_params
    p_ref, s_ref, ref_losses = _run_single(
        mkor(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        params0, steps)

    opt_d = mkor(firstorder.sgd(1e-2, momentum=0.9),
                 MKORConfig(dist=dist, **common))
    assert opt_d.precompute is not None       # dist step uses the 2-phase path
    step = train_lib.make_dist_step_fn(_grads_fn, opt_d, mesh, ("data",),
                                       stats_payload_dtype=None)
    p, s = _copy(params0), opt_d.init(params0)
    losses = []
    for i in range(steps):
        p, s, m = step(p, s, _batch(i))
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    _assert_trees_close(p, p_ref)
    _assert_trees_close(s, s_ref)
    assert "pending_banks" in s and "stat_windows" in s


def test_dist_hybrid_switch_identical_across_shards(ae_params):
    """MKOR-H under the dist step (satellite): the sticky switch decision
    is computed from the pmean'd loss, so the replicated hybrid state must
    match the single-device run exactly — same trip step, same stickiness."""
    steps = 8
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    from repro.core.mkor import mkor_h
    common = dict(hybrid=True, hybrid_min_steps=2, hybrid_threshold=0.9,
                  inv_freq=2, stagger=True, exclude=())
    params0 = ae_params
    p_ref, s_ref, _ = _run_single(
        mkor_h(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        params0, steps)
    assert not bool(s_ref["hybrid"]["on"])    # threshold 0.9 must trip

    opt_d = mkor_h(firstorder.sgd(1e-2, momentum=0.9),
                   MKORConfig(dist=dist, **common))
    step = train_lib.make_dist_step_fn(_grads_fn, opt_d, mesh, ("data",),
                                       stats_payload_dtype=None)
    p, s = _copy(params0), opt_d.init(params0)
    for i in range(steps):
        p, s, _ = step(p, s, _batch(i))
    assert bool(s["hybrid"]["on"]) == bool(s_ref["hybrid"]["on"])
    np.testing.assert_allclose(np.asarray(s["hybrid"]["ema_fast"]),
                               np.asarray(s_ref["hybrid"]["ema_fast"]),
                               rtol=1e-5)
    _assert_trees_close(p, p_ref)


def test_dist_step_rejects_indivisible_batch():
    mesh = _mesh()
    opt = mkor(firstorder.sgd(1e-2), MKORConfig(exclude=()))
    step = train_lib.make_dist_step_fn(_grads_fn, opt, mesh, ("data",))
    params = baseline_net.init_autoencoder(jax.random.key(0), 96, (48,))
    with pytest.raises(ValueError, match="does not divide"):
        step(params, opt.init(params), _batch(0, n=12))


def _dist_train_step_matches_single_device(cfg):
    from repro.data import pipeline

    from repro.models import model as model_lib
    params0 = model_lib.init_params(jax.random.key(0), cfg)
    ds = pipeline.make_dataset(cfg, global_batch=8, seq_len=16)
    batches = [pipeline.make_batch(ds, i) for i in range(2)]

    mcfg = MKORConfig(inv_freq=1)
    opt = mkor(firstorder.lamb(1e-3), mcfg)
    step = jax.jit(train_lib.make_train_step(cfg, opt))
    p_ref, s_ref = _copy(params0), opt.init(params0)
    for b in batches:
        p_ref, s_ref, m_ref = step(p_ref, s_ref, b)

    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    opt_d = mkor(firstorder.lamb(1e-3),
                 MKORConfig(inv_freq=1, dist=dist))
    dstep = train_lib.make_dist_train_step(cfg, opt_d, mesh,
                                           stats_payload_dtype=None)
    p, s = _copy(params0), opt_d.init(params0)
    for b in batches:
        p, s, m = dstep(p, s, b)

    assert float(m["loss"]) == pytest.approx(float(m_ref["loss"]),
                                             rel=1e-4)
    _assert_trees_close(p, p_ref, rtol=5e-4, atol=5e-5)


# --------------------------------------------------------------------- #
# Elastic fault tolerance (DESIGN.md §15): liveness, remap, resume
# --------------------------------------------------------------------- #
def test_bucket_owner_map_liveness_remaps_over_survivors():
    params = baseline_net.init_autoencoder(jax.random.key(0), 96,
                                           (48, 48, 12, 48))
    manifest = manifest_for(params, MKORConfig(exclude=()))
    for dead in ([3], [0, 7], [1, 2, 3]):
        live = tuple(w not in dead for w in range(WORLD))
        owners = statlib.bucket_owner_map(manifest, WORLD, live)
        n_live = sum(live)
        for b in manifest:
            n = statlib.bucket_slices(b)
            ranges = owners[b.bucket_id]
            # dead workers own nothing; survivors cover every slice once
            assert all(ranges[w] == (0, 0) for w in dead)
            covered = [s for start, stop in ranges
                       for s in range(start, stop)]
            assert covered == list(range(n))
            chunk = collectives.owner_chunk(n, n_live)
            assert all(stop - start <= chunk for start, stop in ranges)


def test_live_mask_validation():
    assert statlib.live_mask(4, None) == (True,) * 4
    with pytest.raises(ValueError, match="entries"):
        statlib.live_mask(4, (True, False))
    with pytest.raises(ValueError, match="dead"):
        statlib.live_mask(2, (False, False))


def test_owner_shard_gather_roundtrip_with_dead_worker():
    """Remapped owner_shard + gather_shards is still the identity when a
    worker is dead — survivors take over its slices and the masked psum
    zeroes the dead worker's contribution."""
    mesh = _mesh()
    dist = (("data", WORLD),)
    live = (True, True, True, False, True, True, True, False)
    for n_slots in (3, 8, 11):
        x = jnp.arange(n_slots * 4, dtype=jnp.float32).reshape(n_slots, 4)

        def body(v):
            mine = collectives.owner_shard(v, dist, live=live)
            return collectives.gather_shards(2.0 * mine, dist,
                                             v.shape[0], live=live)

        out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                    out_specs=P(), check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(2.0 * x))


def test_dist_remap_step_matches_fully_live(ae_params):
    """The elastic-remapped step (one worker dead, owners re-split over
    the survivors) computes the SAME update as the static owner map —
    failover redistributes the inversion work, it never changes the
    math (DESIGN.md §15)."""
    steps = 5
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=2, stagger=True, staleness=1, exclude=(),
                  dist=dist)
    live = (True, True, True, False, True, True, True, True)

    outs = {}
    for name, mask in (("static", None), ("remap", live)):
        opt = mkor(firstorder.sgd(1e-2, momentum=0.9),
                   MKORConfig(live=mask, **common))
        step = train_lib.make_dist_step_fn(_grads_fn, opt, mesh,
                                           ("data",),
                                           stats_payload_dtype=None)
        p, s = _copy(ae_params), opt.init(ae_params)
        for i in range(steps):
            p, s, _ = step(p, s, _batch(i))
        outs[name] = (p, s)
    _assert_trees_close(outs["remap"][0], outs["static"][0])
    _assert_trees_close(outs["remap"][1], outs["static"][1])


@pytest.mark.parametrize("new_world", [4, 1])
def test_elastic_resume_into_smaller_world(tmp_path, ae_params,
                                           new_world):
    """W=8 owner-sharded run, checkpoint mid-training, restore into a
    W'-way world and finish: the result must match the uninterrupted
    single-device run (the state tree is replicated/world-independent;
    owner maps re-derive at trace time) and the persisted data cursor
    must hand back the first unconsumed batch."""
    from repro.data import pipeline

    steps, cut = 6, 3
    common = dict(inv_freq=2, stagger=True, exclude=())
    p_ref, s_ref, _ = _run_single(
        mkor(firstorder.sgd(1e-2, momentum=0.9), MKORConfig(**common)),
        ae_params, steps)

    def dist_step_for(world):
        mesh = _mesh(world)
        dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
        opt = mkor(firstorder.sgd(1e-2, momentum=0.9),
                   MKORConfig(dist=dist, **common))
        return opt, train_lib.make_dist_step_fn(
            _grads_fn, opt, mesh, ("data",), stats_payload_dtype=None)

    # W=8 run to the cut, checkpoint with the data cursor
    opt8, step8 = dist_step_for(8)
    p, s = _copy(ae_params), opt8.init(ae_params)
    for i in range(cut):
        p, s, _ = step8(p, s, _batch(i))
    checkpointing.save(
        str(tmp_path), cut - 1, (p, s),
        {"step": cut - 1, "world": 8,
         "cursor": pipeline.cursor_metadata(
             pipeline.cursor_for_step(cut))})

    # restore into the W' world and finish
    like = (ae_params, opt8.init(ae_params))
    (p, s), meta, latest = checkpointing.restore_latest_valid(
        str(tmp_path), like)
    assert latest == cut - 1 and meta["world"] == 8
    cur = pipeline.cursor_from_metadata(meta)
    assert cur.step == cut                     # no chunk double-trained
    if new_world == 1:
        opt_n = mkor(firstorder.sgd(1e-2, momentum=0.9),
                     MKORConfig(**common))
        step_fn = jax.jit(lambda pp, ss, b: _apply_local(opt_n, pp, ss, b))
        for i in range(cur.step, steps):
            p, s, _ = step_fn(p, s, _batch(i))
    else:
        _, step_n = dist_step_for(new_world)
        for i in range(cur.step, steps):
            p, s, _ = step_n(p, s, _batch(i))
    _assert_trees_close(p, p_ref)
    _assert_trees_close(s, s_ref)


def _apply_local(opt, params, state, batch):
    loss, grads, stats = baseline_net.grads_and_full_stats(params, batch)
    upd, state = opt.update(grads, state, params=params, stats=stats,
                            loss=loss)
    return firstorder.apply_updates(params, upd), state, {"loss": loss}


@pytest.mark.slow   # two 30-step elastic runs + a remap recompile
def test_kill_shard_recovery_slope_at_least_half_of_clean(ae_params):
    """ISSUE 9 acceptance: after kill_shard the run must keep converging
    — quarantined orphans train first-order until fresh windows rebuild
    their factors, and the fitted log-loss slope of the faulted run's
    tail is at least half the clean run's over the same steps."""
    from repro.training import chaos as chaos_lib
    from repro.training import resilience

    steps, kill_at, tail = 30, 6, 12
    mesh = _mesh()
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    common = dict(inv_freq=2, stagger=True, staleness=1, health=True,
                  exclude=(), dist=dist)
    mcfg = MKORConfig(**common)

    def factory(live):
        opt = mkor(firstorder.sgd(1e-2, momentum=0.9),
                   MKORConfig(live=live, **common))
        step = train_lib.make_dist_step_fn(_grads_fn, opt, mesh,
                                           ("data",),
                                           stats_payload_dtype=None)
        return train_lib.make_chunk_runner(step, donate=False)

    def run(plan):
        opt = mkor(firstorder.sgd(1e-2, momentum=0.9), mcfg)
        sup = resilience.ElasticSupervisor(WORLD)
        _, _, hist, _ = resilience.elastic_train(
            factory, _copy(ae_params), opt.init(ae_params),
            make_batch=_batch, stack_batches=train_lib.stack_batches,
            start=0, steps=steps, chunk=6, supervisor=sup,
            plan=plan, mcfg=mcfg, sleep=lambda s: None)
        return np.asarray([h["loss"] for h in hist])

    clean = run(None)
    faulted = run(chaos_lib.parse_chaos_spec(f"kill_shard@{kill_at}:3"))
    assert np.isfinite(faulted).all()

    def slope(losses):
        y = np.log(np.maximum(np.asarray(losses, np.float64), 1e-30))
        return float(np.polyfit(np.arange(len(y)), y, 1)[0])

    clean_slope, fault_slope = slope(clean[tail:]), slope(faulted[tail:])
    assert clean_slope < 0, "clean run is not converging; test is vacuous"
    assert fault_slope <= 0.5 * clean_slope, \
        (f"recovery slope {fault_slope:.4f}/step vs clean "
         f"{clean_slope:.4f}/step")


def test_dist_train_step_model_matches_single_device(tiny_model_cfg):
    """make_dist_train_step on a real model config == make_train_step
    after 2 steps (params allclose; fp32 stat payload for tightness).
    Tier-1 uses the shared tiny 2-layer config — the check is about the
    dist plumbing; the real-architecture variant below runs nightly."""
    _dist_train_step_matches_single_device(tiny_model_cfg)


@pytest.mark.slow   # bert-large-reduced compile was a ~30s tier-1 offender
def test_dist_train_step_real_arch_matches_single_device():
    """Same equivalence on bert-large reduced: multi-bucket manifest,
    embed/lm_head exclusions, real attention shapes (nightly CI job)."""
    from repro.configs import registry
    _dist_train_step_matches_single_device(
        registry.get_config("bert-large").reduced())
