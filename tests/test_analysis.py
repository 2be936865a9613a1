"""mkor-lint (repro.analysis) tests.

Two halves, mirroring the checker contract:

* seeded-violation fixtures — deliberately-broken programs, at least one
  per checker, each asserting the checker's stable diagnostic code fires
  AND that no checker beyond the expected set errors on the fixture;
* clean passes — the real bert-large single / chunk / dist steps lint
  with zero errors, with non-vacuity assertions (the walker really sees
  the collectives; the known VMEM fallback warnings really appear).

Plus unit coverage for the plan API, the fallback counter, the chunk
schedule retrace bound, and the Report container.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import jaxpr_walk, trace
from repro.analysis.checkers import run_checkers
from repro.analysis.diagnostics import Diagnostic, Report, Severity
from repro.analysis.trace import LintTarget
from repro.core import firstorder
from repro.core.mkor import MKORConfig, manifest_for
from repro.kernels import ops
from repro.training import loop as train_lib


def _error_checkers(report):
    return {d.checker for d in report.errors}


# --------------------------------------------------------------------- #
# Report / registry plumbing
# --------------------------------------------------------------------- #
def test_report_basics(tmp_path):
    r = Report()
    assert r.exit_code() == 0
    r.add(Diagnostic("c1", "x.warn", Severity.WARNING, "w", target="t"))
    assert r.exit_code() == 0 and len(r.warnings) == 1
    r.add(Diagnostic("c2", "x.err", Severity.ERROR, "e", target="t",
                     context={"k": 1}))
    assert r.exit_code() == 1 and len(r.errors) == 1
    assert [d.code for d in r.by_code("x.err")] == ["x.err"]
    rendered = r.render()
    # errors sort above warnings and the summary line counts both
    assert rendered.index("x.err") < rendered.index("x.warn")
    assert "1 error(s), 1 warning(s)" in rendered
    out = tmp_path / "report.json"
    payload = json.loads(r.to_json(str(out)))
    assert payload["exit_code"] == 1 and payload["n_warnings"] == 1
    assert json.loads(out.read_text())["n_errors"] == 1


def test_run_checkers_rejects_unknown_name():
    with pytest.raises(KeyError, match="no-such-checker"):
        run_checkers([], names=["no-such-checker"])


# --------------------------------------------------------------------- #
# Seeded violation 1: per-step O(d^2) factor payload (comm-linearity)
# --------------------------------------------------------------------- #
def test_seeded_factor_payload_trips_comm_lint():
    """A KFAC-style step that psums a full (256, 256) factor matrix every
    step (no phase gate) must raise comm.factor-payload-per-step."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def bad_step(x):
        return jax.shard_map(
            lambda v: jax.lax.psum(v, "d"),
            mesh=mesh, in_specs=P(), out_specs=P())(x)

    target = trace.custom_target(
        "fixture/kfac-style-psum", bad_step,
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        meta={"factor_dims": {256}, "n_dense_layers": 4,
              "grad_f32_bytes": 10 * 2 ** 20, "world": 8})
    report = run_checkers([target])
    errs = report.by_code("comm.factor-payload-per-step")
    assert errs and all(d.severity == Severity.ERROR for d in errs)
    assert report.exit_code() == 1
    assert _error_checkers(report) == {"comm-linearity"}


def test_seeded_collective_count_drift_trips_comm_lint():
    """More ungated collectives than the explicit-collective design
    allows (n_dense + 8 fixed) raises comm.collective-count-drift."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def chatty_step(xs):
        def inner(xs):
            # per-leaf psums — the drift the bucketed design removed
            return [jax.lax.psum(x, "d") for x in xs]
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(xs)

    xs = [jax.ShapeDtypeStruct((16,), jnp.float32)] * 12
    target = trace.custom_target(
        "fixture/per-leaf-psums", chatty_step, xs,
        meta={"n_dense_layers": 2, "world": 8})
    report = run_checkers([target])
    assert report.by_code("comm.collective-count-drift")
    assert report.exit_code() == 1
    assert _error_checkers(report) == {"comm-linearity"}


# --------------------------------------------------------------------- #
# Seeded violation 2: float64 promotion (dtype-discipline)
# --------------------------------------------------------------------- #
def test_seeded_f64_promotion_trips_dtype_lint():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: jnp.sum(x.astype(jnp.float64) * 2.0))(
            jax.ShapeDtypeStruct((8, 8), jnp.float32))
    target = LintTarget(name="fixture/f64", kind="custom", jaxpr=jaxpr)
    report = run_checkers([target])
    errs = report.by_code("dtype.f64-promotion")
    assert errs and report.exit_code() == 1
    assert _error_checkers(report) == {"dtype-discipline"}


# --------------------------------------------------------------------- #
# Seeded violation 3: over-budget kernel with no fallback (pallas)
# --------------------------------------------------------------------- #
def test_seeded_vmem_over_budget_trips_pallas_lint():
    """A d=32000 factor at window rank 128 plans a fused_block_smw
    dispatch past the 12MB VMEM budget; that kernel has no fallback, so
    the lint must hard-error before anything would dispatch."""
    params = {"layer": {
        "w": jax.ShapeDtypeStruct((32000, 512), jnp.bfloat16),
        "probe": jax.ShapeDtypeStruct((512,), jnp.float32)}}
    cfg = MKORConfig(rank=128, exclude=())
    target = LintTarget(
        name="fixture/vmem-blowout", kind="custom",
        meta={"manifest": manifest_for(params, cfg), "mkor_cfg": cfg})
    report = run_checkers([target])
    errs = report.by_code("pallas.vmem-over-budget")
    assert errs and report.exit_code() == 1
    assert any(d.context.get("kernel") == "fused_block_smw" for d in errs)
    assert _error_checkers(report) == {"pallas-kernels"}


# --------------------------------------------------------------------- #
# Seeded violation 4: chunk runner without donation (donation)
# --------------------------------------------------------------------- #
def _chunk_fixture_target(tiny_model_cfg, donate):
    opt = firstorder.sgd(1e-2)
    step = train_lib.make_train_step(tiny_model_cfg, opt)
    runner = train_lib.make_chunk_runner(step, donate=donate)
    params, opt_state = trace.abstract_state(tiny_model_cfg, opt)
    batch = train_lib.train_batch_shapes(tiny_model_cfg, 4, 8)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype), batch)
    return LintTarget(
        name=f"fixture/chunk-donate={donate}", kind="custom",
        jaxpr=jax.make_jaxpr(runner)(params, opt_state, stacked),
        lowered_text=runner.lower(params, opt_state, stacked).as_text(),
        meta={"n_carry_leaves": len(jax.tree.leaves((params, opt_state))),
              "chunk": 2, "steps": 100})


def test_seeded_missing_donation_trips_donation_lint(tiny_model_cfg):
    report = run_checkers([_chunk_fixture_target(tiny_model_cfg, False)])
    errs = report.by_code("donation.carry-not-donated")
    assert errs and report.exit_code() == 1
    assert _error_checkers(report) == {"donation"}
    # the donate=True twin of the same runner is clean
    good = run_checkers([_chunk_fixture_target(tiny_model_cfg, True)])
    assert not good.errors, good.render()
    assert not good.by_code("donation.carry-not-donated")


# --------------------------------------------------------------------- #
# Seeded violation 5: async double-buffer contracts (staleness-bound)
# --------------------------------------------------------------------- #
def test_seeded_unconditional_swap_trips_staleness_lint():
    """An async step whose pending→active swap is a per-step jnp.where
    (no lax.cond anywhere) must raise staleness.swap-not-gated — the
    block inversions would run every step with nothing to hide."""
    def ungated_swap_step(active, pending, count):
        do = (count % 10) == 0
        new_active = jnp.where(do, pending, active)        # not a cond!
        new_pending = jnp.linalg.inv(new_active + jnp.eye(64))
        return new_active, new_pending, count + 1

    target = trace.custom_target(
        "fixture/where-swap", ungated_swap_step,
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        meta={"staleness": 1, "n_buckets": 2, "factor_dims": {64}})
    report = run_checkers([target])
    errs = report.by_code("staleness.swap-not-gated")
    assert errs and report.exit_code() == 1
    assert _error_checkers(report) == {"staleness-bound"}


def test_seeded_ungated_factor_gather_trips_staleness_lint():
    """An async step that all-reduces the pending (256, 256) factor every
    step raises staleness.ungated-factor-bytes — and, honestly, the same
    payload also trips the comm-linearity factor lint; both fire."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def leaky_tick(pending):
        def inner(p):
            synced = jax.lax.psum(p, "d")                  # ungated O(d^2)
            return jax.lax.cond(True, lambda x: x,
                                lambda x: x, synced)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(pending)

    target = trace.custom_target(
        "fixture/pending-bank-psum", leaky_tick,
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        meta={"staleness": 1, "n_buckets": 1, "factor_dims": {256},
              "world": 8})
    report = run_checkers([target])
    assert report.by_code("staleness.ungated-factor-bytes")
    assert report.exit_code() == 1
    assert _error_checkers(report) == {"staleness-bound", "comm-linearity"}


def test_seeded_extra_step_bytes_trips_staleness_lint():
    """Differential check against an attached sync baseline: an async
    step that ships extra ungated (non-factor-shaped) bytes beyond the
    sync footprint + slack raises staleness.extra-step-bytes."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def chatty_tick(v):
        def inner(x):
            return jax.lax.psum(x, "d")   # 1 MB of new every-step traffic
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(v)

    target = trace.custom_target(
        "fixture/async-extra-bytes", chatty_tick,
        jax.ShapeDtypeStruct((262144,), jnp.float32),
        meta={"staleness": 1, "sync_ungated_bytes": 4096, "world": 8})
    report = run_checkers([target])
    errs = report.by_code("staleness.extra-step-bytes")
    assert errs and report.exit_code() == 1
    assert _error_checkers(report) == {"staleness-bound"}
    # a sync twin of the same program (staleness=0) is out of scope for
    # the checker: inactive means zero diagnostics, not a clean pass
    sync_target = trace.custom_target(
        "fixture/sync-twin", chatty_tick,
        jax.ShapeDtypeStruct((262144,), jnp.float32),
        meta={"staleness": 0, "sync_ungated_bytes": 4096, "world": 8})
    from repro.analysis.checkers import check_staleness_bound
    assert check_staleness_bound(sync_target) == []


# --------------------------------------------------------------------- #
# Seeded violation 6: health sentinel wire contract (health-gating)
# --------------------------------------------------------------------- #
def test_seeded_health_factor_broadcast_trips_health_lint():
    """A 'sentinel' that broadcasts a quarantine-reset (256, 256) bank on
    an every-step psum raises health.ungated-factor-bytes — resets must
    be local identity writes (the same payload also trips comm-linearity,
    like the staleness twin of this fixture; both fire)."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def leaky_reset(bank):
        def inner(b):
            return jax.lax.psum(b, "d")                    # ungated O(d^2)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(bank)

    target = trace.custom_target(
        "fixture/bank-reset-psum", leaky_reset,
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        meta={"health": True, "factor_dims": {256}, "world": 8})
    report = run_checkers([target])
    assert report.by_code("health.ungated-factor-bytes")
    assert report.exit_code() == 1
    assert _error_checkers(report) == {"health-gating", "comm-linearity"}


def test_seeded_health_extra_collective_trips_health_lint():
    """Differential check against an attached health-off baseline: a
    sentinel that adds an every-step agreement round (any new ungated
    collective) raises health.extra-step-collectives.  The payload here
    is 64 bytes — under the byte slack — so the count code fires alone,
    proving the two differential codes are independent."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def agreeing_step(flags):
        def inner(f):
            return jax.lax.psum(f, "d")    # cross-worker trip agreement
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(flags)

    target = trace.custom_target(
        "fixture/health-agreement-round", agreeing_step,
        jax.ShapeDtypeStruct((16,), jnp.float32),
        meta={"health": True, "plain_ungated_count": 0,
              "plain_ungated_bytes": 0, "n_dense_layers": 2, "world": 8})
    report = run_checkers([target])
    errs = report.by_code("health.extra-step-collectives")
    assert errs and report.exit_code() == 1
    assert not report.by_code("health.extra-step-bytes")
    assert _error_checkers(report) == {"health-gating"}

    # the health-off twin of the same program is out of the checker's
    # scope: inactive means zero diagnostics
    from repro.analysis.checkers import check_health_gating
    off_twin = trace.custom_target(
        "fixture/health-off-twin", agreeing_step,
        jax.ShapeDtypeStruct((16,), jnp.float32),
        meta={"health": False, "plain_ungated_count": 0, "world": 8})
    assert check_health_gating(off_twin) == []


# --------------------------------------------------------------------- #
# Clean passes over the real entry points
# --------------------------------------------------------------------- #
def test_lint_clean_on_bert_large_single_and_chunk():
    targets = [trace.single_target("bert_large"),
               trace.chunk_target("bert_large")]
    report = run_checkers(targets)
    assert report.exit_code() == 0, report.render()
    # non-vacuous: bert-large's 1024-wide buckets genuinely exceed the
    # fused-precondition VMEM budget and ride the two-matmul fallback
    assert report.by_code("pallas.fused-precond-fallback")
    assert not report.by_code("donation.carry-not-donated")
    assert not report.by_code("dtype.f64-promotion")


def test_lint_clean_on_bert_large_dist():
    target = trace.dist_target("bert_large", world=8)
    report = run_checkers([target])
    assert report.exit_code() == 0, report.render()

    # non-vacuity: the walker must actually see the dist step's structure
    res = jaxpr_walk.walk(target.jaxpr)
    ungated = [c for c in res.collectives if not c.gated]
    gated = [c for c in res.collectives if c.gated]
    assert ungated, "no per-step collectives found — walker is blind"
    assert gated, "no phase-gated collectives found (owner gathers)"
    stat_psums = [c for c in ungated if c.prim == "psum" and c.bf16_origin]
    assert stat_psums, "bf16-origin stat psums not detected"
    assert not res.f64_sites
    assert res.eps_guards
    assert all(g.dtype == "float32" for g in res.eps_guards)


def test_lint_clean_on_bert_large_async_dist():
    """The real async (staleness=1) dist step passes staleness-bound with
    the differential sync baseline attached — non-vacuously: the walker
    sees the per-bucket phase conds and a positive sync byte footprint,
    so a regression cannot slip through as an inactive checker."""
    import dataclasses
    cfg = MKORConfig(inv_freq=10)
    sync = trace.dist_target("bert_large", world=8, mkor_cfg=cfg)
    async_t = trace.dist_target(
        "bert_large", world=8,
        mkor_cfg=dataclasses.replace(cfg, staleness=1))
    trace.attach_sync_baseline(async_t, sync)
    report = run_checkers([async_t], names=["staleness-bound"])
    assert report.exit_code() == 0, report.render()
    # non-vacuity: the checker was genuinely active on this target
    assert async_t.meta["staleness"] == 1
    assert async_t.meta["sync_ungated_bytes"] > 0
    res = jaxpr_walk.walk(async_t.jaxpr)
    assert res.prim_counts.get("cond", 0) >= async_t.meta["n_buckets"] > 0
    assert any(not c.gated for c in res.collectives)


def test_lint_clean_on_bert_large_health_dist():
    """The real health-on dist step passes health-gating with the
    differential health-off baseline attached — non-vacuously: the
    checker is genuinely active (health=True in the traced config) and
    the baseline footprint is positive, so the zero-extra-wire claim of
    DESIGN.md §14 is actually being compared against something."""
    import dataclasses
    cfg = MKORConfig(inv_freq=10)
    plain = trace.dist_target("bert_large", world=8, mkor_cfg=cfg)
    health_t = trace.dist_target(
        "bert_large", world=8,
        mkor_cfg=dataclasses.replace(cfg, health=True))
    trace.attach_health_baseline(health_t, plain)
    report = run_checkers([health_t], names=["health-gating"])
    assert report.exit_code() == 0, report.render()
    # non-vacuity: the checker really ran with a real baseline
    assert health_t.meta["mkor_cfg"].health
    assert health_t.meta["plain_ungated_count"] > 0
    assert health_t.meta["plain_ungated_bytes"] > 0
    assert health_t.name.endswith("-health")
    res = jaxpr_walk.walk(health_t.jaxpr)
    assert any(not c.gated for c in res.collectives)


# --------------------------------------------------------------------- #
# Seeded violation 7: elastic failover wire contract (elastic-remap)
# --------------------------------------------------------------------- #
_ONE_DEAD = (True,) * 7 + (False,)


def test_seeded_remap_factor_broadcast_trips_elastic_lint():
    """A 'failover' that re-replicates the dead owner's (256, 256) bank
    slices on an every-step psum raises elastic.ungated-factor-bytes —
    the remap redistributes phase-gated work, it never ships banks per
    step (the payload also trips comm-linearity; both fire)."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def rebroadcast(bank):
        def inner(b):
            return jax.lax.psum(b, "d")                    # ungated O(d^2)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(bank)

    target = trace.custom_target(
        "fixture/remap-bank-psum", rebroadcast,
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        meta={"live": _ONE_DEAD, "factor_dims": {256}, "world": 8})
    report = run_checkers([target])
    assert report.by_code("elastic.ungated-factor-bytes")
    assert report.exit_code() == 1
    assert _error_checkers(report) == {"elastic-remap", "comm-linearity"}


def test_seeded_remap_extra_collective_trips_elastic_lint():
    """Differential check against the static-owner baseline: a remapped
    step that adds an every-step liveness-agreement round (any new
    ungated collective) raises elastic.extra-step-collectives; the
    64-byte payload stays under the byte slack, so the count code fires
    alone.  The fully-live twin of the same program is out of scope:
    zero diagnostics."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def liveness_round(flags):
        def inner(f):
            return jax.lax.psum(f, "d")    # cross-worker liveness vote
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(flags)

    args = (jax.ShapeDtypeStruct((16,), jnp.float32),)
    target = trace.custom_target(
        "fixture/remap-liveness-round", liveness_round, *args,
        meta={"live": _ONE_DEAD, "static_ungated_count": 0,
              "static_ungated_bytes": 0, "world": 8})
    report = run_checkers([target])
    assert report.by_code("elastic.extra-step-collectives")
    assert report.exit_code() == 1
    assert not report.by_code("elastic.extra-step-bytes")
    assert _error_checkers(report) == {"elastic-remap"}

    from repro.analysis.checkers import check_elastic_remap
    live_twin = trace.custom_target(
        "fixture/remap-all-live", liveness_round, *args,
        meta={"live": (True,) * 8})
    assert check_elastic_remap(live_twin) == []


def test_seeded_dequantized_wire_trips_quant_lint():
    """Under factor_quant='int8' a phase-gated gather that ships the
    DEQUANTIZED fp32 bank instead of the stored codes raises
    quant.wire-not-int8-origin — the wire must carry the int8 residency
    (DESIGN.md §16).  Gated so comm-linearity stays quiet: the quant
    checker owns this failure mode."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def leaky_gather(codes):
        def inner(q):
            bank = q.astype(jnp.float32) * 0.01        # dequantized...
            return jax.lax.cond(jnp.sum(bank) > 0,
                                lambda b: jax.lax.psum(b * 0.0, "d") + b,
                                lambda b: b, bank)     # ...on the wire
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(codes)

    target = trace.custom_target(
        "fixture/dequantized-owner-gather", leaky_gather,
        jax.ShapeDtypeStruct((256, 256), jnp.int8),
        meta={"factor_quant": "int8", "factor_dims": {256}, "world": 8})
    report = run_checkers([target])
    errs = report.by_code("quant.wire-not-int8-origin")
    assert errs and all(d.severity == Severity.ERROR for d in errs)
    assert report.exit_code() == 1
    assert _error_checkers(report) == {"quant-discipline"}


def test_seeded_bf16_accum_trips_quant_lint():
    """int8-origin codes widened to bf16 before the collective raise
    quant.accum-not-f32 — a bf16 accumulator silently rounds the codes
    of large banks; widening must go to fp32 (or stay int8)."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))

    def bf16_gather(codes):
        def inner(q):
            return jax.lax.cond(jnp.sum(q) > 0,
                                lambda c: jax.lax.psum(
                                    c.astype(jnp.bfloat16), "d"),
                                lambda c: c.astype(jnp.bfloat16), q)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(codes)

    target = trace.custom_target(
        "fixture/bf16-code-accum", bf16_gather,
        jax.ShapeDtypeStruct((256, 256), jnp.int8),
        meta={"factor_quant": "int8", "factor_dims": {256}, "world": 8})
    report = run_checkers([target])
    assert report.by_code("quant.accum-not-f32")
    assert report.exit_code() == 1
    assert _error_checkers(report) == {"quant-discipline"}

    # the compliant twin — raw int8 codes on the wire — is clean, and
    # the same program without the int8 config is out of scope entirely
    from repro.analysis.checkers import check_quant_discipline

    def int8_gather(codes):
        def inner(q):
            return jax.lax.cond(jnp.sum(q) > 0,
                                lambda c: jax.lax.psum(c, "d"),
                                lambda c: c, q)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=P(), out_specs=P())(codes)

    good = trace.custom_target(
        "fixture/int8-owner-gather", int8_gather,
        jax.ShapeDtypeStruct((256, 256), jnp.int8),
        meta={"factor_quant": "int8", "factor_dims": {256}, "world": 8})
    assert check_quant_discipline(good) == []
    off = trace.custom_target(
        "fixture/quant-off", bf16_gather,
        jax.ShapeDtypeStruct((256, 256), jnp.int8),
        meta={"factor_dims": {256}, "world": 8})
    assert check_quant_discipline(off) == []


def test_lint_clean_on_bert_large_int8_dist():
    """The real int8 dist step passes quant-discipline non-vacuously:
    the traced program really ships int8-origin factor payloads."""
    t = trace.dist_target(
        "bert_large", world=8,
        mkor_cfg=MKORConfig(inv_freq=10, factor_quant="int8"))
    report = run_checkers([t], names=["quant-discipline"])
    assert report.exit_code() == 0, report.render()
    res = jaxpr_walk.walk(t.jaxpr)
    factor_dims = set(t.meta.get("factor_dims", ()))
    wired = [c for c in res.collectives
             if any(len(s) >= 2 and s[-1] == s[-2] and s[-1] in factor_dims
                    for s in c.shapes)]
    assert wired and all(c.int8_origin for c in wired)


def test_lint_clean_on_bert_large_remap_dist():
    """The real elastic-remapped dist step (one worker dead, owners
    re-split over survivors) passes elastic-remap with the static-owner
    baseline attached — non-vacuously: the mask really has a dead worker
    and the baseline footprint is positive, so the zero-extra-traffic
    claim of DESIGN.md §15 is compared against something."""
    static_t = trace.dist_target("bert_large", world=8,
                                 mkor_cfg=MKORConfig(inv_freq=10))
    remap_t = trace.dist_target("bert_large", world=8, live=_ONE_DEAD,
                                mkor_cfg=MKORConfig(inv_freq=10))
    trace.attach_static_owner_baseline(remap_t, static_t)
    report = run_checkers([remap_t], names=["elastic-remap"])
    assert report.exit_code() == 0, report.render()
    assert remap_t.name.endswith("-remap")
    assert remap_t.meta["live"] == _ONE_DEAD
    assert remap_t.meta["static_ungated_count"] > 0
    assert remap_t.meta["static_ungated_bytes"] > 0
    res = jaxpr_walk.walk(remap_t.jaxpr)
    assert any(not c.gated for c in res.collectives)


def test_lint_checker_subset(tiny_model_cfg):
    # --checkers narrowing: only the requested checker runs
    target = _chunk_fixture_target(tiny_model_cfg, False)
    report = run_checkers([target], names=["pallas-kernels"])
    assert not report.diagnostics  # no manifest in meta -> nothing to say
    report = run_checkers([target], names=["donation"])
    assert report.by_code("donation.carry-not-donated")


# --------------------------------------------------------------------- #
# Kernel plan API + fallback counter (satellite a)
# --------------------------------------------------------------------- #
def test_kernel_plans_match_known_shapes():
    p = ops.fused_precond_plan(1024, 4096)
    assert not p.fits and p.falls_back            # bert-large MLP bucket
    assert p.sublane_aligned
    small = ops.fused_precond_plan(96, 48)
    assert small.fits
    smw = ops.fused_smw_plan(1024)
    assert smw.fits and not smw.falls_back
    blk = ops.fused_block_smw_plan(32000, 128)
    assert not blk.fits and not blk.falls_back and blk.rank == 128
    assert ops.fused_block_smw_plan(256, 12).rank == 16  # padded to 8s

    rank1 = ops.bucket_kernel_plans(1024, 1024)
    assert [q.kernel for q in rank1] == [
        "fused_smw", "fused_smw", "fused_precond"]
    rank8 = ops.bucket_kernel_plans(1024, 1024, rank=8)
    assert [q.kernel for q in rank8] == [
        "fused_block_smw", "fused_block_smw", "fused_precond"]


@pytest.mark.parametrize("grad_dtypes,passes", [({}, "float32"),
                                                 (None, "bfloat16")],
                         ids=["no_dtypes", "bf16_grads"])
def test_pallas_lint_reports_fallback_matmul_plans(grad_dtypes, passes):
    """Where the fused precondition does not fit, the lint reports the
    two matmul plans it falls back to, from ops.precondition_matmul_plans
    at the bucket's gradient dtype (fp32 when the target names none)."""
    params = {"layer": {
        "w": jax.ShapeDtypeStruct((2560, 8960), jnp.bfloat16),
        "probe": jax.ShapeDtypeStruct((8960,), jnp.float32)}}
    cfg = MKORConfig(exclude=())
    manifest = manifest_for(params, cfg)
    if grad_dtypes is None:
        grad_dtypes = {b.bucket_id: "bfloat16" for b in manifest}
    target = LintTarget(
        name="fixture/rwkv6-channel-mix", kind="custom",
        meta={"manifest": manifest, "mkor_cfg": cfg,
              "grad_dtypes": grad_dtypes})
    report = run_checkers([target], names=["pallas-kernels"])
    assert report.exit_code() == 0, report.render()
    assert report.by_code("pallas.fused-precond-fallback")
    infos = report.by_code("pallas.precond-matmul-plan")
    want = ops.precondition_matmul_plans(2560, 8960, grad_dtype=passes)
    assert [d.context["dims"] for d in infos] == [list(p.dims)
                                                  for p in want]
    assert [d.context["block"] for d in infos] == [list(p.block)
                                                   for p in want]
    assert [d.context["vmem_bytes"] for d in infos] == [p.vmem_bytes
                                                        for p in want]
    assert all(d.severity == Severity.INFO for d in infos)


def test_fused_precond_fallback_counter_vmem():
    ops.reset_fallback_counts()
    big = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)
    with pytest.warns(ops.PallasFallbackWarning, match="vmem_budget"):
        out = jax.eval_shape(ops.fused_precondition, big, big, big)
    assert out.shape == (4096, 4096)
    assert ops.fallback_counts() == {("fused_precond", "vmem_budget"): 1}
    ops.reset_fallback_counts()
    assert ops.fallback_counts() == {}


def test_fused_precond_fallback_counter_extra_dims():
    ops.reset_fallback_counts()
    l_inv = jax.ShapeDtypeStruct((48, 48), jnp.float32)
    r_inv = jax.ShapeDtypeStruct((96, 96), jnp.float32)
    g_w = jax.ShapeDtypeStruct((2, 96, 48), jnp.float32)  # expert lead dim
    with pytest.warns(ops.PallasFallbackWarning, match="extra_dims"):
        out = jax.eval_shape(ops.fused_precondition, l_inv, r_inv, g_w)
    assert out.shape == (2, 96, 48)
    assert ops.fallback_counts() == {("fused_precond", "extra_dims"): 1}
    ops.reset_fallback_counts()


# --------------------------------------------------------------------- #
# chunk_schedule retrace bound (satellite: launch/train.py loop)
# --------------------------------------------------------------------- #
def test_chunk_schedule():
    assert train_lib.chunk_schedule(100, 8) == [8] * 12 + [4]
    assert train_lib.chunk_schedule(7, 10) == [7]
    assert train_lib.chunk_schedule(0, 4) == []
    assert train_lib.chunk_schedule(5, 0) == [1] * 5  # chunk clamped to 1
    for steps in (1, 2, 7, 50, 99, 100, 1000):
        for chunk in (1, 2, 3, 8, 64):
            sched = train_lib.chunk_schedule(steps, chunk)
            assert sum(sched) == steps
            assert len(set(sched)) <= 2, (steps, chunk, sched)
