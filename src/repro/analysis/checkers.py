"""The mkor-lint contract checkers (DESIGN.md §12).

Each checker is a pure function ``(target) -> [Diagnostic]`` registered
in :data:`CHECKERS`; :func:`run_checkers` applies every applicable
checker to every target and aggregates a :class:`Report`.  Severity
contract: an ERROR means the traced program violates a structural claim
of the paper/design (the CI gate fails); a WARNING flags a degraded but
handled condition (e.g. the fused-precondition VMEM fallback — real on
bert-large's 1024x4096 MLP bucket — or a missing ε-guard).

To add a checker: write ``check_<name>(target)`` returning diagnostics,
declare which target kinds it applies to in ``_APPLIES``, and register
it in ``CHECKERS``.  Keep codes stable — tests and CI key on them.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Optional

from repro.analysis import hlo as hlo_lib
from repro.analysis import jaxpr_walk
from repro.analysis.diagnostics import Diagnostic, Report, Severity
from repro.kernels import ops as kernel_ops
from repro.training.loop import chunk_schedule

# collectives that every dist step legitimately runs outside any phase
# gate: the flat-gradient reduce-scatter + all-gather pair, the loss
# pmean, and the extra-metric pmeans (loss_lm, moe_aux)
_FIXED_UNGATED_COLLECTIVES = 8
# ungated wire bytes may exceed the analytic budget by this factor before
# the comm lint errors (covers padding, fp32-vs-bf16 CPU lowering slack)
_BYTES_SLACK = 1.5
# ignore square payloads below this dim (tiny head matrices, metrics)
_MIN_FACTOR_DIM = 8


def _d(checker: str, code: str, severity: str, message: str, target,
       **context) -> Diagnostic:
    return Diagnostic(checker=checker, code=code, severity=severity,
                      message=message, target=target.name, context=context)


# --------------------------------------------------------------------- #
# 1. comm-linearity: no per-step O(d^2) payloads, bounded count/bytes
# --------------------------------------------------------------------- #
def _is_factor_square(shape, factor_dims) -> bool:
    if len(shape) < 2:
        return False
    a, b = shape[-2], shape[-1]
    return (a == b and a >= _MIN_FACTOR_DIM
            and (not factor_dims or a in factor_dims))


def check_comm_linearity(target) -> List[Diagnostic]:
    """MKOR's linear-communication claim, statically: every collective
    that runs on EVERY step (i.e. outside a ``lax.cond`` phase gate) must
    carry an O(d) payload — stat vectors, the flat gradient buffer,
    scalars — never an O(d^2) factor-shaped matrix; and the per-step
    collective count/bytes must match the explicit-collective design
    (stats.bucket_comm_cost), not drift back toward a per-leaf or
    KFAC-style schedule."""
    out: List[Diagnostic] = []
    if target.jaxpr is None:
        return out
    res = jaxpr_walk.walk(target.jaxpr)
    factor_dims = set(target.meta.get("factor_dims", ()))
    ungated = [c for c in res.collectives if not c.gated]

    for c in ungated:
        for shape in c.shapes:
            if _is_factor_square(shape, factor_dims):
                out.append(_d(
                    "comm-linearity", "comm.factor-payload-per-step",
                    Severity.ERROR,
                    f"per-step (ungated) {c.prim} at {c.path} carries a "
                    f"factor-shaped payload {shape} — O(d^2) on the wire "
                    f"every step; factor traffic must ride the phase-"
                    f"gated owner-gather schedule", target,
                    prim=c.prim, shape=list(shape), path=c.path))

    n_stat = target.meta.get("n_dense_layers")
    if n_stat is not None:
        bound = n_stat + _FIXED_UNGATED_COLLECTIVES
        if len(ungated) > bound:
            out.append(_d(
                "comm-linearity", "comm.collective-count-drift",
                Severity.ERROR,
                f"{len(ungated)} per-step collectives, expected at most "
                f"{bound} ({n_stat} stat psums + "
                f"{_FIXED_UNGATED_COLLECTIVES} fixed grad/metric "
                f"collectives) — the explicit-collective design has "
                f"drifted", target,
                n_ungated=len(ungated), bound=bound))

    grad_bytes = target.meta.get("grad_f32_bytes")
    stats_bytes = target.meta.get("stats_f32_bytes", 0)
    world = max(target.meta.get("world", 1), 1)
    if grad_bytes is not None:
        # flat-grad RS (full buffer) + AG (1/world shard) + stat psums
        budget = grad_bytes * (1 + 1 / world) + stats_bytes + 2 ** 20
        total = sum(c.payload_bytes for c in ungated)
        if total > _BYTES_SLACK * budget:
            out.append(_d(
                "comm-linearity", "comm.bytes-over-budget",
                Severity.ERROR,
                f"per-step collective payload {total / 2**20:.1f}MB "
                f"exceeds {_BYTES_SLACK}x the analytic O(d) budget "
                f"{budget / 2**20:.1f}MB", target,
                payload_bytes=total, budget_bytes=int(budget)))

    # gated factor traffic is allowed but must stay within the
    # owner-sharded schedule's per-phase-step budget
    comm = target.meta.get("bucket_comm", {})
    if comm:
        gated_budget = sum(
            c["kfac_factor_bytes_per_inv"] for c in comm.values())
        gated_sq = [c for c in res.collectives if c.gated
                    and any(_is_factor_square(s, factor_dims)
                            for s in c.shapes)]
        gated_bytes = sum(c.payload_bytes for c in gated_sq)
        # jaxpr payloads are fp32/padded where the analytic budget counts
        # the factor dtype; 2x covers the width difference, 2x the
        # pad/world slack
        if gated_bytes > 4 * max(gated_budget, 1):
            out.append(_d(
                "comm-linearity", "comm.gated-factor-bytes",
                Severity.WARNING,
                f"phase-gated factor collectives carry "
                f"{gated_bytes / 2**20:.1f}MB vs the owner-sharded "
                f"budget {gated_budget / 2**20:.1f}MB", target,
                gated_bytes=gated_bytes, budget=gated_budget))

    # secondary recount over the compiled HLO, when available: the
    # partitioner must not have re-introduced per-step factor traffic
    if target.compiled_text:
        hc = hlo_lib.HloCost(target.compiled_text)
        for site in hc.collective_sites():
            if site.gated:
                continue
            if _is_factor_square(tuple(site.operand_dims), factor_dims):
                out.append(_d(
                    "comm-linearity", "comm.factor-payload-per-step",
                    Severity.ERROR,
                    f"compiled HLO: ungated {site.kind} "
                    f"({site.name} in {site.comp}) moves factor-shaped "
                    f"{list(site.operand_dims)}", target,
                    kind=site.kind, dims=list(site.operand_dims)))
    return out


# --------------------------------------------------------------------- #
# 2. dtype-discipline: no f64 leaks, fp32 accum, bf16 payloads, ε dtypes
# --------------------------------------------------------------------- #
def check_dtype_discipline(target) -> List[Diagnostic]:
    """No silent float64/weak-type promotions anywhere in the step; the
    dist stat reductions follow sharding/collectives' contract (bf16
    payload, fp32 accumulation); SMW/rescale ε-guards compute in fp32
    (a bf16 ε under ~1e-38 flushes to 0 and the guard is a no-op)."""
    out: List[Diagnostic] = []
    if target.jaxpr is None:
        return out
    res = jaxpr_walk.walk(target.jaxpr)

    for path in sorted(set(res.f64_sites)):
        out.append(_d(
            "dtype-discipline", "dtype.f64-promotion", Severity.ERROR,
            f"float64 value at {path} — a silent weak-type/x64 promotion "
            f"(doubles every byte it touches and falls off the TPU fast "
            f"path)", target, path=path))

    if res.eps_guards:
        for g in res.eps_guards:
            if g.dtype in ("float16", "bfloat16"):
                out.append(_d(
                    "dtype-discipline", "dtype.eps-guard-half",
                    Severity.ERROR,
                    f"ε-guard max(x, {g.eps:g}) at {g.path} computes in "
                    f"{g.dtype}; {g.eps:g} underflows to 0 in half "
                    f"precision, so the guard cannot prevent a divide-"
                    f"by-zero", target, eps=g.eps, dtype=g.dtype,
                    path=g.path))
    elif target.kind in ("single", "dist"):
        out.append(_d(
            "dtype-discipline", "dtype.eps-guard-missing",
            Severity.WARNING,
            "no ε-guard (max against a tiny literal) found in the traced "
            "step — the SMW rescale/stabilize denominators may be "
            "unguarded", target))

    if target.kind == "dist":
        factor_dims = set(target.meta.get("factor_dims", ()))
        for c in res.collectives:
            if c.gated or c.prim != "psum" or not c.shapes:
                continue
            shape = c.shapes[0]
            # stat-vector psums: trailing dim is a factor dim; the flat
            # gradient buffer is 1-D and huge, scalars are 0-D
            if not shape or shape[-1] not in factor_dims \
                    or _is_factor_square(shape, factor_dims):
                continue
            if c.dtypes[0] != "float32":
                out.append(_d(
                    "dtype-discipline", "dtype.stats-accum-not-f32",
                    Severity.ERROR,
                    f"stat psum at {c.path} accumulates in {c.dtypes[0]} "
                    f"— the reduction must run in fp32 "
                    f"(sharding/collectives.ACCUM_DTYPE)", target,
                    dtype=c.dtypes[0], shape=list(shape), path=c.path))
            elif not c.bf16_origin:
                out.append(_d(
                    "dtype-discipline", "dtype.stats-payload-not-bf16",
                    Severity.WARNING,
                    f"stat psum at {c.path} (shape {list(shape)}) has no "
                    f"bf16 quantization upstream — the wire payload is "
                    f"full fp32 instead of RANK1_PAYLOAD_DTYPE", target,
                    shape=list(shape), path=c.path))
    return out


# --------------------------------------------------------------------- #
# 3. pallas-kernels: static pre-dispatch VMEM / alignment / rank checks
# --------------------------------------------------------------------- #
def check_pallas_kernels(target) -> List[Diagnostic]:
    """The runtime VMEM-budget fallback in kernels/ops.py, promoted to a
    static pre-dispatch check: for every bucket the manifest implies,
    plan the exact kernel dispatches (ops.bucket_kernel_plans — the same
    plans the runtime consumes) and diagnose over-budget dispatches,
    tile misalignment, and Gauss-Jordan rank bounds per bucket.  Where
    the fused precondition falls back, the two matmul plans it falls back
    to (ops.precondition_matmul_plans, at the bucket's gradient dtype,
    ``meta["grad_dtypes"]``, else fp32) are reported and checked too."""
    out: List[Diagnostic] = []
    manifest = target.meta.get("manifest")
    cfg = target.meta.get("mkor_cfg")
    if manifest is None or cfg is None:
        return out
    grad_dtypes = target.meta.get("grad_dtypes", {})
    quant = getattr(cfg, "factor_quant", "none")
    for b in manifest:
        plans = kernel_ops.bucket_kernel_plans(
            b.d_in, b.d_out, rank=cfg.rank, factor_dtype=cfg.factor_dtype,
            factor_quant=quant)
        if not plans[-1].fits:
            plans += kernel_ops.precondition_matmul_plans(
                b.d_in, b.d_out, factor_dtype=cfg.factor_dtype,
                factor_quant=quant,
                grad_dtype=grad_dtypes.get(b.bucket_id, "float32"))
        for p in plans:
            ctx = dict(bucket=b.bucket_id, kernel=p.kernel,
                       dims=list(p.dims), block=list(p.block),
                       vmem_bytes=p.vmem_bytes, rank=p.rank)
            if not p.fits:
                if p.falls_back:
                    out.append(_d(
                        "pallas-kernels", "pallas.fused-precond-fallback",
                        Severity.WARNING,
                        f"bucket {b.bucket_id}: {p.kernel} plan needs "
                        f"{p.vmem_bytes / 2**20:.1f}MB VMEM (budget "
                        f"{p.vmem_budget / 2**20:.0f}MB) — runtime falls "
                        f"back to the two-matmul path", target, **ctx))
                else:
                    out.append(_d(
                        "pallas-kernels", "pallas.vmem-over-budget",
                        Severity.ERROR,
                        f"bucket {b.bucket_id}: {p.kernel} plan needs "
                        f"{p.vmem_bytes / 2**20:.1f}MB VMEM (budget "
                        f"{p.vmem_budget / 2**20:.0f}MB) and has NO "
                        f"fallback — the dispatch would exceed VMEM",
                        target, **ctx))
            if not p.sublane_aligned:
                out.append(_d(
                    "pallas-kernels", "pallas.block-misaligned",
                    Severity.ERROR,
                    f"bucket {b.bucket_id}: {p.kernel} block {p.block} "
                    f"is not a multiple of the (8, 128) sublane tile",
                    target, **ctx))
            elif not p.lane_aligned and max(p.padded) > 128:
                out.append(_d(
                    "pallas-kernels", "pallas.lane-tile", Severity.WARNING,
                    f"bucket {b.bucket_id}: {p.kernel} block {p.block} "
                    f"below the 128 lane width on a >128 dim — wasted "
                    f"MXU lanes", target, **ctx))
            if p.kernel == "matmul":
                out.append(_d(
                    "pallas-kernels", "pallas.precond-matmul-plan",
                    Severity.INFO,
                    f"bucket {b.bucket_id}: fallback matmul "
                    f"{'x'.join(map(str, p.dims))} on blocks "
                    f"{'x'.join(map(str, p.block))}, grid "
                    f"{'x'.join(map(str, p.grid))}, "
                    f"{p.vmem_bytes / 2**20:.1f}MB VMEM (budget "
                    f"{p.vmem_budget / 2**20:.0f}MB)", target, **ctx))
            if p.kernel == "fused_block_smw":
                if p.rank > 128:
                    out.append(_d(
                        "pallas-kernels", "pallas.gj-rank-unsupported",
                        Severity.ERROR,
                        f"bucket {b.bucket_id}: padded window rank "
                        f"{p.rank} > 128 — the in-register r x r "
                        f"Gauss-Jordan no longer fits a single tile",
                        target, **ctx))
                elif p.rank > 32:
                    out.append(_d(
                        "pallas-kernels", "pallas.gj-rank-large",
                        Severity.WARNING,
                        f"bucket {b.bucket_id}: padded window rank "
                        f"{p.rank} unrolls {p.rank} Gauss-Jordan "
                        f"iterations in-kernel — compile time and "
                        f"register pressure grow linearly", target,
                        **ctx))
    return out


# --------------------------------------------------------------------- #
# 4. donation/retrace: carries donated in lowered HLO, bounded traces
# --------------------------------------------------------------------- #
def check_donation(target) -> List[Diagnostic]:
    """The chunk runner's (params, opt_state) donation (DESIGN.md §9)
    verified in the LOWERED module (``tf.aliasing_output`` marks), plus
    the retrace bound: a run schedules at most two distinct chunk
    lengths, so at most two traces of the scanned step exist."""
    out: List[Diagnostic] = []
    expected = target.meta.get("n_carry_leaves")
    if target.lowered_text and expected:
        donated = hlo_lib.count_donated_params(target.lowered_text)
        if donated == 0:
            out.append(_d(
                "donation", "donation.carry-not-donated", Severity.ERROR,
                f"no donated parameters in the lowered chunk runner "
                f"(expected {expected} params/opt-state leaves) — peak "
                f"memory doubles: every scan chunk holds two full copies "
                f"of the factor banks", target, expected=expected))
        elif donated < expected:
            out.append(_d(
                "donation", "donation.partial-donation", Severity.WARNING,
                f"only {donated}/{expected} carry leaves donated in the "
                f"lowered chunk runner", target, donated=donated,
                expected=expected))
    if target.compiled_text:
        aliases = hlo_lib.input_output_aliases(target.compiled_text)
        if expected and not aliases:
            out.append(_d(
                "donation", "donation.no-compiled-alias", Severity.WARNING,
                "compiled module has an empty input_output_alias set — "
                "the backend dropped the donation (expected on CPU, a "
                "real loss on TPU)", target))
    chunk = target.meta.get("chunk")
    if chunk and target.jaxpr is not None:
        res = jaxpr_walk.walk(target.jaxpr)
        lengths = [s.length for s in res.scans if s.length is not None]
        if chunk not in lengths:
            out.append(_d(
                "donation", "donation.no-chunk-scan", Severity.WARNING,
                f"no lax.scan of length {chunk} in the chunk runner "
                f"jaxpr (scan lengths: {sorted(set(lengths))}) — the "
                f"chunked step is not actually scan-driven", target,
                lengths=sorted(set(lengths))))
    steps = target.meta.get("steps")
    if steps and chunk:
        distinct = sorted(set(chunk_schedule(steps, chunk)))
        if len(distinct) > 2:
            out.append(_d(
                "donation", "donation.retrace-unbounded", Severity.ERROR,
                f"chunk schedule for {steps} steps at chunk {chunk} has "
                f"{len(distinct)} distinct lengths {distinct} — each one "
                f"is a fresh trace/compile of the scanned step", target,
                lengths=distinct))
    return out


# --------------------------------------------------------------------- #
# 5. staleness-bound: async double-buffer contracts (DESIGN.md §13)
# --------------------------------------------------------------------- #
# extra ungated bytes the async step may add over the sync baseline
# before the differential check errors (covers trivial bookkeeping
# scalars; factor banks are megabytes, so this cannot mask a real leak)
_ASYNC_EXTRA_BYTES_SLACK = 1024


def check_staleness_bound(target) -> List[Diagnostic]:
    """The overlap-hidden inversion contracts (DESIGN.md §13), statically:

    1. the pending→active swap (and the chained next-pending launch) is
       ``lax.cond``-gated per bucket — an unconditional swap would run the
       block inversions every step and the stagger/overlap schedule has
       nothing to hide;
    2. the async step moves zero extra per-step (ungated) collective
       bytes vs the synchronous step it replaces — differentially against
       ``meta["sync_ungated_bytes"]`` (trace.attach_sync_baseline) when a
       sync twin was traced, else against the analytic
       ``stats.bucket_comm_cost``-style O(d) budget;
    3. no ungated collective ships a factor-shaped payload (the pending
       bank must ride the SAME phase-gated owner-gather as the sync
       schedule, just one window early).

    Inactive (no diagnostics) on synchronous targets (staleness == 0)."""
    out: List[Diagnostic] = []
    staleness = target.meta.get("staleness")
    if staleness is None:
        cfg = target.meta.get("mkor_cfg")
        staleness = getattr(cfg, "staleness", 0) if cfg is not None else 0
    if not staleness or target.jaxpr is None:
        return out
    res = jaxpr_walk.walk(target.jaxpr)
    factor_dims = set(target.meta.get("factor_dims", ()))

    # 1. swap gating: at least one cond per bucket (each bucket's phase
    # tick is its own lax.cond; sub-conds inside count extra, never fewer)
    n_buckets = target.meta.get("n_buckets")
    if n_buckets is None:
        manifest = target.meta.get("manifest")
        n_buckets = len(manifest) if manifest is not None else None
    n_cond = res.prim_counts.get("cond", 0)
    if n_buckets and n_cond < n_buckets:
        out.append(_d(
            "staleness-bound", "staleness.swap-not-gated", Severity.ERROR,
            f"async step has {n_cond} lax.cond(s) for {n_buckets} "
            f"bucket(s) — the pending→active swap/launch is not phase-"
            f"gated, so the block inversions run (and their collectives "
            f"fire) on every step instead of once per inv_freq window",
            target, n_cond=n_cond, n_buckets=n_buckets))

    # 3. (cheap, do before 2) no ungated factor-shaped payloads
    ungated = [c for c in res.collectives if not c.gated]
    for c in ungated:
        for shape in c.shapes:
            if _is_factor_square(shape, factor_dims):
                out.append(_d(
                    "staleness-bound", "staleness.ungated-factor-bytes",
                    Severity.ERROR,
                    f"async step: ungated {c.prim} at {c.path} moves a "
                    f"factor-shaped payload {list(shape)} every step — "
                    f"the pending bank must ride the phase-gated owner-"
                    f"gather, not per-step collectives", target,
                    prim=c.prim, shape=list(shape), path=c.path))

    # 2. zero extra per-step bytes vs sync
    total = sum(c.payload_bytes for c in ungated)
    sync_bytes = target.meta.get("sync_ungated_bytes")
    if sync_bytes is not None:
        if total > sync_bytes + _ASYNC_EXTRA_BYTES_SLACK:
            out.append(_d(
                "staleness-bound", "staleness.extra-step-bytes",
                Severity.ERROR,
                f"async step moves {total} ungated collective bytes vs "
                f"{sync_bytes} in the synchronous step it replaces "
                f"(+{total - sync_bytes}) — overlap must reorder work, "
                f"not add per-step wire traffic", target,
                async_bytes=total, sync_bytes=sync_bytes))
    else:
        grad_bytes = target.meta.get("grad_f32_bytes")
        stats_bytes = target.meta.get("stats_f32_bytes", 0)
        world = max(target.meta.get("world", 1), 1)
        if grad_bytes is not None and world > 1:
            budget = grad_bytes * (1 + 1 / world) + stats_bytes + 2 ** 20
            if total > _BYTES_SLACK * budget:
                out.append(_d(
                    "staleness-bound", "staleness.extra-step-bytes",
                    Severity.ERROR,
                    f"async step moves {total / 2**20:.1f}MB ungated "
                    f"collective bytes, over {_BYTES_SLACK}x the analytic "
                    f"O(d) per-step budget {budget / 2**20:.1f}MB (no "
                    f"sync baseline attached)", target,
                    async_bytes=total, budget_bytes=int(budget)))
    return out


# --------------------------------------------------------------------- #
# 6. health-gating: the sentinel adds zero ungated wire traffic
# --------------------------------------------------------------------- #
# extra ungated bytes the health-on step may add over its health-off twin
# (trivial bookkeeping scalars only; any real signal collective is KB+)
_HEALTH_EXTRA_BYTES_SLACK = 1024


def check_health_gating(target) -> List[Diagnostic]:
    """The numerical-health sentinel's wire contract (DESIGN.md §14),
    statically:

    1. the sentinel adds NO ungated (per-step) collectives over the
       health-off twin — every signal is derived from already-replicated
       post-collective data, so detection needs no cross-worker agreement
       round (differentially against ``meta["plain_ungated_count"]`` /
       ``plain_ungated_bytes``, trace.attach_health_baseline);
    2. no ungated collective ships a factor-shaped payload — quarantine
       resets are local identity writes, never bank broadcasts.

    Inactive (no diagnostics) unless the target's MKOR config has
    ``health=True`` (or ``meta["health"]`` on custom fixtures)."""
    out: List[Diagnostic] = []
    cfg = target.meta.get("mkor_cfg")
    health = target.meta.get("health")
    if health is None:
        health = bool(getattr(cfg, "health", False))
    if not health or target.jaxpr is None:
        return out
    res = jaxpr_walk.walk(target.jaxpr)
    factor_dims = set(target.meta.get("factor_dims", ()))
    ungated = [c for c in res.collectives if not c.gated]

    # 2. no ungated factor-shaped payloads
    for c in ungated:
        for shape in c.shapes:
            if _is_factor_square(shape, factor_dims):
                out.append(_d(
                    "health-gating", "health.ungated-factor-bytes",
                    Severity.ERROR,
                    f"health step: ungated {c.prim} at {c.path} moves a "
                    f"factor-shaped payload {list(shape)} every step — "
                    f"sentinel signals must be derived from replicated "
                    f"data, and quarantine resets are local identity "
                    f"writes, not bank collectives", target,
                    prim=c.prim, shape=list(shape), path=c.path))

    # 1. differential: zero extra ungated collectives / bytes vs the
    # health-off twin
    plain_count = target.meta.get("plain_ungated_count")
    if plain_count is not None and len(ungated) > plain_count:
        out.append(_d(
            "health-gating", "health.extra-step-collectives",
            Severity.ERROR,
            f"health step runs {len(ungated)} ungated collectives vs "
            f"{plain_count} with the sentinel off "
            f"(+{len(ungated) - plain_count}) — the sentinel must not "
            f"add cross-worker agreement rounds", target,
            health_count=len(ungated), plain_count=plain_count))
    plain_bytes = target.meta.get("plain_ungated_bytes")
    if plain_bytes is not None:
        total = sum(c.payload_bytes for c in ungated)
        if total > plain_bytes + _HEALTH_EXTRA_BYTES_SLACK:
            out.append(_d(
                "health-gating", "health.extra-step-bytes",
                Severity.ERROR,
                f"health step moves {total} ungated collective bytes vs "
                f"{plain_bytes} with the sentinel off "
                f"(+{total - plain_bytes}) — detection is supposed to be "
                f"wire-free", target,
                health_bytes=total, plain_bytes=plain_bytes))
    return out


# --------------------------------------------------------------------- #
# 7. elastic-remap: failover remap adds zero ungated factor traffic
# --------------------------------------------------------------------- #
# extra ungated bytes the remapped step may add over the static-owner
# twin (trivial bookkeeping scalars only; a leaked bank payload is KB+)
_ELASTIC_EXTRA_BYTES_SLACK = 1024


def check_elastic_remap(target) -> List[Diagnostic]:
    """The elastic-failover wire contract (DESIGN.md §15), statically:

    1. no ungated collective ships a factor-shaped payload — the remap
       redistributes ownership of the phase-gated inversion work; it must
       never turn into a per-step bank broadcast (e.g. re-replicating the
       dead owner's slices every step);
    2. the remapped step adds ZERO ungated collectives and zero ungated
       wire bytes over the static (fully-live) owner map — differentially
       against ``meta["static_ungated_count"]`` /
       ``static_ungated_bytes`` (trace.attach_static_owner_baseline).
       Failover changes WHO inverts a slice, not what crosses the wire
       per step.

    Inactive (no diagnostics) unless the target carries a liveness mask
    with at least one dead worker (``meta["live"]`` on custom fixtures,
    else ``mkor_cfg.live``)."""
    out: List[Diagnostic] = []
    cfg = target.meta.get("mkor_cfg")
    live = target.meta.get("live")
    if live is None:
        live = getattr(cfg, "live", None)
    if live is None or all(live) or target.jaxpr is None:
        return out
    res = jaxpr_walk.walk(target.jaxpr)
    factor_dims = set(target.meta.get("factor_dims", ()))
    ungated = [c for c in res.collectives if not c.gated]

    # 1. no ungated factor-shaped payloads
    for c in ungated:
        for shape in c.shapes:
            if _is_factor_square(shape, factor_dims):
                out.append(_d(
                    "elastic-remap", "elastic.ungated-factor-bytes",
                    Severity.ERROR,
                    f"remapped step: ungated {c.prim} at {c.path} moves a "
                    f"factor-shaped payload {list(shape)} every step — "
                    f"failover redistributes the phase-gated inversion "
                    f"work; it must not re-broadcast bank slices per "
                    f"step", target,
                    prim=c.prim, shape=list(shape), path=c.path))

    # 2. differential: zero extra ungated collectives / bytes vs the
    # static owner map
    static_count = target.meta.get("static_ungated_count")
    if static_count is not None and len(ungated) > static_count:
        out.append(_d(
            "elastic-remap", "elastic.extra-step-collectives",
            Severity.ERROR,
            f"remapped step runs {len(ungated)} ungated collectives vs "
            f"{static_count} under the static owner map "
            f"(+{len(ungated) - static_count}) — the liveness remap must "
            f"not add per-step agreement rounds", target,
            remap_count=len(ungated), static_count=static_count))
    static_bytes = target.meta.get("static_ungated_bytes")
    if static_bytes is not None:
        total = sum(c.payload_bytes for c in ungated)
        if total > static_bytes + _ELASTIC_EXTRA_BYTES_SLACK:
            out.append(_d(
                "elastic-remap", "elastic.extra-step-bytes",
                Severity.ERROR,
                f"remapped step moves {total} ungated collective bytes "
                f"vs {static_bytes} under the static owner map "
                f"(+{total - static_bytes}) — failover changes slice "
                f"ownership, not per-step wire traffic", target,
                remap_bytes=total, static_bytes=static_bytes))
    return out


# --------------------------------------------------------------------- #
# 8. quant-discipline: int8 codes on the wire, fp32 (or exact-int8)
#    accumulation (DESIGN.md §16)
# --------------------------------------------------------------------- #
def check_quant_discipline(target) -> List[Diagnostic]:
    """The quantized factor-residency wire contract (DESIGN.md §16),
    statically:

    1. EVERY factor-shaped collective payload (the phase-gated owner-
       gathers of the inverse banks — ungated ones are already errors
       elsewhere) must be int8-origin: raw int8 codes, or a value that
       traces back through transparent ops to an int8 source.  A
       dequantized fp32/bf16 bank on the wire forfeits the ~2x (vs bf16)
       payload reduction the int8 residency exists for;
    2. a widened int8-origin payload must accumulate in float32 — the
       masked-psum of disjoint chunks is exact in int8 or fp32, but a
       bf16/fp16 accumulator silently rounds the codes of large banks.

    Inactive (no diagnostics) unless the target's MKOR config has
    ``factor_quant="int8"`` (or ``meta["factor_quant"]`` on custom
    fixtures)."""
    out: List[Diagnostic] = []
    cfg = target.meta.get("mkor_cfg")
    fq = target.meta.get("factor_quant")
    if fq is None:
        fq = getattr(cfg, "factor_quant", "none") if cfg is not None \
            else "none"
    if fq != "int8" or target.jaxpr is None:
        return out
    res = jaxpr_walk.walk(target.jaxpr)
    factor_dims = set(target.meta.get("factor_dims", ()))
    for c in res.collectives:
        if not any(_is_factor_square(s, factor_dims) for s in c.shapes):
            continue
        if not c.int8_origin:
            out.append(_d(
                "quant-discipline", "quant.wire-not-int8-origin",
                Severity.ERROR,
                f"{c.prim} at {c.path} moves a factor-shaped payload "
                f"({[list(s) for s in c.shapes]}, {list(c.dtypes)}) with "
                f"no int8 source upstream — under factor_quant='int8' "
                f"the owner-gather must ship the stored codes, not a "
                f"dequantized bank", target,
                prim=c.prim, dtypes=list(c.dtypes), path=c.path))
        elif any(d in ("bfloat16", "float16") for d in c.dtypes):
            out.append(_d(
                "quant-discipline", "quant.accum-not-f32",
                Severity.ERROR,
                f"{c.prim} at {c.path} accumulates int8-origin factor "
                f"codes in {[d for d in c.dtypes if d != 'int8']} — "
                f"widened code payloads must accumulate in float32 "
                f"(sharding/collectives.ACCUM_DTYPE); half precision "
                f"rounds codes of banks wider than the 8-bit mantissa",
                target, prim=c.prim, dtypes=list(c.dtypes), path=c.path))
    return out


# --------------------------------------------------------------------- #
# Registry
# --------------------------------------------------------------------- #
CHECKERS: Dict[str, Callable] = {
    "comm-linearity": check_comm_linearity,
    "dtype-discipline": check_dtype_discipline,
    "pallas-kernels": check_pallas_kernels,
    "donation": check_donation,
    "staleness-bound": check_staleness_bound,
    "health-gating": check_health_gating,
    "elastic-remap": check_elastic_remap,
    "quant-discipline": check_quant_discipline,
}

# which target kinds each checker runs on ("custom" targets opt in to
# everything — the seeded-violation fixtures rely on it)
_APPLIES: Dict[str, tuple] = {
    "comm-linearity": ("dist", "custom"),
    "dtype-discipline": ("single", "dist", "custom"),
    "pallas-kernels": ("single", "dist", "custom"),
    "donation": ("chunk", "custom"),
    "staleness-bound": ("single", "dist", "custom"),
    "health-gating": ("single", "dist", "custom"),
    "elastic-remap": ("dist", "custom"),
    "quant-discipline": ("single", "dist", "custom"),
}


def run_checkers(targets: Iterable, *,
                 names: Optional[Iterable[str]] = None) -> Report:
    report = Report()
    selected = list(names) if names else list(CHECKERS)
    unknown = [n for n in selected if n not in CHECKERS]
    if unknown:
        raise KeyError(f"unknown checker(s) {unknown}; "
                       f"available: {sorted(CHECKERS)}")
    for target in targets:
        for name in selected:
            if target.kind not in _APPLIES[name]:
                continue
            report.extend(CHECKERS[name](target))
    return report
