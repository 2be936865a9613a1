"""Build lint targets from the real train-step entry points.

A :class:`LintTarget` bundles everything the checkers consume for one
traced program: the jaxpr (cheap — ``jax.make_jaxpr`` over
ShapeDtypeStructs, no compile), optionally the lowered StableHLO text
(still no XLA compile; carries the ``tf.aliasing_output`` donation
marks), optionally the compiled HLO text, plus static metadata (bucket
manifest, MKOR config, world size, analytic byte budgets).

Everything is abstract: params/opt state come from ``jax.eval_shape``,
batches from ``training.loop.train_batch_shapes`` — lint never allocates
a model or runs a step.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.configs import registry
from repro.core import firstorder
from repro.core import stats as statlib
from repro.core.mkor import MKORConfig, manifest_for, mkor
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib
from repro.sharding import collectives
from repro.training import loop as train_lib


def normalize_arch(name: str) -> str:
    """Registry arch ids use dashes; accept underscores on the CLI
    (``bert_large`` -> ``bert-large``)."""
    return name.replace("_", "-")


@dataclass
class LintTarget:
    name: str                    # e.g. "bert-large/dist"
    kind: str                    # single | dist | chunk | custom
    jaxpr: Any = None            # ClosedJaxpr (make_jaxpr output)
    lowered_text: str = ""       # StableHLO (jit(...).lower().as_text())
    compiled_text: str = ""      # optimized HLO, if compiled
    meta: Dict[str, Any] = field(default_factory=dict)


# --------------------------------------------------------------------- #
# Abstract model/optimizer state
# --------------------------------------------------------------------- #
def abstract_state(cfg, optimizer):
    """(params, opt_state) as ShapeDtypeStruct trees — no allocation."""
    params = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    return params, opt_state


def _target_meta(cfg, params, mkor_cfg: MKORConfig,
                 world: int) -> Dict[str, Any]:
    """Static facts the checkers compare the traced program against."""
    dense = statlib.iter_dense_layers(params)
    stats_bytes = 0
    factor_dims = set()
    for p in dense:
        stack, extra, d_in, d_out = statlib.layer_dims(
            statlib.tree_get(params, p))
        n = int(np.prod(stack)) if stack else 1
        stats_bytes += n * d_in * 4            # one fp32 a-vec psum each
        factor_dims.update((d_in, d_out))
    manifest = manifest_for(params, mkor_cfg)
    fbytes = statlib.factor_itemsize(mkor_cfg.factor_dtype,
                                     mkor_cfg.factor_quant)
    sbytes = np.dtype(collectives.RANK1_PAYLOAD_DTYPE).itemsize
    comm = {b.bucket_id: statlib.bucket_comm_cost(
                b, world_size=world, factor_bytes=fbytes,
                stats_bytes=sbytes, rank=mkor_cfg.rank,
                factor_quant=mkor_cfg.factor_quant)
            for b in manifest}
    grad_bytes = sum(int(np.prod(l.shape)) * 4
                     for l in jax.tree.leaves(params))
    return {
        "model_cfg": cfg,
        "mkor_cfg": mkor_cfg,
        "manifest": manifest,
        "world": world,
        "n_dense_layers": len(dense),
        "n_buckets": len(manifest),
        "staleness": mkor_cfg.staleness,
        "factor_dims": factor_dims,
        "grad_f32_bytes": grad_bytes,
        "stats_f32_bytes": stats_bytes,
        "bucket_comm": comm,
        "grad_dtypes": {b.bucket_id: str(statlib.tree_get(
            params, b.paths[0])["w"].dtype) for b in manifest},
    }


def _default_optimizer(mkor_cfg: MKORConfig):
    return mkor(firstorder.lamb(1e-3), mkor_cfg)


# --------------------------------------------------------------------- #
# Target builders
# --------------------------------------------------------------------- #
def single_target(arch: str, *, mkor_cfg: Optional[MKORConfig] = None,
                  global_batch: int = 8, seq_len: int = 16,
                  reduced: bool = False, lower: bool = False) -> LintTarget:
    """The single-device jitted train step (training.loop.make_train_step)."""
    cfg = registry.get_config(normalize_arch(arch))
    if reduced:
        cfg = cfg.reduced()
    mkor_cfg = mkor_cfg or MKORConfig()
    opt = _default_optimizer(mkor_cfg)
    params, opt_state = abstract_state(cfg, opt)
    batch = train_lib.train_batch_shapes(cfg, global_batch, seq_len)
    step = jax.jit(train_lib.make_train_step(cfg, opt))
    jaxpr = jax.make_jaxpr(step)(params, opt_state, batch)
    lowered = step.lower(params, opt_state, batch).as_text() if lower else ""
    suffix = ("-async" if mkor_cfg.staleness else "") \
        + ("-health" if mkor_cfg.health else "")
    return LintTarget(
        name=f"{cfg.name}/single{suffix}", kind="single", jaxpr=jaxpr,
        lowered_text=lowered,
        meta=_target_meta(cfg, params, mkor_cfg, world=1))


def dist_target(arch: str, *, world: int = 8,
                mkor_cfg: Optional[MKORConfig] = None,
                global_batch: int = 8, seq_len: int = 16,
                reduced: bool = False,
                live: Optional[tuple] = None,
                compile_hlo: bool = False) -> LintTarget:
    """The explicit-collective shard_map step (``--dist``).  Needs
    ``world`` available devices (the CLI forces fake host devices; tests
    ride conftest's 8).  ``live`` traces the elastic-remapped step
    (MKORConfig.live, DESIGN.md §15): dead workers own zero inversion
    slices and ownership re-splits over the survivors — the
    `elastic-remap` checker proves the remap adds zero ungated traffic."""
    cfg = registry.get_config(normalize_arch(arch))
    if reduced:
        cfg = cfg.reduced()
    if global_batch % world:
        raise ValueError(f"global_batch {global_batch} must be a multiple "
                         f"of world {world}")
    mesh = mesh_lib.make_host_mesh(n_data=world)
    dist = collectives.dist_axes(mesh, mesh_lib.mesh_axes(mesh))
    mkor_cfg = dataclasses.replace(mkor_cfg or MKORConfig(), dist=dist,
                                   live=live)
    opt = _default_optimizer(mkor_cfg)
    params, opt_state = abstract_state(cfg, opt)
    batch = train_lib.train_batch_shapes(cfg, global_batch, seq_len)
    step = train_lib.make_dist_train_step(cfg, opt, mesh)
    jaxpr = jax.make_jaxpr(step)(params, opt_state, batch)
    compiled = ""
    if compile_hlo:
        compiled = step.lower(params, opt_state,
                              batch).compile().as_text()
    suffix = ("-async" if mkor_cfg.staleness else "") \
        + ("-health" if mkor_cfg.health else "") \
        + ("-remap" if live is not None and not all(live) else "")
    meta = _target_meta(cfg, params, mkor_cfg, world=world)
    if live is not None:
        meta["live"] = tuple(bool(x) for x in live)
    return LintTarget(
        name=f"{cfg.name}/dist{suffix}", kind="dist", jaxpr=jaxpr,
        compiled_text=compiled, meta=meta)


def chunk_target(arch: str, *, chunk: int = 2, steps: int = 100,
                 donate: bool = True,
                 mkor_cfg: Optional[MKORConfig] = None,
                 global_batch: int = 8, seq_len: int = 16,
                 reduced: bool = False) -> LintTarget:
    """The scan-chunked runner (training.loop.make_chunk_runner) lowered
    to StableHLO — where the ``tf.aliasing_output`` donation marks live."""
    cfg = registry.get_config(normalize_arch(arch))
    if reduced:
        cfg = cfg.reduced()
    mkor_cfg = mkor_cfg or MKORConfig()
    opt = _default_optimizer(mkor_cfg)
    params, opt_state = abstract_state(cfg, opt)
    batch = train_lib.train_batch_shapes(cfg, global_batch, seq_len)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((chunk,) + s.shape, s.dtype), batch)
    runner = train_lib.make_chunk_runner(
        train_lib.make_train_step(cfg, opt), donate=donate)
    jaxpr = jax.make_jaxpr(runner)(params, opt_state, stacked)
    lowered = runner.lower(params, opt_state, stacked).as_text()
    meta = _target_meta(cfg, params, mkor_cfg, world=1)
    meta.update({
        "chunk": chunk,
        "steps": steps,
        "donate": donate,
        "n_carry_leaves": len(jax.tree.leaves((params, opt_state))),
    })
    suffix = "-async" if mkor_cfg.staleness else ""
    return LintTarget(name=f"{cfg.name}/chunk{suffix}", kind="chunk",
                      jaxpr=jaxpr, lowered_text=lowered, meta=meta)


def custom_target(name: str, fn: Callable, *args, kind: str = "custom",
                  lower: bool = False, compile_hlo: bool = False,
                  meta: Optional[Dict[str, Any]] = None) -> LintTarget:
    """Wrap an arbitrary function for the checkers — the seeded-violation
    test fixtures use this to lint deliberately-broken steps."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    lowered = compiled = ""
    if lower or compile_hlo:
        low = jax.jit(fn).lower(*args)
        lowered = low.as_text()
        if compile_hlo:
            compiled = low.compile().as_text()
    return LintTarget(name=name, kind=kind, jaxpr=jaxpr,
                      lowered_text=lowered, compiled_text=compiled,
                      meta=dict(meta or {}))


def attach_health_baseline(health_target: LintTarget,
                           plain_target: LintTarget) -> LintTarget:
    """Record the health-off twin's ungated per-step collective footprint
    in the health-on target's meta (``plain_ungated_bytes`` /
    ``plain_ungated_count``).

    The `health-gating` checker uses this as its differential baseline:
    the sentinel derives every signal from already-replicated data, so
    turning it on must add ZERO ungated collectives and zero ungated
    wire bytes (DESIGN.md §14).  Mutates and returns ``health_target``."""
    from repro.analysis import jaxpr_walk

    res = jaxpr_walk.walk(plain_target.jaxpr)
    ungated = [c for c in res.collectives if not c.gated]
    health_target.meta["plain_ungated_bytes"] = sum(
        c.payload_bytes for c in ungated)
    health_target.meta["plain_ungated_count"] = len(ungated)
    return health_target


def attach_static_owner_baseline(remap_target: LintTarget,
                                 static_target: LintTarget) -> LintTarget:
    """Record the fully-live twin's ungated per-step collective footprint
    in the remapped target's meta (``static_ungated_bytes`` /
    ``static_ungated_count``).

    The `elastic-remap` checker uses this as its differential baseline:
    failover re-splits the phase-gated inversion work over the survivors,
    so the remapped step must add ZERO ungated collectives and zero
    ungated wire bytes vs the static owner map (DESIGN.md §15).  Mutates
    and returns ``remap_target``."""
    from repro.analysis import jaxpr_walk

    res = jaxpr_walk.walk(static_target.jaxpr)
    ungated = [c for c in res.collectives if not c.gated]
    remap_target.meta["static_ungated_bytes"] = sum(
        c.payload_bytes for c in ungated)
    remap_target.meta["static_ungated_count"] = len(ungated)
    return remap_target


def attach_sync_baseline(async_target: LintTarget,
                         sync_target: LintTarget) -> LintTarget:
    """Record the sync step's ungated per-step collective footprint in the
    async target's meta (``sync_ungated_bytes`` / ``sync_ungated_count``).

    The `staleness-bound` checker uses this as its differential baseline:
    the async schedule must move NO more ungated (i.e. every-step) bytes
    than the synchronous step it replaces — the whole point of the overlap
    is reordering work, not shipping extra state.  Mutates and returns
    ``async_target``."""
    from repro.analysis import jaxpr_walk

    res = jaxpr_walk.walk(sync_target.jaxpr)
    ungated = [c for c in res.collectives if not c.gated]
    async_target.meta["sync_ungated_bytes"] = sum(
        c.payload_bytes for c in ungated)
    async_target.meta["sync_ungated_count"] = len(ungated)
    return async_target
