"""Train-step builder: loss, gradients, MKOR stat plumbing, optimizer glue.

One jitted step contains the full Algorithm-1 pipeline:
forward (capturing E[a]) → backward (probe grads = E[g], all-reduced with
the weight gradients) → MKOR factor update + preconditioning → backend
optimizer → parameter update.  Under pjit the rank-1 statistics are
synchronised by the same collective schedule as the gradients — the paper's
line-4 AllReduce at O(d) volume.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import scopes
from repro.core import firstorder
from repro.core.firstorder import GradientTransformation
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.sharding import collectives


def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray,
            ignore_id: int = -1) -> jnp.ndarray:
    """Mean next-token cross-entropy.  The mean reduction is what makes the
    probe-gradient identity exact (models/layers.py docstring).

    Written as compare-select-reduce over the vocab dim (no log-softmax /
    one-hot materialisation) so a vocab-sharded logits tensor (256k vocab,
    gemma2) reduces shard-locally under GSPMD — the only cross-shard traffic
    is the scalar-per-token logsumexp partial."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    label_logit = jnp.sum(
        jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    nll = lse - label_logit
    valid = labels != ignore_id
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)


def text_prefix_len(cfg: ModelConfig) -> int:
    """Positions occupied by the multimodal prefix in decoder-only VLMs."""
    if cfg.frontend != "none" and not cfg.is_encoder_decoder:
        return cfg.frontend_len
    return 0


def make_loss_fn(cfg: ModelConfig, *, collect_stats: bool = True) -> Callable:
    n_prefix = text_prefix_len(cfg)

    def loss_fn(params, batch):
        logits, aux = model_lib.forward(params, cfg, batch,
                                        collect_stats=collect_stats)
        text_logits = logits[:, n_prefix:] if n_prefix else logits
        loss_lm = lm_loss(text_logits, batch["labels"])
        loss = loss_lm + aux["moe_aux"]
        return loss, {"stats": aux["stats"], "loss_lm": loss_lm,
                      "moe_aux": aux["moe_aux"]}

    return loss_fn


def train_batch_shapes(cfg: ModelConfig, global_batch: int, seq_len: int,
                       *, dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs for one training batch (dry-run input_specs)."""
    n_prefix = text_prefix_len(cfg)
    n_text = seq_len - n_prefix
    shapes = {
        "tokens": jax.ShapeDtypeStruct((global_batch, n_text), jnp.int32),
        "labels": jax.ShapeDtypeStruct((global_batch, n_text), jnp.int32),
    }
    if cfg.frontend != "none":
        fl = cfg.encoder.n_positions if cfg.is_encoder_decoder \
            else cfg.frontend_len
        fd = cfg.frontend_dim or cfg.d_model
        shapes["frontend_embeds"] = jax.ShapeDtypeStruct(
            (global_batch, fl, fd), dtype)
        if cfg.is_encoder_decoder:
            # encoder consumes the frames; decoder sees the full seq_len
            shapes["tokens"] = jax.ShapeDtypeStruct(
                (global_batch, seq_len), jnp.int32)
            shapes["labels"] = jax.ShapeDtypeStruct(
                (global_batch, seq_len), jnp.int32)
    return shapes


def step_metrics(params, updates, grads, **metrics):
    """The step's last stage: the updates added to the parameters, and
    the step's metrics with the gradient and update norms."""
    with jax.named_scope(scopes.APPLY):
        params = firstorder.apply_updates(params, updates)
        return params, {**metrics,
                        "grad_norm": firstorder.global_norm(grads),
                        "update_norm": firstorder.global_norm(updates)}


def make_train_step(cfg: ModelConfig, optimizer: GradientTransformation,
                    *, collect_stats: bool = True,
                    donate: bool = True) -> Callable:
    loss_fn = scopes.scoped(scopes.FORWARD)(
        make_loss_fn(cfg, collect_stats=collect_stats))

    def train_step(params, opt_state, batch):
        # Two-phase async protocol (DESIGN.md §13): the precompute tick
        # consumes only carried state, so running it BEFORE the gradients
        # exist hands XLA an inversion launch it can overlap with the
        # forward/backward.  Sync optimizers (precompute=None) skip it.
        if optimizer.precompute is not None:
            opt_state = optimizer.precompute(opt_state, params=params)
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        updates, opt_state = optimizer.update(
            grads, opt_state, params=params, stats=aux["stats"], loss=loss,
            precomputed=optimizer.precompute is not None)
        params, metrics = step_metrics(
            params, updates, grads, loss=loss, loss_lm=aux["loss_lm"],
            moe_aux=aux["moe_aux"])
        return params, opt_state, metrics

    return train_step


# ----------------------------------------------------------------------- #
# Explicit-collective distributed step (DESIGN.md §10)
#
# Under pjit/GSPMD the rank-1 statistics ride whatever collective schedule
# the partitioner picks for the replicated factor state — the paper's
# linear-communication design is neither explicit nor measurable.  The
# shard_map step below makes every wire byte explicit: the batch is the
# only sharded input, gradients are mean-reduced with one flat
# reduce-scatter + all-gather pair, the rank-1 stats are mean-reduced at
# O(d) per layer (bf16 payload, fp32 accumulation), and — when the
# optimizer carries ``MKORConfig.dist`` — factor inversions are
# owner-sharded over the bank dim with the inverse slices all-gathered
# only on each bucket's phase step.
# ----------------------------------------------------------------------- #
def make_dist_step_fn(grads_fn: Callable, optimizer: GradientTransformation,
                      mesh: Mesh, data_axes: Sequence[str], *,
                      stats_payload_dtype: Optional[str] = "bfloat16"
                      ) -> Callable:
    """Wrap a local ``grads_fn(params, local_batch) -> (loss, grads, stats
    [, extra_metrics])`` into a jitted shard_map step with explicit
    data-parallel collectives.

    params/opt_state are replicated (each worker holds full copies — the
    paper's per-worker replication; FSDP-style weight sharding stays with
    the GSPMD path, sharding/rules.py); every batch leaf is sharded on its
    leading dim across ``data_axes``.  Returns a ``(params, opt_state,
    batch) -> (params, opt_state, metrics)`` step interchangeable with
    :func:`make_train_step` — it composes with :func:`make_chunk_runner`
    unchanged.

    The step is allclose-equal to the single-device path when the global
    batch splits evenly (mean-of-equal-shard-means == global mean); set
    ``stats_payload_dtype=None`` for the bit-tight variant the equivalence
    tests use (default bf16 quantizes the stat payload to the factor
    dtype's precision — Lemma 3.2 territory).
    """
    dist = tuple((a, int(mesh.shape[a])) for a in data_axes)
    names = collectives.axis_names(dist)
    batch_axis = names if len(names) > 1 else names[0]
    world = collectives.world_size(dist)

    def local_step(params, opt_state, batch):
        # Async tick first (DESIGN.md §13): launched on carried state only,
        # before any of this step's data exists, so the owner shards'
        # next-phase inversions are free to overlap with the forward/
        # backward AND the gradient collectives below.
        if optimizer.precompute is not None:
            opt_state = optimizer.precompute(opt_state, params=params)
        out = grads_fn(params, batch)
        loss, grads, stats = out[:3]
        extra = out[3] if len(out) > 3 else {}
        # Gradient mean as its two explicit ring-all-reduce phases with
        # the independent O(d) stat pmean interleaved between them — the
        # widest scheduling window for hiding the inversion launch inside
        # the gradient exchange (numerically identical to the fused
        # all_reduce_mean_tree; the stat pmean commutes with both halves).
        with jax.named_scope(scopes.GRAD_ALLREDUCE):
            loss = collectives.pmean(loss, dist)
            shard, spec = collectives.flat_reduce_scatter_mean(grads, dist)
        with jax.named_scope(scopes.STAT_ALLREDUCE):
            stats = collectives.pmean_rank1_stats(
                stats, dist, payload_dtype=stats_payload_dtype)
        with jax.named_scope(scopes.GRAD_ALLREDUCE):
            grads = collectives.flat_all_gather_tree(shard, spec, dist)
        updates, opt_state = optimizer.update(
            grads, opt_state, params=params, stats=stats, loss=loss,
            precomputed=optimizer.precompute is not None)
        with jax.named_scope(scopes.APPLY):
            extra = {k: collectives.pmean(v, dist) for k, v in extra.items()}
        params, metrics = step_metrics(params, updates, grads, loss=loss,
                                       **extra)
        return params, opt_state, metrics

    def step(params, opt_state, batch):
        for path, leaf in jax.tree_util.tree_leaves_with_path(batch):
            if not leaf.shape or leaf.shape[0] % world:
                raise ValueError(
                    f"batch leaf {jax.tree_util.keystr(path)} leading dim "
                    f"{leaf.shape and leaf.shape[0]} does not divide the "
                    f"data world size {world}")
        bspecs = jax.tree.map(
            lambda x: P(batch_axis, *([None] * (x.ndim - 1))), batch)
        fn = jax.shard_map(local_step, mesh=mesh,
                           in_specs=(P(), P(), bspecs),
                           out_specs=(P(), P(), P()), check_vma=False)
        return fn(params, opt_state, batch)

    return jax.jit(step)


def make_dist_train_step(cfg: ModelConfig,
                         optimizer: GradientTransformation, mesh: Mesh,
                         data_axes: Sequence[str] = ("data",), *,
                         collect_stats: bool = True,
                         stats_payload_dtype: Optional[str] = "bfloat16"
                         ) -> Callable:
    """Distributed variant of :func:`make_train_step` (launch/train.py
    ``--dist``): same signature and metrics, explicit collectives.  Build
    the MKOR optimizer with ``MKORConfig.dist = collectives.dist_axes(...)``
    to owner-shard the factor inversions across the same axes."""
    loss_fn = scopes.scoped(scopes.FORWARD)(
        make_loss_fn(cfg, collect_stats=collect_stats))

    def local_grads(params, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, grads, aux["stats"], {"loss_lm": aux["loss_lm"],
                                           "moe_aux": aux["moe_aux"]}

    return make_dist_step_fn(local_grads, optimizer, mesh, data_axes,
                             stats_payload_dtype=stats_payload_dtype)


# ----------------------------------------------------------------------- #
# Scan-driven multi-step runner (DESIGN.md §9)
#
# The per-step Python loop pays one dispatch plus a blocking float(metrics)
# device sync per step — at small scale that, not the optimizer, is the
# bottleneck.  The chunk runner stacks `chunk` prefetched batches and runs
# them under ONE jitted lax.scan with donated (params, opt_state): one
# dispatch per chunk, metrics fetched off-device once per chunk.
# ----------------------------------------------------------------------- #
def chunk_schedule(n_steps: int, chunk: int) -> List[int]:
    """Chunk lengths for an ``n_steps`` run at scan-chunk size ``chunk``.

    At most TWO distinct lengths appear (full chunks + one trailing
    partial), so the chunk runner compiles at most two traces per run —
    the retrace bound the donation/retrace lint asserts statically."""
    chunk = max(chunk, 1)
    full, rem = divmod(max(n_steps, 0), chunk)
    return [chunk] * full + ([rem] if rem else [])


def stack_batches(batches: Sequence[Dict]) -> Dict:
    """Stack a list of same-shaped batch dicts along a new leading scan dim
    (host-side numpy: no device transfer until the runner call)."""
    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


def make_chunk_runner(step_fn: Callable, *, donate: bool = True) -> Callable:
    """Jit a ``(params, opt_state, stacked_batches) -> (params, opt_state,
    stacked_metrics)`` runner that folds ``step_fn`` over the chunk with
    ``lax.scan``.  (params, opt_state) are donated: the optimizer state
    (factor banks included) is updated in place buffer-wise, so peak memory
    stays at one copy regardless of chunk length."""

    def run_chunk(params, opt_state, stacked):
        def body(carry, batch):
            params, opt_state = carry
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            return (params, opt_state), metrics

        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), stacked)
        return params, opt_state, metrics

    return jax.jit(run_chunk, donate_argnums=(0, 1) if donate else ())


def run_chunk(runner: Callable, params, opt_state, batches: Sequence[Dict],
              step: int):
    """One chunk of the training loop: stack ``batches`` on the host, run
    them through ``runner`` (:func:`make_chunk_runner`), fetch the chunk's
    metrics.  Returns ``(params, opt_state, metrics)``, the metrics on the
    host and stacked per step.

    Each part is a host span of the profiler's trace (``stack_batches``,
    ``dispatch``, ``device_get``), inside a ``train`` step span numbered by
    the chunk's first step: on the trace's one clock with the device ops
    the runner's named scopes label (repro/scopes.py)."""
    with jax.profiler.StepTraceAnnotation(scopes.STEP_SPAN, step_num=step):
        with jax.profiler.TraceAnnotation(scopes.STACK_BATCHES):
            stacked = stack_batches(batches)
        with jax.profiler.TraceAnnotation(scopes.DISPATCH):
            params, opt_state, metrics = runner(params, opt_state, stacked)
        with jax.profiler.TraceAnnotation(scopes.DEVICE_GET):
            metrics = jax.device_get(metrics)       # one sync per chunk
    return params, opt_state, metrics


def train_epoch(step_fn: Callable, params, opt_state, batches, *,
                chunk: int = 8, donate: bool = True,
                runner: Optional[Callable] = None,
                hooks: Optional[Callable[[int, Dict], None]] = None):
    """Run ``batches`` through ``step_fn`` in jitted ``lax.scan`` chunks.

    Metrics come off-device once per chunk (stacked), then are split into
    per-step float dicts; ``hooks(step_idx, metrics)`` therefore fires in
    bursts at chunk boundaries, not per step — checkpoint/log cadence
    aligns to chunks (DESIGN.md §9).  A trailing partial chunk triggers one
    extra compile at its shorter length.  Returns (params, opt_state,
    history) like :func:`train_loop`.

    Callers invoking this once per epoch should build the runner ONCE with
    :func:`make_chunk_runner` and pass it via ``runner`` — a fresh runner
    per call means a fresh jit cache, i.e. a full recompile of the scanned
    step every epoch.
    """
    if runner is None:
        runner = make_chunk_runner(step_fn, donate=donate)
    history: List[Dict] = []

    def flush(buf):
        nonlocal params, opt_state
        params, opt_state, metrics = run_chunk(runner, params, opt_state,
                                               buf, len(history))
        for k in range(len(buf)):
            m = {key: float(v[k]) for key, v in metrics.items()}
            if hooks is not None:
                hooks(len(history), m)
            history.append(m)

    buf = []
    for batch in batches:
        buf.append(batch)
        if len(buf) == chunk:
            flush(buf)
            buf = []
    if buf:
        flush(buf)
    return params, opt_state, history


def train_loop(cfg: ModelConfig, optimizer: GradientTransformation,
               params, batches, *, jit: bool = True,
               hooks: Optional[Callable[[int, Dict], None]] = None):
    """Simple single-host per-step loop, kept for the hook-based examples
    (hooks fire synchronously every step; see train_epoch for the fast
    scan-chunked path)."""
    step_fn = make_train_step(cfg, optimizer)
    if jit:
        step_fn = jax.jit(step_fn)
    opt_state = optimizer.init(params)
    history = []
    for i, batch in enumerate(batches):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        history.append(metrics)
        if hooks is not None:
            hooks(i, metrics)
    return params, opt_state, history
