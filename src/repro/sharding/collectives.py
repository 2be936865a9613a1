"""Explicit collectives for the distributed MKOR step (DESIGN.md §10).

MKOR's systems claim is *linear communication complexity*: per layer the
workers exchange the rank-1 statistics vectors ā (d_in,) and ḡ (d_out,) —
O(d) on the wire — instead of the O(d²) Kronecker factors/inverses that
KFAC/KAISA-style distributions broadcast on every factor update.  This
module is the communication layer that makes that schedule explicit under
``jax.shard_map`` instead of leaving collective placement to
GSPMD:

* :func:`pmean_rank1_stats` — mean-reduce only the rank-1 ``"a"`` leaves of
  the stats tree across the data axes.  The payload is quantized to bf16
  (the factor dtype — Lemma 3.2 bounds the factor quantization error, so a
  bf16 stat vector costs nothing extra) and accumulated in fp32.  Note the
  wire dtype is whatever the backend lowers the fp32 psum to: the CPU
  emulation moves fp32 (the quantization then only bounds the payload's
  information content), while the TPU-target accounting
  (launch/hlo_analysis.py's bf16-origin rule) counts the collective at
  bf16 width.
* :func:`all_reduce_mean_tree` — one flat-bucket gradient all-reduce:
  every leaf is raveled into a single fp32 buffer, reduced with an explicit
  reduce-scatter + all-gather pair (the two halves of a ring all-reduce),
  and split back.  One pair of collectives per step instead of one
  all-reduce per leaf.
* :func:`owner_shard` / :func:`gather_shards` — the owner-sharded inversion
  schedule: each data-parallel worker slices out the bank-dim chunk of the
  factor bank it owns, runs stabilize+SMW on that chunk only, and the
  updated inverse slices are all-gathered.  Per phase step each worker
  ships 1/world_size of the bucket's factor bytes instead of the full
  factors a single-owner broadcast would move.

A "dist spec" is a static, hashable description of the data axes of the
active mesh: ``((axis_name, axis_size), ...)``, e.g. ``(("data", 8),)`` or
``(("pod", 2), ("data", 16))``.  Axis order follows the mesh's axis order,
which matches the row-major concatenation order jax uses for multi-axis
``all_gather``/``psum_scatter`` — :func:`worker_index` is defined to agree
with it.  Everything here must run inside ``shard_map`` over those axes.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes

DistSpec = Tuple[Tuple[str, int], ...]

# The wire contract the dtype-discipline lint (repro.analysis) checks
# statically: rank-1 stat payloads are quantized to the factor dtype
# before the reduction, and every mean reduction accumulates in fp32.
RANK1_PAYLOAD_DTYPE = "bfloat16"
ACCUM_DTYPE = "float32"

# Owner-gather wire dtype under factor_quant="int8" (DESIGN.md §16): the
# dominant phase-step payload is the int8 factor codes + fp32 per-slice
# scales — ~2x smaller than the bf16 factors it replaces.  The
# quant-discipline lint (repro.analysis) proves the gathered payload is
# int8-origin against this contract.
QUANT_WIRE_DTYPE = "int8"


def dist_axes(mesh, axes) -> DistSpec:
    """Build the dist spec for a mesh + MeshAxes (sharding/rules.py)."""
    return tuple((a, int(mesh.shape[a])) for a in axes.data)


def axis_names(dist: DistSpec) -> Tuple[str, ...]:
    return tuple(n for n, _ in dist)


def world_size(dist: Optional[DistSpec]) -> int:
    if not dist:
        return 1
    w = 1
    for _, s in dist:
        w *= int(s)
    return w


def worker_index(dist: DistSpec) -> jnp.ndarray:
    """Row-major linear worker index over the dist axes — the same order in
    which multi-axis ``all_gather(..., tiled=True)`` concatenates shards."""
    idx = jnp.zeros((), jnp.int32)
    for name, size in dist:
        idx = idx * size + lax.axis_index(name)
    return idx


def _names(dist: DistSpec):
    names = axis_names(dist)
    return names if len(names) > 1 else names[0]


# --------------------------------------------------------------------- #
# Mean reductions
# --------------------------------------------------------------------- #
def pmean(x: jnp.ndarray, dist: DistSpec) -> jnp.ndarray:
    """Mean over the data axes, accumulated in fp32 (ACCUM_DTYPE)."""
    out = lax.psum(x.astype(jnp.dtype(ACCUM_DTYPE)),
                   _names(dist)) / world_size(dist)
    return out.astype(x.dtype)


def pmean_tree(tree, dist: DistSpec):
    return jax.tree.map(lambda x: pmean(x, dist), tree)


def pmean_rank1_stats(stats, dist: DistSpec,
                      payload_dtype: Optional[str] = RANK1_PAYLOAD_DTYPE):
    """Synchronize ONLY the rank-1 statistics across the data axes.

    The stats tree mirrors the params tree with each dense layer replaced
    by a dict holding ``"a"`` = E[a] (plus, for the full-stat baselines,
    per-sample ``"A"``/``"G"`` matrices).  Only the O(d) ``"a"`` means are
    exchanged — that is MKOR's linear-communication contract; full-stat
    leaves are dropped from the reduced tree (a KFAC-style optimizer needs
    its own O(d²) schedule and cannot ride this one).  The reduction is
    shape-agnostic: a rank-r stat block (r, d) still rides it at O(r·d) —
    though the block rank-r schedule (DESIGN.md §11) deliberately ships
    only the per-step (d,) vectors and rebuilds its ring windows from them
    on every worker, so ``MKORConfig.rank`` adds zero wire bytes per step.

    ``payload_dtype`` quantizes the payload (default bf16, matching
    ``MKORConfig.factor_dtype``); the psum itself runs in fp32 — that is
    the accumulation guarantee, and also what the CPU lowering puts on the
    wire (see the module docstring for the TPU-target byte accounting).
    ``None`` skips quantization — the bit-tight mode the single-device
    equivalence tests use.
    """
    pd = jnp.dtype(payload_dtype) if payload_dtype is not None else None

    def reduce_a(a):
        payload = a.astype(pd) if pd is not None else a
        out = lax.psum(payload.astype(jnp.dtype(ACCUM_DTYPE)),
                       _names(dist))
        return (out / world_size(dist)).astype(a.dtype)

    def walk(node):
        if isinstance(node, dict):
            if "a" in node and hasattr(node["a"], "ndim"):
                return {"a": reduce_a(node["a"])}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        return node

    return walk(stats)


def flat_reduce_scatter_mean(tree, dist: DistSpec):
    """First half of the flat-bucket gradient mean: ravel every leaf into
    one fp32 buffer and reduce-scatter it, leaving worker i owning (and
    having summed) shard i.  Returns ``(shard, spec)`` where ``spec`` is
    the static unflatten recipe for :func:`flat_all_gather_tree`.

    Splitting the ring all-reduce into its two explicit phases is what
    gives the async inversion schedule (DESIGN.md §13) its overlap window:
    the dist step can issue the reduce-scatter, interleave independent
    work (the stat pmean, the already-launched factor inversions), and
    only then all-gather — XLA's async collectives hide the inversion
    latency inside the gradient exchange."""
    leaves, treedef = jax.tree.flatten(tree)
    spec = (treedef, leaves)
    if not leaves:
        return None, spec
    w = world_size(dist)
    flat = jnp.concatenate([l.astype(jnp.float32).ravel() for l in leaves])
    n = flat.size
    pad = (-n) % w
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, _names(dist), scatter_dimension=0,
                             tiled=True) / w
    return shard, spec


def flat_all_gather_tree(shard, spec, dist: DistSpec):
    """Second half of the flat-bucket mean: all-gather the reduced shards
    back in worker order, trim the pad, and unflatten to the original tree
    (leaf shapes/dtypes from ``spec``)."""
    treedef, leaves = spec
    if not leaves:
        return jax.tree.unflatten(treedef, leaves)
    n = sum(l.size for l in leaves)
    full = lax.all_gather(shard, _names(dist), tiled=True)
    if full.size != n:
        full = full[:n]
    out, off = [], 0
    for l in leaves:
        k = l.size
        out.append(full[off:off + k].reshape(l.shape).astype(l.dtype))
        off += k
    return jax.tree.unflatten(treedef, out)


def all_reduce_mean_tree(tree, dist: DistSpec):
    """Flat-bucket gradient mean: ravel every leaf into one fp32 buffer,
    reduce-scatter it across the data axes, all-gather the reduced shards
    back, and unflatten.  Explicitly the two phases of a ring all-reduce —
    one collective pair per step regardless of tree width.  Composition of
    :func:`flat_reduce_scatter_mean` + :func:`flat_all_gather_tree`; the
    dist train step calls the halves directly to interleave independent
    work between them."""
    shard, spec = flat_reduce_scatter_mean(tree, dist)
    return flat_all_gather_tree(shard, spec, dist)


# --------------------------------------------------------------------- #
# Owner-sharded factor inversions (DESIGN.md §10, liveness §15)
# --------------------------------------------------------------------- #
LiveMask = Tuple[bool, ...]


def normalize_live(dist: Optional[DistSpec],
                   live: Optional[LiveMask]) -> LiveMask:
    """Validated per-worker liveness tuple (``None`` → fully live).  The
    mask is static config: remapping ownership after a death/demotion is a
    recompile with a new mask, not a runtime branch (DESIGN.md §15)."""
    w = world_size(dist)
    if live is None:
        return (True,) * w
    mask = tuple(bool(x) for x in live)
    if len(mask) != w:
        raise ValueError(f"liveness mask has {len(mask)} entries "
                         f"for world {w}")
    if not any(mask):
        raise ValueError("liveness mask declares every worker dead")
    return mask


def n_live(dist: Optional[DistSpec],
           live: Optional[LiveMask] = None) -> int:
    return sum(normalize_live(dist, live))


def survivor_index(dist: DistSpec,
                   live: Optional[LiveMask] = None) -> jnp.ndarray:
    """This worker's rank among the live workers (dead workers get 0 — any
    value they compute is masked out of the recombine).  The static mask
    lowers to a constant gather on :func:`worker_index`."""
    mask = normalize_live(dist, live)
    ranks, r = [], 0
    for alive in mask:
        ranks.append(r if alive else 0)
        r += int(alive)
    return jnp.asarray(ranks, jnp.int32)[worker_index(dist)]


def is_live(dist: DistSpec,
            live: Optional[LiveMask] = None) -> jnp.ndarray:
    """Per-worker liveness bit as a traced scalar (constant-indexed)."""
    mask = normalize_live(dist, live)
    return jnp.asarray(mask, jnp.bool_)[worker_index(dist)]


def effective_live(dist: Optional[DistSpec],
                   live: Optional[LiveMask]) -> Optional[LiveMask]:
    """Degrade a fully-live mask to ``None`` so the all-live elastic step
    traces to the IDENTICAL program as the static step — the steady-state
    in-graph overhead of ``--elastic`` is exactly zero (perf-budget
    contract, benchmarks/failover.py)."""
    if live is None:
        return None
    mask = normalize_live(dist, live)
    return None if all(mask) else mask


def owner_chunk(n_slots: int, world: int) -> int:
    """Bank-dim slots each worker owns (last chunks may be pure padding).
    Under a liveness mask ``world`` is the number of LIVE workers."""
    return -(-n_slots // max(world, 1))


def owner_shard(x: jnp.ndarray, dist: DistSpec,
                live: Optional[LiveMask] = None) -> jnp.ndarray:
    """Slice this worker's owned chunk of a bank-dim-leading array.

    dim 0 is padded (zeros) to ``n_live * chunk`` so every worker slices a
    static-size chunk; zero-padded slots are numerically inert through
    stabilize + SMW (zero factor, zero vector → zero update) and are
    dropped again by :func:`gather_shards`.  Under a liveness mask the
    slices are re-split over the survivors (survivor-rank offsets); dead
    workers slice offset 0 — whatever they compute never reaches the
    recombined bank."""
    live = effective_live(dist, live)
    mask = normalize_live(dist, live)
    nl = sum(mask)
    chunk = owner_chunk(x.shape[0], nl)
    padded = nl * chunk
    if padded > x.shape[0]:
        x = jnp.pad(x, [(0, padded - x.shape[0])] + [(0, 0)] * (x.ndim - 1))
    off = (survivor_index(dist, mask) if live is not None
           else worker_index(dist)) * chunk
    return lax.dynamic_slice_in_dim(x, off, chunk, axis=0)


def owner_sharded_map(fn, arrays, dist: DistSpec, n_slots: int,
                      live: Optional[LiveMask] = None) -> jnp.ndarray:
    """Owner-sharded map over dim 0: slice each array's owned chunk
    (:func:`owner_shard`), apply ``fn`` to the local chunks, and recombine
    the result's dim 0 (:func:`gather_shards`).

    ``fn(*chunks)`` must return ONE array whose dim 0 matches the chunk
    extent; zero-padded slots reach it and must be numerically inert (the
    factor paths guarantee this: zero factor + zero vector, or a rank-r
    window count of 0, is a no-op).  This is the single home of the
    pad/slice/compute/recombine contract the optimizer's rank-1 and
    block-rank-r inversions share (DESIGN.md §10/§11).  A liveness mask
    redistributes the chunks over the survivors without touching state
    layout — the elastic-remap contract is that this changes WHO inverts a
    slice, never what is shipped per step (DESIGN.md §15)."""
    chunks = [owner_shard(x, dist, live) for x in arrays]
    return gather_shards(fn(*chunks), dist, n_slots, live)


def owner_sharded_map_quant(fn, arrays, dist: DistSpec, n_slots: int,
                            live: Optional[LiveMask] = None
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Owner-sharded map whose result is a QUANTIZED bank chunk: ``fn``
    returns ``(codes, scales)`` — int8 values with dim 0 matching the
    chunk extent plus their fp32 per-slice scales — and BOTH are
    recombined (DESIGN.md §16).

    The wire payload per phase step is the int8 codes + the (tiny) fp32
    scales instead of the bf16 factors: ~2x fewer bytes.  The recombine
    is exact for both :func:`gather_shards` strategies: ``all_gather``
    moves the codes verbatim, and the masked-psum sums DISJOINT integer
    contributions (each slot has exactly one non-zero contributor, and
    int8 addition of a value and zero is exact).  The owner quantizes its
    freshly inverted fp32 chunk right at the wire boundary, so the wire
    quantization IS the storage quantization — workers store the gathered
    codes directly and every replica holds bit-identical banks."""
    chunks = [owner_shard(x, dist, live) for x in arrays]
    codes, scales = fn(*chunks)
    if jnp.dtype(codes.dtype) != jnp.dtype(QUANT_WIRE_DTYPE):
        raise TypeError(f"quantized owner-gather payload must be "
                        f"{QUANT_WIRE_DTYPE}, got {codes.dtype}")
    return (gather_shards(codes, dist, n_slots, live),
            gather_shards(scales, dist, n_slots, live))


@scopes.scoped(scopes.OWNER_GATHER)
def gather_shards(x: jnp.ndarray, dist: DistSpec, n_slots: int,
                  live: Optional[LiveMask] = None) -> jnp.ndarray:
    """Recombine the per-worker owned chunks into the full bank dim.

    Each worker's wire *payload* is its chunk — ~1/min(world, n_slots) of
    the bank bytes.  Two recombine strategies, chosen statically:

    * ``all_gather`` (tiled, padded tail dropped) when every worker is live
      and the padded gather is within ~2x of the useful bytes — the cheap
      case whenever the bank has at least ~world/2 slices;
    * masked-psum otherwise (world >> n_slots, where a padded all-gather
      would move world/n_slots times the bank — or any worker is dead, so
      worker order no longer equals chunk order): every live worker
      scatters its chunk into a zero buffer at its survivor-rank offset,
      dead workers contribute an all-zero buffer, and one all-reduce sums
      the disjoint contributions — bit-exact (each slot has exactly one
      non-zero contributor; adding zeros is exact in fp) and bounded at
      ring-all-reduce cost ~2x the bank bytes regardless of world size.
    """
    live = effective_live(dist, live)
    mask = normalize_live(dist, live)
    nl = sum(mask)
    chunk = x.shape[0]
    padded = nl * chunk
    if live is None and (nl - 1) * chunk <= 2 * n_slots:
        full = lax.all_gather(x, _names(dist), axis=0, tiled=True)
        return full[:n_slots]
    if live is not None:
        x = jnp.where(is_live(dist, mask), x,
                      jnp.zeros_like(x))
        off = survivor_index(dist, mask) * chunk
    else:
        off = worker_index(dist) * chunk
    buf = jnp.zeros((padded,) + x.shape[1:], x.dtype)
    buf = lax.dynamic_update_slice_in_dim(buf, x, off, axis=0)
    return lax.psum(buf[:n_slots], _names(dist))
