"""MKOR: Momentum-Enabled Kronecker-Factor-Based Optimizer Using Rank-1
Updates (NeurIPS 2023) — faithful implementation of Algorithm 1, plus the
hybrid MKOR-H controller (§3.2) and the higher-rank extension (§4).

Per eligible 2-D layer with weight W (d_in, d_out), gradient G, rank-1
statistics ā = E[a] (d_in,) and ḡ = E[g] (d_out,):

  line 5/6  norm-based stabilizer:   if ‖F⁻¹‖∞ > ε:  F⁻¹ ← ζF⁻¹ + (1−ζ)I
  line 7/8  SM-based factor inversion (Eq. 5/6, O(d²)):
      L⁻¹ ← γL⁻¹ + (1−γ) / (γ²(1 + γ(1−γ) ḡᵀL⁻¹ḡ)) · (L⁻¹ḡ)(L⁻¹ḡ)ᵀ
      R⁻¹ ← (same with ā)
  line 9    precondition:            ΔW = R⁻¹ G L⁻¹
  line 10   rescale:                 ΔW ← ΔW · ‖G‖_F / ‖ΔW‖_F
  line 14   backend step (LAMB / momentum-SGD / ...)

Factors are stored in ``factor_dtype`` (bf16 by default — the paper's
half-precision, TPU-native; Lemma 3.2 bounds the quantization error) and
updated every ``inv_freq`` steps (the paper uses ~10 vs KFAC's 100-1000).
The SM update is two mat-vecs + one outer product; Lemma 3.1 guarantees the
scalar denominator is positive, so there is no damping factor anywhere.

Beyond-paper options (each recorded in EXPERIMENTS.md):
* ``variant="exact_smw"`` — the *exact* Sherman–Morrison inverse of the
  EMA'd factor  (γL + (1−γ)ḡḡᵀ)⁻¹  (the paper's Eq. 5 is a PD-preserving
  approximation of it; see DESIGN.md).
* block rank-r updates (paper §4, DESIGN.md §11): ``rank=r`` buffers the
  last r per-step stat vectors per factor in a ring window (core/stats.py)
  and consumes the whole window on the factor's phase step with ONE
  block-Woodbury update (:func:`smw_block_update`) — O(r·d² + r³) in a
  single dispatch instead of r chained rank-1 dispatches.  (Legacy: stats
  carrying an extra leading rank dim still chain r rank-1 updates at
  rank=1.)
* ``use_pallas`` — fused Pallas TPU kernels for the SM update and the
  two-sided preconditioning (kernels/).
* factor sharding over the "model" mesh axis (launch/dryrun.py) instead of
  the paper's per-worker replication.

Factor banks (DESIGN.md §2)
---------------------------
With ``layout="bank"`` (the default) factors are not stored per layer but
in shape-bucketed *banks*: at ``init`` all eligible layers are grouped by
``(stack, extra, d_in, d_out)`` (core/stats.py bucket manifest) and each
bucket owns two stacked arrays

    l_inv: (n_layers_in_bucket, *stack, d_out, d_out)
    r_inv: (n_layers_in_bucket, *stack, d_in,  d_in)

``update`` then runs stabilize → SMW → precondition → rescale once per
bucket, vmapped over the bank dim, instead of once per layer in Python —
a handful of fused kernels per step regardless of depth.  The manifest is
static (pure function of tree structure + shapes) and is rebuilt at trace
time, so bank slots never need to be stored in the jitted state.
``layout="per_layer"`` keeps the legacy dict-of-factors state and is the
numerical reference the bank path is tested against (tests/test_mkor.py).

Staggered inversions (DESIGN.md §9)
-----------------------------------
With ``stagger=True`` (the default) bucket b inverts on steps where
``count % inv_freq == manifest[b].phase(inv_freq)`` — a static round-robin
that carries ~1/inv_freq of the SMW work per step instead of spiking it all
on every inv_freq-th step.  Each bucket still inverts exactly once per
window (factor staleness <= inv_freq, same bound as the paper's global
schedule); ``stagger=False`` restores the paper-exact spike.  The per-layer
oracle runs the identical schedule (each layer inherits its bucket's
phase), so layouts stay numerically interchangeable.

Overlap-hidden inversions (DESIGN.md §13)
-----------------------------------------
With ``staleness=1`` the inverse state is double-buffered: preconditioning
reads an *active* bank while the next bank (*pending*) is computed from the
ring stat window the step carried in.  On each bucket's phase tick —
exposed as ``GradientTransformation.precompute`` and run at the top of the
train step, before gradients exist — the pending bank is promoted to
active and the next pending launch is chained onto it.  The launch has no
data dependency on the current step, so XLA can overlap the inversion work
with the forward/backward and the gradient collectives; active factors lag
the synchronous schedule by exactly one ``inv_freq`` window (the bounded
staleness).  ``staleness=0`` (default) is the synchronous path above,
bit-identical state tree included.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import scopes
from repro.core import stats as statlib
from repro.core.firstorder import GradientTransformation
from repro.sharding import collectives


@dataclass(frozen=True)
class MKORConfig:
    gamma: float = 0.9                 # factor momentum (Eqs. 3-6)
    inv_freq: int = 10                 # update factors every f steps
    stabilizer_threshold: float = 50.0  # ε: ‖F⁻¹‖∞ trigger (lines 5-6)
    zeta: float = 0.95                 # blend-toward-identity strength
    factor_dtype: str = "bfloat16"     # paper: half precision
    # Quantized factor residency (DESIGN.md §16): "none" stores banks,
    # pending banks, and stat windows at ``factor_dtype`` (the shipped
    # bf16 default — bit-identical legacy state tree); "bf16" forces
    # bfloat16 regardless of factor_dtype; "int8" stores per-slice
    # symmetric int8 codes + fp32 scales, with fp32 error-feedback
    # accumulators in the optimizer state (single-process requant folds
    # the residual back in; under ``dist`` the wire quantization is the
    # storage quantization and the accumulators stay zero so state stays
    # replicated).  Dequant is fused into the Pallas SMW / block-SMW /
    # precondition kernels — no separate cast pass materializes fp32
    # banks in HBM — and the phase-step owner-gather ships int8 codes +
    # scales: ~2x fewer wire bytes than bf16.  int8 requires the bank
    # layout (the per-layer oracle stays the plain reference).
    factor_quant: str = "none"         # "none" | "bf16" | "int8"
    max_factor_dim: int = 32768        # skip layers with huge factor dims
    min_factor_dim: int = 4
    rescale: bool = True               # line 10 gradient rescaling
    exclude: Tuple[str, ...] = ("embed", "lm_head")
    variant: str = "paper"             # "paper" | "exact_smw"
    # Block rank-r updates (paper §4, DESIGN.md §11): buffer the last
    # ``rank`` per-step stat vectors per factor in a ring window
    # (core/stats.py window_push) and consume the WHOLE window with one
    # block-Woodbury update on the factor's phase step — O(r·d²+r³) in a
    # single dispatch instead of r chained rank-1 dispatches.  rank=1 is
    # bit-identical to the original per-step rank-1 schedule (no window
    # state is allocated).
    rank: int = 1
    use_pallas: bool = False           # fused TPU kernels (kernels/)
    interpret: bool = False            # pallas interpret mode (CPU tests)
    layout: str = "bank"               # "bank" (bucketed) | "per_layer"
    # Staggered inversion schedule (DESIGN.md §9): bucket b inverts on steps
    # where count % inv_freq == phase[b] (static round-robin), spreading the
    # SMW work across the window instead of spiking every inv_freq-th step.
    # stagger=False is the paper-exact global schedule (all phases 0).
    stagger: bool = True
    # Overlap-hidden inversions (DESIGN.md §13): staleness=1 double-buffers
    # the inverse state — preconditioning reads an *active* bank while the
    # next bank (the *pending* bank) is computed from the stat window the
    # step carried in (stats through t-1), so the inversion work has no
    # data dependency on the current step's forward/backward and can be
    # overlapped with the gradient collectives (the optimizer exposes the
    # tick as GradientTransformation.precompute; training/loop.py runs it
    # at the top of the step).  On each bucket's phase tick the pending
    # bank is promoted to active and the next pending is launched — the
    # active factors lag the synchronous schedule by exactly one inv_freq
    # window (the bounded staleness).  staleness=0 is the synchronous
    # path, bit-identical (state tree included) to the pre-async
    # optimizer.  staleness=1 allocates ring stat windows at every rank
    # (rank=1 gets a 1-row window holding the latest stat vectors).
    staleness: int = 0
    # Numerical-health sentinel (DESIGN.md §14): per-bucket detection +
    # quarantine + recovery, entirely in-graph.  Every step each bucket
    # derives health signals from already-replicated data (non-finite
    # counts in grads / stat vectors / ring windows / inverse banks, the
    # ‖F⁻¹‖∞ trend against the stabilizer threshold, the min Gauss-Jordan
    # pivot of the block mid-matrix solve, and rescale-denominator
    # collapse).  A tripped bucket resets its banks to identity — the
    # MKOR-H first-order passthrough, ΔW = I·G·I rescaled by exactly 1 —
    # zeroes its stat window, and skips SMW/inversion for
    # ``health_cooldown`` of its own phase steps before re-entering with
    # a fresh window.  Healthy buckets are untouched (all gates are
    # scalar ``where`` no-ops), and no signal crosses workers: under
    # ``dist`` every input to the sentinel is replicated post-collective
    # state, so trip decisions are bit-identical on all workers with zero
    # extra wire bytes (analysis `health-gating` lint proves it).  Bank
    # layout only — the per-layer oracle stays the plain reference.
    health: bool = False
    health_cooldown: int = 2           # K: phase steps quarantined per trip
    health_norm_factor: float = 4.0    # trip at factor·stabilizer_threshold
    health_pivot_tol: float = 1e-12    # min GJ pivot below this trips
    # Owner-sharded inversions (DESIGN.md §10): static dist spec
    # ((axis_name, axis_size), ...) of the data axes when the optimizer runs
    # inside shard_map (training/loop.py make_dist_train_step).  Each worker
    # then stabilizes+SMWs only its owned chunk of every bucket's bank dim
    # (core/stats.py bucket_owner_map) and the updated inverse slices are
    # all-gathered on that bucket's phase step.  None = single-program.
    # Only the bank layout shards; the per-layer oracle stays replicated.
    dist: Optional[Tuple[Tuple[str, int], ...]] = None
    # Elastic liveness mask (DESIGN.md §15): static per-worker bools, one
    # per dist worker.  Dead/demoted workers own zero inversion slices and
    # every bucket's bank dim is re-split over the survivors
    # (survivor-rank order, collectives.owner_shard/gather_shards).  The
    # mask changes WHO inverts a slice, never the state tree or the wire
    # bytes per step — failover is a recompile with a new mask plus
    # host-side quarantine of the orphaned buckets
    # (training/resilience.py).  None or all-True = the static schedule,
    # bit-identical program.
    live: Optional[Tuple[bool, ...]] = None
    # MKOR-H (§3.2)
    hybrid: bool = False
    hybrid_ema_fast: float = 0.9
    hybrid_ema_slow: float = 0.99
    hybrid_threshold: float = 0.02     # relative improvement-rate floor
    hybrid_min_steps: int = 50


# ----------------------------------------------------------------------- #
# Core math (single factor, single layer) — the O(d²) heart of the paper.
# ----------------------------------------------------------------------- #
def smw_rank1_update(j_inv: jnp.ndarray, v: jnp.ndarray, gamma: float,
                     variant: str = "paper") -> jnp.ndarray:
    """One rank-1 SM-based inverse update (paper Eq. 5/6). O(d²)."""
    dtype = j_inv.dtype
    u = (j_inv.astype(jnp.float32) @ v.astype(jnp.float32))
    s = jnp.dot(v.astype(jnp.float32), u)                 # ḡᵀ J⁻¹ ḡ  (fp32)
    if variant == "paper":
        coef = (1.0 - gamma) / (gamma ** 2 * (1.0 + gamma * (1.0 - gamma) * s))
        new = gamma * j_inv.astype(jnp.float32) + coef * jnp.outer(u, u)
    elif variant == "exact_smw":
        # (γJ + (1-γ)vvᵀ)⁻¹ = (1/γ)(J⁻¹ − (1−γ) uuᵀ / (γ + (1−γ)s))
        new = (j_inv.astype(jnp.float32)
               - (1.0 - gamma) * jnp.outer(u, u) / (gamma + (1.0 - gamma) * s)
               ) / gamma
    else:
        raise ValueError(variant)
    return new.astype(dtype)


def smw_update_maybe_rank_r(j_inv, v, gamma, variant):
    """v: (d,) rank-1, or (r, d) chained rank-r (paper §4, O(r·d²))."""
    if v.ndim == 1:
        return smw_rank1_update(j_inv, v, gamma, variant)
    for i in range(v.shape[0]):
        j_inv = smw_rank1_update(j_inv, v[i], gamma, variant)
    return j_inv


def block_weights(n_valid, rank: int, gamma: float):
    """Per-row sqrt-weights + base scale of the block rank-r update.

    Chaining m = min(n_valid, rank) rank-1 EMA updates composes to

        J_m = γ^m J_0 + Σ_{i<m} (1-γ) γ^(m-1-i) v_i v_iᵀ   (i=0 oldest)

    so the block update folds row i of the window by √w_i with
    w_i = (1-γ)γ^(m-1-i) and scales the base factor by γ^m.  Rows at or
    beyond ``n_valid`` (unwritten/stale ring slots) get weight zero, and
    n_valid = 0 makes the whole update an exact no-op (γ⁰ = 1, Ṽ = 0).
    ``n_valid`` may be traced (it is optimizer state)."""
    i = jnp.arange(rank, dtype=jnp.float32)
    m = jnp.minimum(jnp.asarray(n_valid, jnp.float32), float(rank))
    w = jnp.where(i < m, (1.0 - gamma) * gamma ** jnp.maximum(m - 1.0 - i,
                                                              0.0), 0.0)
    return jnp.sqrt(w), gamma ** m


def smw_block_update(j_inv: jnp.ndarray, v: jnp.ndarray, gamma: float,
                     variant: str = "paper",
                     n_valid=None, with_pivot: bool = False):
    """Block rank-r Woodbury inverse update (paper §4, DESIGN.md §11).

    v: (r, d) window rows, oldest first.  One O(r·d² + r³) shot instead of
    r sequential rank-1 dispatches:

      exact_smw:  (γ^m J + ṼᵀṼ)⁻¹
                  = (1/γ^m)(J⁻¹ − J⁻¹Ṽᵀ (γ^m I_r + ṼJ⁻¹Ṽᵀ)⁻¹ ṼJ⁻¹)
                  — EXACTLY equal to m chained rank-1 exact SMW updates
                  (Ṽ rows = √w_i v_i, see :func:`block_weights`);
      paper:      J⁻¹ ← γ^m J⁻¹ + J⁻¹Ṽᵀ (γ^{2m}(I_r + γ^m S))⁻¹ ṼJ⁻¹,
                  S = ṼJ⁻¹Ṽᵀ — the PD-preserving generalization of Eq. 5/6
                  (the middle matrix is PD whenever S is PSD, so Lemma 3.1
                  carries over); at r = 1 it reduces to Eq. 5/6 exactly.

    ``n_valid`` masks a partially-filled window (see block_weights);
    n_valid = 0 returns the factor bit-unchanged.

    ``with_pivot=True`` additionally returns the minimum Gauss-Jordan
    pivot of the (r, r) mid-matrix solve as an fp32 scalar — the health
    sentinel's conditioning signal (DESIGN.md §14).  For a PD mid matrix
    the GJ pivots are the squared Cholesky diagonal; a non-PD mid gives
    NaN, which the sentinel's ``pivot >= tol`` test treats as a trip.
    The fused Pallas kernel exports the matching signal straight from
    its in-register elimination (kernels/rank1_smw.py)."""
    r = v.shape[0]
    dtype = j_inv.dtype
    jf = j_inv.astype(jnp.float32)
    sq, gm = block_weights(r if n_valid is None else n_valid, r, gamma)
    vt = v.astype(jnp.float32) * sq[:, None]              # Ṽ rows (r, d)
    u = jnp.einsum("ij,rj->ri", jf, vt)                   # rows = J⁻¹ṽ_i
    s = vt @ u.T                                          # ṼJ⁻¹Ṽᵀ (r, r)
    eye = jnp.eye(r, dtype=jnp.float32)
    if variant == "paper":
        mid = gm ** 2 * eye + gm ** 3 * s
        new = gm * jf + u.T @ jnp.linalg.solve(mid, u)
    elif variant == "exact_smw":
        mid = gm * eye + s
        new = (jf - u.T @ jnp.linalg.solve(mid, u)) / gm
    else:
        raise ValueError(variant)
    if with_pivot:
        piv = jnp.min(jnp.square(jnp.diagonal(jnp.linalg.cholesky(mid))))
        return new.astype(dtype), piv
    return new.astype(dtype)


def stabilize(j_inv: jnp.ndarray, threshold: float, zeta: float) -> jnp.ndarray:
    """Norm-based stabilizer (lines 5-6 / Eqs. 7-8) + norm cap.

    The paper's Eq. 5 multiplies the dominant factor eigenvalue by up to
    γ + γ⁻³ (> 1 for every γ) when the rank-1 statistics are persistent, so
    the stabilizer is the *required* control loop, not an optional guard —
    and the ζ-blend alone only bounds the norm when ζ(γ+γ⁻³) < 1.  After
    the paper's blend-toward-identity we therefore also rescale back to the
    threshold norm.  Because line 10 rescales the preconditioned update to
    the raw gradient norm, a pure rescale of the factor is invisible to the
    update direction — it only prevents overflow (bf16-safe, Lemma 3.2).
    """
    jf = j_inv.astype(jnp.float32)
    norm = jnp.max(jnp.abs(jf))
    eye = jnp.eye(j_inv.shape[-1], dtype=jnp.float32)
    blended = zeta * jf + (1.0 - zeta) * eye          # Eqs. 7-8
    out = jnp.where(norm > threshold, blended, jf)
    n2 = jnp.max(jnp.abs(out))
    out = jnp.where(n2 > threshold,
                    out * (threshold / jnp.maximum(n2, 1e-30)), out)
    return out.astype(j_inv.dtype)


def precondition(l_inv: jnp.ndarray, r_inv: jnp.ndarray,
                 g_w: jnp.ndarray) -> jnp.ndarray:
    """ΔW = R⁻¹ G L⁻¹ for W (.., d_in, d_out); broadcasts over extra dims."""
    gw = g_w.astype(jnp.float32)
    out = jnp.einsum("ij,...jk->...ik", r_inv.astype(jnp.float32), gw)
    out = jnp.einsum("...ik,kl->...il", out, l_inv.astype(jnp.float32))
    return out


def rescale_update(delta: jnp.ndarray, g_w: jnp.ndarray) -> jnp.ndarray:
    """Line 10: match the raw gradient's Frobenius norm (per stacked layer
    slice — all dims except none here; caller vmaps over stack dims).

    The ε = 1e-30 guard on ‖ΔW‖ is the all-zero-slice escape: a zero
    gradient slice gives ΔW = R⁻¹·0·L⁻¹ = 0 and ‖G‖ = ‖ΔW‖ = 0, so the
    ratio degenerates to 0/0.  Clamping the denominator turns that into
    0 · (0/ε) = 0 — the update stays exactly zero instead of NaN.  The
    fused Pallas kernel uses the identical guard (kernels/precond.py
    RESCALE_EPS)."""
    gn = jnp.sqrt(jnp.sum(jnp.square(g_w.astype(jnp.float32))))
    dn = jnp.sqrt(jnp.sum(jnp.square(delta)))
    return delta * (gn / jnp.maximum(dn, 1e-30))


def _vmap_over_stack(fn, n_stack: int):
    for _ in range(n_stack):
        fn = jax.vmap(fn)
    return fn


# ----------------------------------------------------------------------- #
# Numerical-health sentinel primitives (DESIGN.md §14).  All pure scalar
# reductions of already-materialized data — no collectives, so under dist
# every worker derives the identical signals from its replicated copies.
# ----------------------------------------------------------------------- #
@scopes.scoped(scopes.MKOR_SMW)
def _any_nonfinite(arrays) -> jnp.ndarray:
    """Scalar bool: any non-finite element anywhere in ``arrays``."""
    bad = jnp.zeros((), jnp.bool_)
    for a in arrays:
        bad = bad | ~jnp.all(jnp.isfinite(a.astype(jnp.float32)))
    return bad


@scopes.scoped(scopes.MKOR_SMW)
def _finite_or_zero(x: jnp.ndarray) -> jnp.ndarray:
    """Replace non-finite elements with 0 (identity on clean data)."""
    return jnp.where(jnp.isfinite(x), x, jnp.zeros((), x.dtype))


def _slice_sumsq(x: jnp.ndarray) -> jnp.ndarray:
    """Per-layer-slice Σx² (reduces the trailing matrix dims, fp32)."""
    return jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(-2, -1))


@scopes.scoped(scopes.MKOR_SMW)
def _identity_like(bank: jnp.ndarray) -> jnp.ndarray:
    """Identity factors broadcast to a bank's shape — the quarantine
    reset value.  An identity bank preconditions to ΔW = I·G·I = G and
    rescales by ‖G‖/‖G‖ = 1: the exact MKOR-H first-order passthrough."""
    d = bank.shape[-1]
    return jnp.broadcast_to(jnp.eye(d, dtype=bank.dtype), bank.shape)


# ----------------------------------------------------------------------- #
# Quantized factor residency (factor_quant="int8", DESIGN.md §16).  A bank
# side is the triple (codes int8, scale fp32 per slice, error-feedback
# fp32) instead of a bare array; the quantized identity is 127·I codes at
# scale 1/127 — decode is a scalar multiple of I, so the first-order
# passthrough direction is exact and rescale restores the magnitude.
# ----------------------------------------------------------------------- #
_QUANT_ID_SCALE = 1.0 / statlib.INT8_QMAX


def _quant_identity_codes(bank_q: jnp.ndarray) -> jnp.ndarray:
    """int8 identity codes broadcast to a quantized bank's shape."""
    d = bank_q.shape[-1]
    eye = (jnp.eye(d, dtype=jnp.float32)
           * statlib.INT8_QMAX).astype(jnp.int8)
    return jnp.broadcast_to(eye, bank_q.shape)


def _quant_identity_side(shape: Tuple[int, ...], d: int):
    """Fresh quantized-identity bank side: (codes, scales, zero EF)."""
    eye = (jnp.eye(d, dtype=jnp.float32)
           * statlib.INT8_QMAX).astype(jnp.int8)
    return (jnp.broadcast_to(eye, shape + (d, d)),
            jnp.full(shape, _QUANT_ID_SCALE, jnp.float32),
            jnp.zeros(shape + (d, d), jnp.float32))


@scopes.scoped(scopes.MKOR_SMW)
def _quant_side_reset(side, trip):
    """Quarantine reset of a quantized side: identity codes + identity
    scale + ZERO error feedback — a stale residual from before the trip
    must never leak into the fresh post-cooldown factors (DESIGN.md §14
    x §16 interaction)."""
    q, sc, ef = side
    return (jnp.where(trip, _quant_identity_codes(q), q),
            jnp.where(trip, jnp.float32(_QUANT_ID_SCALE), sc),
            jnp.where(trip, jnp.zeros((), jnp.float32), ef))


def _quant_side_maxabs(side) -> jnp.ndarray:
    """max |decode| over a quantized bank — scale·max|codes| per slice,
    no dequantized materialization (the health sentinel's norm signal)."""
    q, sc, _ = side
    per = jnp.max(jnp.abs(q.astype(jnp.float32)), axis=(-2, -1))
    return jnp.max(sc * per)


# ----------------------------------------------------------------------- #
# The optimizer
# ----------------------------------------------------------------------- #
def _eligible(path, dense, cfg: MKORConfig) -> bool:
    _, _, d_in, d_out = statlib.layer_dims(dense)
    if any(str(p) in cfg.exclude for p in path):
        return False
    lo, hi = cfg.min_factor_dim, cfg.max_factor_dim
    return lo <= d_in <= hi and lo <= d_out <= hi


def _init_factors(dense, cfg: MKORConfig):
    stack, _, d_in, d_out = statlib.layer_dims(dense)
    fd = jnp.dtype(statlib.factor_storage_dtype(cfg.factor_dtype,
                                                cfg.factor_quant))
    eye = lambda d: jnp.broadcast_to(jnp.eye(d, dtype=fd), stack + (d, d))
    return {"l_inv": eye(d_out), "r_inv": eye(d_in)}


def _hybrid_init() -> Dict:
    return {
        "on": jnp.ones((), jnp.bool_),
        "ema_fast": jnp.zeros((), jnp.float32),
        "ema_slow": jnp.zeros((), jnp.float32),
    }


def _hybrid_update(h: Dict, loss, count, cfg: MKORConfig) -> Dict:
    """MKOR-H (§3.2): sticky switch to first-order when the relative
    loss-improvement rate stalls."""
    loss = loss.astype(jnp.float32)
    first = count == 0
    fast = jnp.where(first, loss,
                     cfg.hybrid_ema_fast * h["ema_fast"]
                     + (1 - cfg.hybrid_ema_fast) * loss)
    slow = jnp.where(first, loss,
                     cfg.hybrid_ema_slow * h["ema_slow"]
                     + (1 - cfg.hybrid_ema_slow) * loss)
    rate = (slow - fast) / jnp.maximum(jnp.abs(slow), 1e-12)
    stalled = (count > cfg.hybrid_min_steps) & (rate < cfg.hybrid_threshold)
    return {"on": h["on"] & ~stalled, "ema_fast": fast, "ema_slow": slow}


def manifest_for(tree, cfg: MKORConfig) -> statlib.BucketManifest:
    return statlib.build_bucket_manifest(
        tree, lambda path, dense: _eligible(path, dense, cfg))


def factor_slices(state, tree, cfg: MKORConfig = MKORConfig()):
    """Per-layer ``{path_str: {"l_inv", "r_inv"}}`` views of the factor
    state, regardless of layout.  Bank slices are lazy gathers — intended
    for tests, checkpoints-in-flight inspection, and debugging."""
    if "factors" in state:                          # layout="per_layer"
        return dict(state["factors"])
    out = {}
    for bucket in manifest_for(tree, cfg):
        bank = state["factor_banks"][bucket.bucket_id]
        for i, key in enumerate(bucket.path_strs):
            if "l_scale" in bank:                   # int8: fp32 views
                out[key] = {
                    "l_inv": statlib.quant_decode(bank["l_inv"][i],
                                                  bank["l_scale"][i]),
                    "r_inv": statlib.quant_decode(bank["r_inv"][i],
                                                  bank["r_scale"][i])}
            else:
                out[key] = {"l_inv": bank["l_inv"][i],
                            "r_inv": bank["r_inv"][i]}
    return out


def mkor(backend: GradientTransformation,
         cfg: MKORConfig = MKORConfig()) -> GradientTransformation:
    """MKOR wrapping a first-order ``backend`` (Alg. 1)."""

    if cfg.layout not in ("bank", "per_layer"):
        raise ValueError(f"unknown layout {cfg.layout!r}")
    if cfg.rank < 1:
        raise ValueError(f"rank must be >= 1, got {cfg.rank}")
    if cfg.staleness not in (0, 1):
        raise ValueError(
            f"staleness must be 0 (synchronous) or 1 (double-buffered "
            f"async, DESIGN.md §13), got {cfg.staleness}")
    if cfg.health and cfg.layout != "bank":
        raise ValueError(
            "health=True requires layout='bank': the sentinel state "
            "machine is per-bucket (DESIGN.md §14); the per-layer "
            "oracle stays the plain numerical reference")
    if cfg.health and cfg.health_cooldown < 1:
        raise ValueError(
            f"health_cooldown must be >= 1, got {cfg.health_cooldown}")
    if cfg.factor_quant not in statlib.FACTOR_QUANT_MODES:
        raise ValueError(
            f"factor_quant must be one of {statlib.FACTOR_QUANT_MODES}, "
            f"got {cfg.factor_quant!r}")
    if cfg.factor_quant == "int8" and cfg.layout != "bank":
        raise ValueError(
            "factor_quant='int8' requires layout='bank': the scale / "
            "error-feedback state machine is per-bucket (DESIGN.md §16); "
            "the per-layer oracle stays the plain numerical reference")
    # rank=1 async still rides the block-Woodbury path (1-row window);
    # staleness=0 keeps the legacy rank-1 state tree bit-identical
    needs_window = cfg.rank > 1 or cfg.staleness > 0
    win_rank = max(cfg.rank, 1)

    # Each stage runs under its named scope (repro/scopes.py), set on the
    # closures every path shares (bank, per-layer, async, int8): stabilize,
    # the factor updates and the health signals under mkor_smw, the
    # precondition under mkor_precondition.
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        smw_fn = scopes.scoped(scopes.MKOR_SMW)(partial(
            kops.smw_rank1_update, gamma=cfg.gamma, variant=cfg.variant,
            interpret=cfg.interpret))

        @scopes.scoped(scopes.MKOR_SMW)
        def banked_smw(j, v, n_lead):
            return kops.smw_rank1_update_banked(
                j, v, gamma=cfg.gamma, variant=cfg.variant,
                interpret=cfg.interpret)

        @scopes.scoped(scopes.MKOR_SMW)
        def block_slice(j, v, n):
            return kops.smw_block_update(
                j, v, gamma=cfg.gamma, variant=cfg.variant, n_valid=n,
                interpret=cfg.interpret)

        @scopes.scoped(scopes.MKOR_SMW)
        def banked_block(j, v, n, n_lead):
            return kops.smw_block_update_banked(
                j, v, n, gamma=cfg.gamma, variant=cfg.variant,
                interpret=cfg.interpret)

        @scopes.scoped(scopes.MKOR_SMW)
        def banked_block_piv(j, v, n, n_lead):
            # (new bank, min GJ pivot) — the pivot comes straight from
            # the fused kernel's in-register elimination
            return kops.smw_block_update_banked(
                j, v, n, gamma=cfg.gamma, variant=cfg.variant,
                interpret=cfg.interpret, with_pivot=True)

        @scopes.scoped(scopes.MKOR_PRECONDITION)
        def precond_slice(linv, rinv, gw):
            # fused precondition + Frobenius rescale, one dispatch per
            # slice (kernels/precond.py; extra dims / VMEM overflow fall
            # back to the two-matmul path inside)
            delta = kops.fused_precondition(linv, rinv, gw,
                                            rescale=cfg.rescale,
                                            interpret=cfg.interpret)
            return delta.astype(gw.dtype)

        @scopes.scoped(scopes.MKOR_PRECONDITION)
        def banked_precond(l, r, gw, n_lead):
            delta = kops.fused_precondition_banked(
                l, r, gw, rescale=cfg.rescale, interpret=cfg.interpret)
            return delta.astype(gw.dtype)
    else:
        smw_fn = scopes.scoped(scopes.MKOR_SMW)(partial(
            smw_update_maybe_rank_r, gamma=cfg.gamma, variant=cfg.variant))

        @scopes.scoped(scopes.MKOR_SMW)
        def banked_smw(j, v, n_lead):
            return _vmap_over_stack(smw_fn, n_lead)(j, v)

        @scopes.scoped(scopes.MKOR_SMW)
        def block_slice(j, v, n):
            return smw_block_update(j, v, cfg.gamma, cfg.variant, n_valid=n)

        @scopes.scoped(scopes.MKOR_SMW)
        def banked_block(j, v, n, n_lead):
            return _vmap_over_stack(block_slice, n_lead)(j, v, n)

        @scopes.scoped(scopes.MKOR_SMW)
        def banked_block_piv(j, v, n, n_lead):
            out, piv = _vmap_over_stack(
                lambda jj, vv, nn: smw_block_update(
                    jj, vv, cfg.gamma, cfg.variant, n_valid=nn,
                    with_pivot=True), n_lead)(j, v, n)
            return out, jnp.min(piv)

        @scopes.scoped(scopes.MKOR_PRECONDITION)
        def precond_slice(linv, rinv, gw):
            delta = precondition(linv, rinv, gw)
            if cfg.rescale:
                delta = rescale_update(delta, gw)
            return delta.astype(gw.dtype)

        @scopes.scoped(scopes.MKOR_PRECONDITION)
        def banked_precond(l, r, gw, n_lead):
            return _vmap_over_stack(precond_slice, n_lead)(l, r, gw)

    stab_slice = scopes.scoped(scopes.MKOR_SMW)(partial(
        stabilize, threshold=cfg.stabilizer_threshold, zeta=cfg.zeta))

    @scopes.scoped(scopes.MKOR_SMW)
    def norm_hot(bank):
        # ‖F⁻¹‖∞ trend signal (DESIGN.md §14): the stabilizer caps the
        # norm AT the threshold every inversion, so a bank sitting well
        # above factor·threshold can only mean corrupted carried state.
        return jnp.max(jnp.abs(bank.astype(jnp.float32))) \
            > cfg.health_norm_factor * cfg.stabilizer_threshold

    # ------------------------------------------------------------------ #
    # Quantized factor residency (factor_quant="int8", DESIGN.md §16).
    # A bank side is the triple (codes int8, scale fp32, error-feedback
    # fp32).  The schedule per inversion is update → stabilize → requant:
    # the kernels consume the codes directly (fused dequant — no fp32
    # bank copy in HBM) and the stabilizer caps the fp32 transient BEFORE
    # requantization, so the stored norm — and with it the quant scale,
    # hence the absolute quantization error scale/2 — stays bounded by
    # the stabilizer threshold.  Single-process requant folds the
    # residual into the EF accumulator; under dist each owner quantizes
    # its freshly inverted chunk at the wire boundary (quant_encode, no
    # EF) and the gathered codes ARE the stored codes, keeping the state
    # tree replicated and the EF leaves zero on every worker.
    # ------------------------------------------------------------------ #
    quant8 = cfg.factor_quant == "int8"
    store_dtype = jnp.dtype(statlib.factor_storage_dtype(
        cfg.factor_dtype, cfg.factor_quant))
    win_dtype = jnp.float32 if cfg.factor_quant == "none" else store_dtype
    dist_on = cfg.dist is not None and collectives.world_size(cfg.dist) > 1
    hot_norm = cfg.health_norm_factor * cfg.stabilizer_threshold

    if quant8:
        def side_take(side, idx):
            return tuple(a[idx] for a in side)

        def side_set(side, idx, sub):
            return tuple(a.at[idx].set(b) for a, b in zip(side, sub))

        def pack_sides(l_side, r_side):
            return {"l_inv": l_side[0], "l_scale": l_side[1],
                    "l_ef": l_side[2], "r_inv": r_side[0],
                    "r_scale": r_side[1], "r_ef": r_side[2]}

        def unpack_sides(bank):
            return ((bank["l_inv"], bank["l_scale"], bank["l_ef"]),
                    (bank["r_inv"], bank["r_scale"], bank["r_ef"]))

        @scopes.scoped(scopes.MKOR_SMW)
        def side_rank1(side, v, ns1):
            """stab∘SMW on one quantized side (rank-1 schedule)."""
            q, sc, ef = side
            if not dist_on:
                if cfg.use_pallas:
                    f = kops.smw_rank1_update_banked(
                        q, v, gamma=cfg.gamma, variant=cfg.variant,
                        interpret=cfg.interpret, scale=sc)
                else:
                    f = banked_smw(statlib.quant_decode(q, sc), v, ns1)
                f = _vmap_over_stack(stab_slice, ns1)(f)
                return statlib.quant_requantize(f, ef)
            n = 1
            for dd in q.shape[:ns1]:
                n *= dd

            def chunk_fn(qc, scc, vc):
                if cfg.use_pallas:
                    fc = kops.smw_rank1_update_banked(
                        qc, vc, gamma=cfg.gamma, variant=cfg.variant,
                        interpret=cfg.interpret, scale=scc)
                else:
                    fc = banked_smw(statlib.quant_decode(qc, scc), vc, 1)
                fc = _vmap_over_stack(stab_slice, 1)(fc)
                return statlib.quant_encode(fc)   # wire quant == storage

            qg, scg = collectives.owner_sharded_map_quant(
                chunk_fn,
                (q.reshape((n,) + q.shape[ns1:]), sc.reshape((n,)),
                 v.reshape((n,) + v.shape[ns1:])),
                cfg.dist, n, cfg.live)
            return (qg.reshape(q.shape), scg.reshape(sc.shape), ef)

        @scopes.scoped(scopes.MKOR_SMW)
        def side_block(side, v_ord, cnt_full, ns1, want_pivot):
            """Block-Woodbury + stab + requant on one quantized side.
            Returns (new side, min GJ pivot); pivot is +inf when the
            path exports none (dist — DESIGN.md §14's post checks catch
            a singular solve after the gather instead)."""
            q, sc, ef = side
            piv = jnp.float32(jnp.inf)
            if not dist_on:
                if cfg.use_pallas:
                    res = kops.smw_block_update_banked(
                        q, v_ord, cnt_full, gamma=cfg.gamma,
                        variant=cfg.variant, interpret=cfg.interpret,
                        with_pivot=want_pivot, scale=sc)
                    f, piv = res if want_pivot else (res, piv)
                else:
                    jd = statlib.quant_decode(q, sc)
                    if want_pivot:
                        f, piv = banked_block_piv(jd, v_ord, cnt_full, ns1)
                    else:
                        f = banked_block(jd, v_ord, cnt_full, ns1)
                f = _vmap_over_stack(stab_slice, ns1)(f)
                return statlib.quant_requantize(f, ef), piv
            n = 1
            for dd in q.shape[:ns1]:
                n *= dd

            def chunk_fn(qc, scc, vc, cc):
                if cfg.use_pallas:
                    fc = kops.smw_block_update_banked(
                        qc, vc, cc, gamma=cfg.gamma, variant=cfg.variant,
                        interpret=cfg.interpret, scale=scc)
                else:
                    fc = banked_block(statlib.quant_decode(qc, scc),
                                      vc, cc, 1)
                fc = _vmap_over_stack(stab_slice, 1)(fc)
                return statlib.quant_encode(fc)

            qg, scg = collectives.owner_sharded_map_quant(
                chunk_fn,
                (q.reshape((n,) + q.shape[ns1:]), sc.reshape((n,)),
                 v_ord.reshape((n,) + v_ord.shape[ns1:]),
                 cnt_full.reshape((n,))),
                cfg.dist, n, cfg.live)
            return (qg.reshape(q.shape), scg.reshape(sc.shape), ef), piv

        @scopes.scoped(scopes.MKOR_PRECONDITION)
        def side_precond(l_side, r_side, gw, ns1):
            lq, lsc, _ = l_side
            rq, rsc, _ = r_side
            if cfg.use_pallas:
                # fused dequant at the factor load sites — the int8
                # banks feed the kernel directly (kernels/precond.py)
                delta = kops.fused_precondition_banked(
                    lq, rq, gw, rescale=cfg.rescale,
                    interpret=cfg.interpret, l_scale=lsc, r_scale=rsc)
                return delta.astype(gw.dtype)
            return banked_precond(statlib.quant_decode(lq, lsc),
                                  statlib.quant_decode(rq, rsc), gw, ns1)

        def side_finite_srcs(side):
            # codes are integers (always finite): the sentinel checks
            # the fp32 scale + error-feedback leaves instead
            return [side[1], side[2]]

        @scopes.scoped(scopes.MKOR_SMW)
        def sides_bad(l_side, r_side):
            return (_any_nonfinite(side_finite_srcs(l_side)
                                   + side_finite_srcs(r_side))
                    | (_quant_side_maxabs(l_side) > hot_norm)
                    | (_quant_side_maxabs(r_side) > hot_norm))

    @scopes.scoped(scopes.MKOR_PRECONDITION)
    def put_deltas(out, bucket, delta, gw, so_on):
        """Write a bucket's preconditioned slices into the update tree;
        the raw gradients where MKOR-H has switched MKOR off."""
        delta = jnp.where(so_on, delta, gw)
        for i, path in enumerate(bucket.paths):
            out = statlib.tree_set(
                out, path, {**statlib.tree_get(out, path), "w": delta[i]})
        return out

    # ------------------------------------------------------------------ #
    # init
    # ------------------------------------------------------------------ #
    def init_factor_state(params):
        # rank > 1 (or staleness >= 1): fp32 ring windows of the last
        # `win_rank` stat vectors per factor plus a per-slot write count
        # (DESIGN.md §11/§13).  At rank=1 staleness=0 no window state is
        # allocated — the state tree is bit-identical to the original
        # rank-1 optimizer (checkpoint compatible).  staleness >= 1 adds
        # the pending inverse banks (the double buffer) initialized equal
        # to the active banks (identity).
        def window(lead, d):
            # windows ride the factor storage dtype ("none" keeps the
            # legacy fp32 rings bit-identical); int8 windows carry
            # per-row scales and are built in the banked branch below
            return jnp.zeros(lead + (win_rank, d), win_dtype)

        if cfg.layout == "per_layer":
            factors, windows = {}, {}
            for path in statlib.iter_dense_layers(params):
                dense = statlib.tree_get(params, path)
                if _eligible(path, dense, cfg):
                    key = statlib.path_str(path)
                    factors[key] = _init_factors(dense, cfg)
                    if needs_window:
                        stack, _, d_in, d_out = statlib.layer_dims(dense)
                        windows[key] = {"a": window(stack, d_in),
                                        "g": window(stack, d_out),
                                        "n": jnp.zeros((), jnp.int32)}
            out = {"factors": factors}
            if needs_window:
                out["stat_windows"] = windows
            if cfg.staleness:
                # distinct buffers, not views of the active factors: the
                # chunk runner donates the whole opt_state, and XLA
                # rejects the same buffer donated twice
                out["pending_factors"] = jax.tree.map(
                    jnp.array, factors)
            return out
        fd = store_dtype
        banks, windows = {}, {}
        for b in manifest_for(params, cfg):
            shape = (b.n_slots,) + b.stack

            def eye(d):
                return jnp.broadcast_to(jnp.eye(d, dtype=fd),
                                        shape + (d, d))

            if quant8:
                # int8 residency (DESIGN.md §16): codes + per-slice fp32
                # scale + fp32 error-feedback accumulator per side.  The
                # identity encodes exactly (codes 127·I at scale 1/127)
                # and EF starts — and under dist, stays — zero.
                lq, lsc, lef = _quant_identity_side(shape, b.d_out)
                rq, rsc, ref_ = _quant_identity_side(shape, b.d_in)
                banks[b.bucket_id] = {"l_inv": lq, "l_scale": lsc,
                                      "l_ef": lef, "r_inv": rq,
                                      "r_scale": rsc, "r_ef": ref_}
            else:
                banks[b.bucket_id] = {"l_inv": eye(b.d_out),
                                      "r_inv": eye(b.d_in)}
            if needs_window:
                if quant8:
                    # per-ROW scales: each push re-encodes only the new
                    # row, so window quantization is exact (no EF)
                    windows[b.bucket_id] = {
                        "a": jnp.zeros(shape + (win_rank, b.d_in),
                                       jnp.int8),
                        "a_scale": jnp.zeros(shape + (win_rank,),
                                             jnp.float32),
                        "g": jnp.zeros(shape + (win_rank, b.d_out),
                                       jnp.int8),
                        "g_scale": jnp.zeros(shape + (win_rank,),
                                             jnp.float32),
                        "n": jnp.zeros((b.n_slots,), jnp.int32)}
                else:
                    windows[b.bucket_id] = {
                        "a": window(shape, b.d_in),
                        "g": window(shape, b.d_out),
                        "n": jnp.zeros((b.n_slots,), jnp.int32)}
        out = {"factor_banks": banks}
        if needs_window:
            out["stat_windows"] = windows
        if cfg.staleness:
            # distinct buffers (see the per-layer branch above)
            out["pending_banks"] = jax.tree.map(jnp.array, banks)
        if cfg.health:
            # 8 bytes/bucket (stats.bucket_cost health_state_bytes):
            # phase-steps of quarantine left + lifetime trip counter
            out["health"] = {
                b.bucket_id: {"cooldown": jnp.zeros((), jnp.int32),
                              "trips": jnp.zeros((), jnp.int32)}
                for b in manifest_for(params, cfg)}
        return out

    def init(params):
        return {
            "count": jnp.zeros((), jnp.int32),
            **init_factor_state(params),
            "hybrid": _hybrid_init(),
            "backend": backend.init(params),
        }

    # ------------------------------------------------------------------ #
    # per-layer update (legacy layout — the bank path's numerical oracle)
    # ------------------------------------------------------------------ #
    def update_per_layer(grads, state, params, stats, do_inv_fn, so_on):
        layer_paths = {statlib.path_str(p): p
                       for p in statlib.iter_dense_layers(grads)}
        phases = statlib.layer_phases(
            manifest_for(params if params is not None else grads, cfg),
            cfg.inv_freq, cfg.stagger)
        new_factors = {}
        new_windows = {}
        out = grads
        for key, fac in state["factors"].items():
            path = layer_paths[key]
            g_w = statlib.tree_get(grads, path)["w"]
            a_vec = statlib.get_a_vec(stats, path) if stats is not None \
                else None
            g_vec = statlib.get_g_vec(grads, path)
            stack, _, _, _ = statlib.layer_dims(
                statlib.tree_get(params if params is not None else grads,
                                 path))
            ns = len(stack)

            l_inv, r_inv = fac["l_inv"], fac["r_inv"]

            # --- lines 5-8: stabilize + SM factor update, on this layer's
            # scheduled steps only.  lax.cond (not where) so non-inverting
            # steps skip the SMW work entirely — the staggered schedule
            # (DESIGN.md §9) relies on the skip for its flat step time. ----
            if cfg.rank > 1:
                # Rank-r window schedule (DESIGN.md §11): every step pushes
                # the current stat vectors into the ring window; the phase
                # step consumes the whole window with one block-Woodbury
                # update and resets the write count.  The push precedes the
                # consume so the phase step's own stats are included —
                # exactly the rank-1 schedule at rank=1.
                win = state["stat_windows"][key]
                a_win, g_win, n_cnt = win["a"], win["g"], win["n"]
                if a_vec is not None and g_vec is not None:
                    a_win = statlib.window_push(a_win, n_cnt, a_vec)
                    g_win = statlib.window_push(g_win, n_cnt, g_vec)
                    n_cnt = n_cnt + 1
                    do_inv = do_inv_fn(phases.get(key, 0))

                    # A layer with NO stats this step never reaches this
                    # branch (same skip as the rank-1 path), so cnt >= 1
                    # here; a whole window of absent stats therefore leaves
                    # the factor bit-untouched — the zero-window no-op.
                    def inv_branch(l, r, aw=a_win, gw=g_win, cnt=n_cnt,
                                   ns=ns, stack=stack):
                        stab = _vmap_over_stack(stab_slice, ns)
                        upd = _vmap_over_stack(block_slice, ns)
                        cnt_s = jnp.broadcast_to(cnt, stack)
                        l_new = upd(stab(l), statlib.window_ordered(gw, cnt),
                                    cnt_s)
                        r_new = upd(stab(r), statlib.window_ordered(aw, cnt),
                                    cnt_s)
                        return l_new, r_new

                    l_inv, r_inv = jax.lax.cond(
                        do_inv, inv_branch, lambda l, r: (l, r),
                        l_inv, r_inv)
                    n_cnt = jnp.where(do_inv, 0, n_cnt)
                new_windows[key] = {"a": a_win, "g": g_win, "n": n_cnt}
            elif a_vec is not None and g_vec is not None:
                def inv_branch(l, r, gv=g_vec, av=a_vec, ns=ns):
                    stab = _vmap_over_stack(stab_slice, ns)
                    upd = _vmap_over_stack(smw_fn, ns)
                    return upd(stab(l), gv), upd(stab(r), av)

                l_inv, r_inv = jax.lax.cond(
                    do_inv_fn(phases.get(key, 0)), inv_branch,
                    lambda l, r: (l, r), l_inv, r_inv)
            new_factors[key] = {"l_inv": l_inv, "r_inv": r_inv}

            # --- line 9-10: precondition + rescale ----------------------- #
            delta = _vmap_over_stack(precond_slice, ns)(l_inv, r_inv, g_w)
            delta = jnp.where(so_on, delta, g_w)      # MKOR-H fallback
            out = statlib.tree_set(
                out, path, {**statlib.tree_get(out, path), "w": delta})
        fstate = {"factors": new_factors}
        if cfg.rank > 1:
            fstate["stat_windows"] = new_windows
        return out, fstate

    # ------------------------------------------------------------------ #
    # bucketed bank update: one vmapped stabilize → SMW → precondition →
    # rescale pipeline per bucket (DESIGN.md §2)
    # ------------------------------------------------------------------ #
    def update_banked(grads, state, params, stats, do_inv_fn, so_on):
        manifest = manifest_for(params if params is not None else grads,
                                 cfg)
        phases = statlib.bucket_phases(manifest, cfg.inv_freq, cfg.stagger)
        new_banks = {}
        new_windows = {}
        new_health = {}
        out = grads
        for bucket in manifest:
            bank = state["factor_banks"][bucket.bucket_id]
            l_bank, r_bank = bank["l_inv"], bank["r_inv"]
            if quant8:
                l_side, r_side = unpack_sides(bank)
            do_inv = do_inv_fn(phases[bucket.bucket_id])
            ns = len(bucket.stack)
            if cfg.rank > 1:
                win = state["stat_windows"][bucket.bucket_id]
                a_win, g_win, n_cnt = win["a"], win["g"], win["n"]
                if quant8:
                    a_wsc, g_wsc = win["a_scale"], win["g_scale"]

            g_ws, g_vecs, a_vecs = [], [], []
            for path in bucket.paths:
                g_ws.append(statlib.tree_get(grads, path)["w"])
                g_vecs.append(statlib.get_g_vec(grads, path))
                a_vecs.append(statlib.get_a_vec(stats, path)
                              if stats is not None else None)

            # --- health sentinel, detect phase (DESIGN.md §14): derive
            # this bucket's pre-inversion signals from replicated data
            # only (post-collective grads/stats + carried state), so
            # under dist every worker trips identically with zero wire
            # bytes.  A quarantined bucket (cooling down or already
            # dirty) skips the SMW/inversion work entirely. ------------- #
            piv_min = jnp.float32(jnp.inf)
            if cfg.health:
                hst = state["health"][bucket.bucket_id]
                cool, trips = hst["cooldown"], hst["trips"]
                phase_hit = do_inv            # pre-gating: cooldown clock
                if quant8:
                    # int8 codes are always finite — the sentinel watches
                    # the fp32 scale/EF leaves and the decoded-norm proxy
                    # scale·max|codes| instead (no dequant materialized)
                    srcs = side_finite_srcs(l_side) \
                        + side_finite_srcs(r_side) + g_ws \
                        + [v for v in g_vecs + a_vecs if v is not None]
                    if cfg.rank > 1:
                        srcs += [a_wsc, g_wsc]
                    pre_bad = _any_nonfinite(srcs) \
                        | sides_bad(l_side, r_side)
                else:
                    srcs = [l_bank, r_bank] + g_ws \
                        + [v for v in g_vecs + a_vecs if v is not None]
                    if cfg.rank > 1:
                        srcs += [a_win, g_win]
                    pre_bad = (_any_nonfinite(srcs)
                               | norm_hot(l_bank) | norm_hot(r_bank))
                do_inv = do_inv & (cool == 0) & ~pre_bad

            # --- lines 5-8, banked.  Slots are sub-grouped by the runtime
            # stat signature (rank-r stats may differ per layer); in the
            # common case one group covers the whole bank. ---------------- #
            sig_groups: Dict[Any, list] = {}
            for slot, (av, gv) in enumerate(zip(a_vecs, g_vecs)):
                if av is None or gv is None:
                    continue                      # no stats: slot untouched
                sig_groups.setdefault((av.shape, gv.shape),
                                      []).append(slot)
            for sig in sorted(sig_groups, key=str):
                slots = sig_groups[sig]
                whole = len(slots) == bucket.n_slots
                idx = jnp.asarray(slots)
                if quant8:
                    l_sub_s = l_side if whole else side_take(l_side, idx)
                    r_sub_s = r_side if whole else side_take(r_side, idx)
                else:
                    l_sub = l_bank if whole else l_bank[idx]
                    r_sub = r_bank if whole else r_bank[idx]
                gv = jnp.stack([g_vecs[i] for i in slots])
                av = jnp.stack([a_vecs[i] for i in slots])
                if cfg.health:
                    # poisoned stat vectors must not enter the carried
                    # windows/factors: the trip already fired via
                    # pre_bad, the zeroed rows keep the state clean
                    gv = _finite_or_zero(gv)
                    av = _finite_or_zero(av)

                if cfg.rank > 1:
                    # Rank-r window schedule, banked (DESIGN.md §11):
                    # push this step's vectors into the ring windows of the
                    # group's slots (O(r·d) selects, every step), then on
                    # the bucket's phase step consume each slot's whole
                    # window with ONE block-Woodbury dispatch and reset the
                    # per-slot write counts.  Slots with no stats are not
                    # in any sig group, so window, count, and factors stay
                    # untouched — the rank-1 no-op contract; inside the
                    # branch cnt >= 1 always (the push precedes it).
                    aw = a_win if whole else a_win[idx]
                    gw = g_win if whole else g_win[idx]
                    cnt = n_cnt if whole else n_cnt[idx]
                    cnt_b = cnt.reshape(cnt.shape + (1,) * ns)
                    if quant8:
                        # per-row scales: only the new row is (exactly)
                        # re-encoded, the stored rows never requantize
                        awsc = a_wsc if whole else a_wsc[idx]
                        gwsc = g_wsc if whole else g_wsc[idx]
                        aw, awsc = statlib.window_push_quant(
                            aw, awsc, cnt_b, av)
                        gw, gwsc = statlib.window_push_quant(
                            gw, gwsc, cnt_b, gv)
                    else:
                        aw = statlib.window_push(aw, cnt_b, av)
                        gw = statlib.window_push(gw, cnt_b, gv)
                    cnt = cnt + 1

                    if quant8:
                        want_piv = bool(cfg.health) and not dist_on

                        def inv_branch_q(ls, rs, aw=aw, awsc=awsc, gw=gw,
                                         gwsc=gwsc, cnt=cnt, ns=ns):
                            cnt_full = jnp.broadcast_to(
                                cnt.reshape(cnt.shape + (1,) * ns),
                                ls[0].shape[:ns + 1])
                            g_ord = statlib.window_ordered(
                                statlib.window_decode(gw, gwsc), cnt_full)
                            a_ord = statlib.window_ordered(
                                statlib.window_decode(aw, awsc), cnt_full)
                            nl, pl = side_block(ls, g_ord, cnt_full,
                                                ns + 1, want_piv)
                            nr, pr = side_block(rs, a_ord, cnt_full,
                                                ns + 1, want_piv)
                            return nl, nr, jnp.minimum(pl, pr)

                        l_new_s, r_new_s, piv = jax.lax.cond(
                            do_inv, inv_branch_q,
                            lambda ls, rs: (ls, rs, jnp.float32(jnp.inf)),
                            l_sub_s, r_sub_s)
                        if cfg.health:
                            piv_min = jnp.minimum(piv_min, piv)
                        cnt = jnp.where(do_inv, 0, cnt)
                        if whole:
                            l_side, r_side = l_new_s, r_new_s
                            a_win, g_win, n_cnt = aw, gw, cnt
                            a_wsc, g_wsc = awsc, gwsc
                        else:
                            l_side = side_set(l_side, idx, l_new_s)
                            r_side = side_set(r_side, idx, r_new_s)
                            a_win = a_win.at[idx].set(aw)
                            g_win = g_win.at[idx].set(gw)
                            a_wsc = a_wsc.at[idx].set(awsc)
                            g_wsc = g_wsc.at[idx].set(gwsc)
                            n_cnt = n_cnt.at[idx].set(cnt)
                        continue

                    def inv_branch(l, r, aw=aw, gw=gw, cnt=cnt, ns=ns):
                        stab = _vmap_over_stack(stab_slice, ns + 1)
                        cnt_full = jnp.broadcast_to(
                            cnt.reshape(cnt.shape + (1,) * ns),
                            l.shape[:ns + 1])
                        g_ord = statlib.window_ordered(gw, cnt_full)
                        a_ord = statlib.window_ordered(aw, cnt_full)
                        if cfg.dist is None \
                                or collectives.world_size(cfg.dist) <= 1:
                            if cfg.health:
                                # min GJ pivot of the mid solves — the
                                # sentinel's conditioning signal
                                l_new, pl = banked_block_piv(
                                    stab(l), g_ord, cnt_full, ns + 1)
                                r_new, pr = banked_block_piv(
                                    stab(r), a_ord, cnt_full, ns + 1)
                                return l_new, r_new, jnp.minimum(pl, pr)
                            l_new = banked_block(stab(l), g_ord, cnt_full,
                                                 ns + 1)
                            r_new = banked_block(stab(r), a_ord, cnt_full,
                                                 ns + 1)
                        else:
                            # Owner-sharded block inversions (DESIGN.md
                            # §10/§11): flatten (slot x stack) slices, each
                            # worker block-updates only its owned chunk of
                            # factors + windows + counts, inverse slices
                            # all-gathered.  Zero-padded slices carry
                            # count 0 -> exact no-op -> inert.
                            def sharded(j, v, c):
                                n = 1
                                for d in j.shape[:ns + 1]:
                                    n *= d
                                new = collectives.owner_sharded_map(
                                    lambda jc, vc, cc: banked_block(
                                        _vmap_over_stack(stab_slice, 1)(jc),
                                        vc, cc, 1),
                                    (j.reshape((n,) + j.shape[ns + 1:]),
                                     v.reshape((n,) + v.shape[ns + 1:]),
                                     c.reshape((n,))),
                                    cfg.dist, n, cfg.live)
                                return new.reshape(j.shape)

                            l_new = sharded(l, g_ord, cnt_full)
                            r_new = sharded(r, a_ord, cnt_full)
                        if cfg.health:
                            # dist: no pivot export — a singular solve
                            # surfaces as non-finite/hot banks after the
                            # all-gather, caught by the post checks the
                            # same step on every worker (DESIGN.md §14)
                            return l_new, r_new, jnp.float32(jnp.inf)
                        return l_new, r_new

                    if cfg.health:
                        l_new, r_new, piv = jax.lax.cond(
                            do_inv, inv_branch,
                            lambda l, r: (l, r, jnp.float32(jnp.inf)),
                            l_sub, r_sub)
                        piv_min = jnp.minimum(piv_min, piv)
                    else:
                        l_new, r_new = jax.lax.cond(
                            do_inv, inv_branch, lambda l, r: (l, r),
                            l_sub, r_sub)
                    cnt = jnp.where(do_inv, 0, cnt)
                    if whole:
                        l_bank, r_bank = l_new, r_new
                        a_win, g_win, n_cnt = aw, gw, cnt
                    else:
                        l_bank = l_bank.at[idx].set(l_new)
                        r_bank = r_bank.at[idx].set(r_new)
                        a_win = a_win.at[idx].set(aw)
                        g_win = g_win.at[idx].set(gw)
                        n_cnt = n_cnt.at[idx].set(cnt)
                    continue

                if quant8:
                    # rank-1 quant schedule: the side triples ride the
                    # cond as pytrees; update → stabilize → requant (or
                    # quantized owner-gather under dist) per side
                    def inv_branch_q(ls, rs, gv=gv, av=av, ns=ns):
                        return (side_rank1(ls, gv, ns + 1),
                                side_rank1(rs, av, ns + 1))

                    l_new_s, r_new_s = jax.lax.cond(
                        do_inv, inv_branch_q, lambda ls, rs: (ls, rs),
                        l_sub_s, r_sub_s)
                    if whole:
                        l_side, r_side = l_new_s, r_new_s
                    else:
                        l_side = side_set(l_side, idx, l_new_s)
                        r_side = side_set(r_side, idx, r_new_s)
                    continue

                # lax.cond (not where): off-phase steps must skip the SMW
                # work, or the staggered schedule has nothing to spread.
                # With cfg.dist each worker stabilizes+SMWs only its owned
                # chunk of the group's bank dim and the inverse slices are
                # all-gathered — the collectives sit inside the cond, so
                # off-phase steps move zero factor bytes (DESIGN.md §10).
                def inv_branch(l, r, gv=gv, av=av, ns=ns):
                    stab = _vmap_over_stack(stab_slice, ns + 1)
                    if cfg.dist is None \
                            or collectives.world_size(cfg.dist) <= 1:
                        return (banked_smw(stab(l), gv, ns + 1),
                                banked_smw(stab(r), av, ns + 1))

                    # Owner-sharded: the shardable unit is a *slice* —
                    # (bank slot x stacked repeat), i.e. the lead dims
                    # flattened — so scan-stacked models parallelize over
                    # depth, not just over the (often tiny) slot count.
                    def sharded(j, v):
                        n = 1
                        for d in j.shape[:ns + 1]:
                            n *= d
                        new = collectives.owner_sharded_map(
                            lambda jc, vc: banked_smw(
                                _vmap_over_stack(stab_slice, 1)(jc), vc, 1),
                            (j.reshape((n,) + j.shape[ns + 1:]),
                             v.reshape((n,) + v.shape[ns + 1:])),
                            cfg.dist, n, cfg.live)
                        return new.reshape(j.shape)

                    return sharded(l, gv), sharded(r, av)

                l_new, r_new = jax.lax.cond(
                    do_inv, inv_branch, lambda l, r: (l, r), l_sub, r_sub)
                if whole:
                    l_bank, r_bank = l_new, r_new
                else:
                    l_bank = l_bank.at[idx].set(l_new)
                    r_bank = r_bank.at[idx].set(r_new)
            # --- health sentinel, trip phase: post-inversion signals on
            # the freshly written banks (non-finite, ‖F⁻¹‖∞ hot, GJ pivot
            # below tolerance).  A trip resets the bucket's banks to
            # identity — exact first-order passthrough — before they are
            # consumed or stored. ---------------------------------------- #
            with jax.named_scope(scopes.MKOR_PRECONDITION):
                gw = jnp.stack(g_ws)
            if cfg.health:
                if quant8:
                    post_bad = (_any_nonfinite(side_finite_srcs(l_side)
                                               + side_finite_srcs(r_side))
                                | sides_bad(l_side, r_side)
                                | ~(piv_min >= cfg.health_pivot_tol))
                    trip = pre_bad | post_bad
                    # reset = quantized identity codes at scale 1/127
                    # AND a zeroed error-feedback accumulator — carried
                    # EF from the poisoned epoch must not re-enter
                    l_side = _quant_side_reset(l_side, trip)
                    r_side = _quant_side_reset(r_side, trip)
                else:
                    post_bad = (_any_nonfinite([l_bank, r_bank])
                                | norm_hot(l_bank) | norm_hot(r_bank)
                                | ~(piv_min >= cfg.health_pivot_tol))
                    trip = pre_bad | post_bad
                    l_bank = jnp.where(trip, _identity_like(l_bank),
                                       l_bank)
                    r_bank = jnp.where(trip, _identity_like(r_bank),
                                       r_bank)
                gw_c = _finite_or_zero(gw)
            else:
                gw_c = gw

            # --- lines 9-10, banked: one batched two-sided precondition +
            # rescale over (bank, *stack); extra dims broadcast inside
            # (the pallas path is the banked fused kernel entry). -------- #
            if quant8:
                delta = side_precond(l_side, r_side, gw_c, ns + 1)
            else:
                delta = banked_precond(l_bank, r_bank, gw_c, ns + 1)
            if cfg.health:
                # rescale-denominator collapse: a slice whose update was
                # annihilated (ΔW = 0) while its gradient was not means
                # the ε = 1e-30 guard fired on a rank-collapsed factor
                eps_hit = jnp.any((_slice_sumsq(delta) == 0.0)
                                  & (_slice_sumsq(gw_c) > 0.0))
                trip = trip | eps_hit | _any_nonfinite([delta])
                if quant8:
                    l_side = _quant_side_reset(l_side, trip)
                    r_side = _quant_side_reset(r_side, trip)
                else:
                    l_bank = jnp.where(trip, _identity_like(l_bank),
                                       l_bank)
                    r_bank = jnp.where(trip, _identity_like(r_bank),
                                       r_bank)
                delta = _finite_or_zero(delta)
                if cfg.rank > 1:
                    # fresh stat window on re-entry: zero the rows too,
                    # NOT just the count — 0-weighted NaN rows would
                    # still poison the next block update (0·NaN = NaN)
                    a_win = jnp.where(trip, jnp.zeros((), a_win.dtype),
                                      a_win)
                    g_win = jnp.where(trip, jnp.zeros((), g_win.dtype),
                                      g_win)
                    if quant8:
                        # zero the per-row scales too, so a decoded
                        # window reads exactly zero on re-entry
                        a_wsc = jnp.where(trip, 0.0, a_wsc)
                        g_wsc = jnp.where(trip, 0.0, g_wsc)
                    n_cnt = jnp.where(trip, 0, n_cnt)
                new_health[bucket.bucket_id] = {
                    "cooldown": jnp.where(
                        trip, jnp.int32(cfg.health_cooldown),
                        jnp.where(phase_hit,
                                  jnp.maximum(cool - 1, 0), cool)),
                    "trips": trips + trip.astype(jnp.int32)}
            if quant8:
                new_banks[bucket.bucket_id] = pack_sides(l_side, r_side)
            else:
                new_banks[bucket.bucket_id] = {"l_inv": l_bank,
                                               "r_inv": r_bank}
            if cfg.rank > 1:
                w = {"a": a_win, "g": g_win, "n": n_cnt}
                if quant8:
                    w["a_scale"], w["g_scale"] = a_wsc, g_wsc
                new_windows[bucket.bucket_id] = w
            out = put_deltas(out, bucket, delta, gw_c, so_on)
        fstate = {"factor_banks": new_banks}
        if cfg.rank > 1:
            fstate["stat_windows"] = new_windows
        if cfg.health:
            fstate["health"] = new_health
        return out, fstate

    # ------------------------------------------------------------------ #
    # Overlap-hidden inversions (staleness >= 1, DESIGN.md §13).
    #
    # The synchronous schedule above reads this step's stats, inverts, and
    # preconditions with the result — the SMW/block work sits on the
    # critical path of every phase step.  The async schedule double-buffers
    # the inverse state instead:
    #
    #   tick (phase step t, top of step, BEFORE grads exist):
    #     active  <- pending                       (promote: pure swap)
    #     pending <- block_update(stabilize(active'),
    #                             window rows through step t-1)  (launch)
    #   every step: push this step's stat vectors into the ring window,
    #     precondition with the ACTIVE bank only.
    #
    # The launch consumes only carried state, so it has no data dependency
    # on the current forward/backward — XLA is free to overlap it with the
    # gradient collectives (training/loop.py runs the tick through
    # GradientTransformation.precompute before grads are computed).  The
    # active factors lag the synchronous schedule by exactly one inv_freq
    # window: the bounded staleness.  Under cfg.dist the launch reuses the
    # owner-sharded map INSIDE the phase cond, so the async path moves
    # zero extra per-step collective bytes vs the sync schedule
    # (analysis/checkers.py `staleness-bound` proves this statically).
    # MKOR-H gates the tick on the CARRIED switch state, so after the
    # hybrid switch flips both banks freeze (no promote, no launch).
    # ------------------------------------------------------------------ #
    def tick_banked(state, tree):
        manifest = manifest_for(tree, cfg)
        phases = statlib.bucket_phases(manifest, cfg.inv_freq, cfg.stagger)
        count = state["count"]
        so_on = state["hybrid"]["on"] if cfg.hybrid \
            else jnp.ones((), jnp.bool_)
        new_active, new_pending, new_windows = {}, {}, {}
        for bucket in manifest:
            bid = bucket.bucket_id
            act = state["factor_banks"][bid]
            pend = state["pending_banks"][bid]
            win = state["stat_windows"][bid]
            ns = len(bucket.stack)
            do_inv = so_on & (count % cfg.inv_freq == phases[bid])
            if cfg.health:
                # quarantined bucket: no promote, no launch — both banks
                # hold the identity reset until the cool-down (decremented
                # by update_banked_async on phase steps) expires, then the
                # next tick relaunches from the fresh window
                do_inv = do_inv \
                    & (state["health"][bid]["cooldown"] == 0)

            if quant8:
                # Quantized promote-then-launch: promote is a pure swap of
                # the side triples (codes + scale + EF move together); the
                # launch block-updates the just-promoted codes through the
                # fused-dequant kernel and requantizes — EF rides the
                # pending buffer (single-process) or stays zero (dist).
                def tick_branch_q(als, ars, pls, prs, aw=win["a"],
                                  awsc=win["a_scale"], gw=win["g"],
                                  gwsc=win["g_scale"], cnt=win["n"],
                                  ns=ns):
                    del als, ars                      # promoted away
                    cnt_full = jnp.broadcast_to(
                        cnt.reshape(cnt.shape + (1,) * ns),
                        pls[0].shape[:ns + 1])
                    g_ord = statlib.window_ordered(
                        statlib.window_decode(gw, gwsc), cnt_full)
                    a_ord = statlib.window_ordered(
                        statlib.window_decode(aw, awsc), cnt_full)
                    nls, _ = side_block(pls, g_ord, cnt_full, ns + 1,
                                        False)
                    nrs, _ = side_block(prs, a_ord, cnt_full, ns + 1,
                                        False)
                    return pls, prs, nls, nrs

                a_ls, a_rs, p_ls, p_rs = jax.lax.cond(
                    do_inv, tick_branch_q,
                    lambda als, ars, pls, prs: (als, ars, pls, prs),
                    *unpack_sides(act), *unpack_sides(pend))
                new_active[bid] = pack_sides(a_ls, a_rs)
                new_pending[bid] = pack_sides(p_ls, p_rs)
                new_windows[bid] = {
                    "a": win["a"], "a_scale": win["a_scale"],
                    "g": win["g"], "g_scale": win["g_scale"],
                    "n": jnp.where(do_inv, 0, win["n"])}
                continue

            # Promote-then-launch.  The new pending chains the block update
            # onto the just-promoted factors (the same inverse the sync
            # schedule would have updated in place).  A slot whose window
            # was never written carries count 0 -> block update is an exact
            # no-op and its identity factor is a stabilize fixed point, so
            # stat-less slots stay bit-identical to the sync path.
            def tick_branch(a_l, a_r, p_l, p_r, aw=win["a"], gw=win["g"],
                            cnt=win["n"], ns=ns):
                del a_l, a_r                          # promoted away
                cnt_full = jnp.broadcast_to(
                    cnt.reshape(cnt.shape + (1,) * ns), p_l.shape[:ns + 1])
                g_ord = statlib.window_ordered(gw, cnt_full)
                a_ord = statlib.window_ordered(aw, cnt_full)
                if cfg.dist is None \
                        or collectives.world_size(cfg.dist) <= 1:
                    stab = _vmap_over_stack(stab_slice, ns + 1)
                    n_l = banked_block(stab(p_l), g_ord, cnt_full, ns + 1)
                    n_r = banked_block(stab(p_r), a_ord, cnt_full, ns + 1)
                else:
                    # Identical owner-sharded launch as the sync branch —
                    # same collectives, same payloads, just gated by the
                    # tick instead of the inline phase step.
                    def sharded(j, v, c):
                        n = 1
                        for d in j.shape[:ns + 1]:
                            n *= d
                        new = collectives.owner_sharded_map(
                            lambda jc, vc, cc: banked_block(
                                _vmap_over_stack(stab_slice, 1)(jc),
                                vc, cc, 1),
                            (j.reshape((n,) + j.shape[ns + 1:]),
                             v.reshape((n,) + v.shape[ns + 1:]),
                             c.reshape((n,))),
                            cfg.dist, n, cfg.live)
                        return new.reshape(j.shape)

                    n_l = sharded(p_l, g_ord, cnt_full)
                    n_r = sharded(p_r, a_ord, cnt_full)
                return p_l, p_r, n_l, n_r

            a_l, a_r, p_l, p_r = jax.lax.cond(
                do_inv, tick_branch,
                lambda a_l, a_r, p_l, p_r: (a_l, a_r, p_l, p_r),
                act["l_inv"], act["r_inv"], pend["l_inv"], pend["r_inv"])
            new_active[bid] = {"l_inv": a_l, "r_inv": a_r}
            new_pending[bid] = {"l_inv": p_l, "r_inv": p_r}
            # Window rows persist (n_valid masking makes stale rows inert);
            # only the write count resets when the window was consumed.
            new_windows[bid] = {"a": win["a"], "g": win["g"],
                                "n": jnp.where(do_inv, 0, win["n"])}
        return {**state, "factor_banks": new_active,
                "pending_banks": new_pending, "stat_windows": new_windows}

    def tick_per_layer(state, tree):
        phases = statlib.layer_phases(manifest_for(tree, cfg),
                                      cfg.inv_freq, cfg.stagger)
        count = state["count"]
        so_on = state["hybrid"]["on"] if cfg.hybrid \
            else jnp.ones((), jnp.bool_)
        new_active, new_pending, new_windows = {}, {}, {}
        for key, fac in state["factors"].items():
            pend = state["pending_factors"][key]
            win = state["stat_windows"][key]
            ns = fac["l_inv"].ndim - 2
            stack = fac["l_inv"].shape[:ns]
            do_inv = so_on & (count % cfg.inv_freq == phases.get(key, 0))

            def tick_branch(a_l, a_r, p_l, p_r, aw=win["a"], gw=win["g"],
                            cnt=win["n"], ns=ns, stack=stack):
                del a_l, a_r
                stab = _vmap_over_stack(stab_slice, ns)
                upd = _vmap_over_stack(block_slice, ns)
                cnt_s = jnp.broadcast_to(cnt, stack)
                n_l = upd(stab(p_l), statlib.window_ordered(gw, cnt), cnt_s)
                n_r = upd(stab(p_r), statlib.window_ordered(aw, cnt), cnt_s)
                return p_l, p_r, n_l, n_r

            a_l, a_r, p_l, p_r = jax.lax.cond(
                do_inv, tick_branch,
                lambda a_l, a_r, p_l, p_r: (a_l, a_r, p_l, p_r),
                fac["l_inv"], fac["r_inv"], pend["l_inv"], pend["r_inv"])
            new_active[key] = {"l_inv": a_l, "r_inv": a_r}
            new_pending[key] = {"l_inv": p_l, "r_inv": p_r}
            new_windows[key] = {"a": win["a"], "g": win["g"],
                                "n": jnp.where(do_inv, 0, win["n"])}
        return {**state, "factors": new_active,
                "pending_factors": new_pending,
                "stat_windows": new_windows}

    def tick(state, tree):
        return tick_per_layer(state, tree) if cfg.layout == "per_layer" \
            else tick_banked(state, tree)

    # Async per-step work: push this step's stat vectors into the ring
    # windows and precondition with the ACTIVE bank.  No inversion here —
    # that happened at the tick.
    def update_per_layer_async(grads, state, params, stats, so_on):
        layer_paths = {statlib.path_str(p): p
                       for p in statlib.iter_dense_layers(grads)}
        new_windows = {}
        out = grads
        for key, fac in state["factors"].items():
            path = layer_paths[key]
            g_w = statlib.tree_get(grads, path)["w"]
            a_vec = statlib.get_a_vec(stats, path) if stats is not None \
                else None
            g_vec = statlib.get_g_vec(grads, path)
            ns = fac["l_inv"].ndim - 2

            win = state["stat_windows"][key]
            a_win, g_win, n_cnt = win["a"], win["g"], win["n"]
            if a_vec is not None and g_vec is not None:
                a_win = statlib.window_push(a_win, n_cnt, a_vec)
                g_win = statlib.window_push(g_win, n_cnt, g_vec)
                n_cnt = n_cnt + 1
            new_windows[key] = {"a": a_win, "g": g_win, "n": n_cnt}

            delta = _vmap_over_stack(precond_slice, ns)(
                fac["l_inv"], fac["r_inv"], g_w)
            delta = jnp.where(so_on, delta, g_w)      # MKOR-H fallback
            out = statlib.tree_set(
                out, path, {**statlib.tree_get(out, path), "w": delta})
        return out, {"factors": state["factors"],
                     "pending_factors": state["pending_factors"],
                     "stat_windows": new_windows}

    def update_banked_async(grads, state, params, stats, so_on):
        manifest = manifest_for(params if params is not None else grads,
                                cfg)
        phases = statlib.bucket_phases(manifest, cfg.inv_freq, cfg.stagger)
        new_windows = {}
        new_banks, new_pending, new_health = {}, {}, {}
        out = grads
        for bucket in manifest:
            bank = state["factor_banks"][bucket.bucket_id]
            pend = state["pending_banks"][bucket.bucket_id]
            l_act, r_act = bank["l_inv"], bank["r_inv"]
            l_pen, r_pen = pend["l_inv"], pend["r_inv"]
            if quant8:
                l_act_s, r_act_s = unpack_sides(bank)
                l_pen_s, r_pen_s = unpack_sides(pend)
            ns = len(bucket.stack)
            win = state["stat_windows"][bucket.bucket_id]
            a_win, g_win, n_cnt = win["a"], win["g"], win["n"]
            if quant8:
                a_wsc, g_wsc = win["a_scale"], win["g_scale"]

            g_ws, g_vecs, a_vecs = [], [], []
            for path in bucket.paths:
                g_ws.append(statlib.tree_get(grads, path)["w"])
                g_vecs.append(statlib.get_g_vec(grads, path))
                a_vecs.append(statlib.get_a_vec(stats, path)
                              if stats is not None else None)

            # --- health sentinel, async (DESIGN.md §14): same detect
            # phase as the sync path, with BOTH buffers of the double-
            # buffered state in scope — a trip resets active AND pending
            # to identity (the pending launch may have consumed poisoned
            # windows at the last tick).  Inversion itself is gated at
            # the tick (tick_banked) via the carried cooldown. ---------- #
            if cfg.health:
                hst = state["health"][bucket.bucket_id]
                cool, trips = hst["cooldown"], hst["trips"]
                phase_hit = so_on & (state["count"] % cfg.inv_freq
                                     == phases[bucket.bucket_id])
                if quant8:
                    srcs = (side_finite_srcs(l_act_s)
                            + side_finite_srcs(r_act_s)
                            + side_finite_srcs(l_pen_s)
                            + side_finite_srcs(r_pen_s)
                            + [a_wsc, g_wsc] + g_ws
                            + [v for v in g_vecs + a_vecs
                               if v is not None])
                    trip = (_any_nonfinite(srcs)
                            | sides_bad(l_act_s, r_act_s)
                            | sides_bad(l_pen_s, r_pen_s))
                else:
                    srcs = [l_act, r_act, l_pen, r_pen, a_win, g_win] \
                        + g_ws \
                        + [v for v in g_vecs + a_vecs if v is not None]
                    trip = (_any_nonfinite(srcs)
                            | norm_hot(l_act) | norm_hot(r_act)
                            | norm_hot(l_pen) | norm_hot(r_pen))

            sig_groups: Dict[Any, list] = {}
            for slot, (av, gv) in enumerate(zip(a_vecs, g_vecs)):
                if av is None or gv is None:
                    continue                      # no stats: slot untouched
                sig_groups.setdefault((av.shape, gv.shape),
                                      []).append(slot)
            for sig in sorted(sig_groups, key=str):
                slots = sig_groups[sig]
                whole = len(slots) == bucket.n_slots
                idx = jnp.asarray(slots)
                gv = jnp.stack([g_vecs[i] for i in slots])
                av = jnp.stack([a_vecs[i] for i in slots])
                if cfg.health:
                    gv = _finite_or_zero(gv)      # keep windows clean
                    av = _finite_or_zero(av)
                aw = a_win if whole else a_win[idx]
                gw = g_win if whole else g_win[idx]
                cnt = n_cnt if whole else n_cnt[idx]
                cnt_b = cnt.reshape(cnt.shape + (1,) * ns)
                if quant8:
                    awsc = a_wsc if whole else a_wsc[idx]
                    gwsc = g_wsc if whole else g_wsc[idx]
                    aw, awsc = statlib.window_push_quant(
                        aw, awsc, cnt_b, av)
                    gw, gwsc = statlib.window_push_quant(
                        gw, gwsc, cnt_b, gv)
                else:
                    aw = statlib.window_push(aw, cnt_b, av)
                    gw = statlib.window_push(gw, cnt_b, gv)
                cnt = cnt + 1
                if whole:
                    a_win, g_win, n_cnt = aw, gw, cnt
                    if quant8:
                        a_wsc, g_wsc = awsc, gwsc
                else:
                    a_win = a_win.at[idx].set(aw)
                    g_win = g_win.at[idx].set(gw)
                    n_cnt = n_cnt.at[idx].set(cnt)
                    if quant8:
                        a_wsc = a_wsc.at[idx].set(awsc)
                        g_wsc = g_wsc.at[idx].set(gwsc)
            with jax.named_scope(scopes.MKOR_PRECONDITION):
                stacked_gw = jnp.stack(g_ws)
            if cfg.health:
                if quant8:
                    l_act_s = _quant_side_reset(l_act_s, trip)
                    r_act_s = _quant_side_reset(r_act_s, trip)
                else:
                    l_act = jnp.where(trip, _identity_like(l_act), l_act)
                    r_act = jnp.where(trip, _identity_like(r_act), r_act)
                gw_c = _finite_or_zero(stacked_gw)
            else:
                gw_c = stacked_gw
            if quant8:
                delta = side_precond(l_act_s, r_act_s, gw_c, ns + 1)
            else:
                delta = banked_precond(l_act, r_act, gw_c, ns + 1)
            if cfg.health:
                eps_hit = jnp.any((_slice_sumsq(delta) == 0.0)
                                  & (_slice_sumsq(gw_c) > 0.0))
                trip = trip | eps_hit | _any_nonfinite([delta])
                if quant8:
                    # a trip resets BOTH buffers of the double-buffered
                    # side triples — identity codes, 1/127 scale, zero EF
                    l_act_s = _quant_side_reset(l_act_s, trip)
                    r_act_s = _quant_side_reset(r_act_s, trip)
                    l_pen_s = _quant_side_reset(l_pen_s, trip)
                    r_pen_s = _quant_side_reset(r_pen_s, trip)
                else:
                    l_act = jnp.where(trip, _identity_like(l_act), l_act)
                    r_act = jnp.where(trip, _identity_like(r_act), r_act)
                    l_pen = jnp.where(trip, _identity_like(l_pen), l_pen)
                    r_pen = jnp.where(trip, _identity_like(r_pen), r_pen)
                delta = _finite_or_zero(delta)
                a_win = jnp.where(trip, jnp.zeros((), a_win.dtype), a_win)
                g_win = jnp.where(trip, jnp.zeros((), g_win.dtype), g_win)
                if quant8:
                    a_wsc = jnp.where(trip, 0.0, a_wsc)
                    g_wsc = jnp.where(trip, 0.0, g_wsc)
                n_cnt = jnp.where(trip, 0, n_cnt)
                new_health[bucket.bucket_id] = {
                    "cooldown": jnp.where(
                        trip, jnp.int32(cfg.health_cooldown),
                        jnp.where(phase_hit,
                                  jnp.maximum(cool - 1, 0), cool)),
                    "trips": trips + trip.astype(jnp.int32)}
                if quant8:
                    new_banks[bucket.bucket_id] = pack_sides(l_act_s,
                                                             r_act_s)
                    new_pending[bucket.bucket_id] = pack_sides(l_pen_s,
                                                               r_pen_s)
                else:
                    new_banks[bucket.bucket_id] = {"l_inv": l_act,
                                                   "r_inv": r_act}
                    new_pending[bucket.bucket_id] = {"l_inv": l_pen,
                                                     "r_inv": r_pen}
            w = {"a": a_win, "g": g_win, "n": n_cnt}
            if quant8:
                w["a_scale"], w["g_scale"] = a_wsc, g_wsc
            new_windows[bucket.bucket_id] = w
            out = put_deltas(out, bucket, delta, gw_c, so_on)
        fstate = {"factor_banks": new_banks if cfg.health
                  else state["factor_banks"],
                  "pending_banks": new_pending if cfg.health
                  else state["pending_banks"],
                  "stat_windows": new_windows}
        if cfg.health:
            fstate["health"] = new_health
        return out, fstate

    def precompute(state, params=None, **_):
        """Phase tick of the two-phase async protocol (DESIGN.md §13).

        Runs promote+launch over the carried state only — call at the TOP
        of the train step, before grads exist, then pass
        ``precomputed=True`` to ``update``.  ``update`` without
        ``precomputed`` runs the identical tick inline, so the two call
        protocols are bit-equal."""
        if params is None:
            raise ValueError("mkor precompute needs params "
                             "(the bucket manifest is derived from them)")
        return tick(state, params)

    # ------------------------------------------------------------------ #
    def update(grads, state, params=None, stats=None, loss=None,
               precomputed=False, **_):
        if cfg.staleness and not precomputed:
            state = tick(state, params if params is not None else grads)
        count = state["count"]
        hybrid = state["hybrid"]
        if cfg.hybrid:
            if loss is None:
                raise ValueError("MKOR-H needs the loss for switching")
            hybrid = _hybrid_update(hybrid, loss, count, cfg)
        so_on = hybrid["on"] if cfg.hybrid else jnp.ones((), jnp.bool_)

        def do_inv_fn(phase):
            # Staggered round-robin (DESIGN.md §9): phase is static per
            # bucket, so every bucket inverts exactly once per inv_freq
            # window and factor staleness stays <= inv_freq.
            return so_on & (count % cfg.inv_freq == phase)

        if cfg.staleness:
            step_fn = update_per_layer_async if cfg.layout == "per_layer" \
                else update_banked_async
            out, factor_state = step_fn(grads, state, params, stats, so_on)
        else:
            step_fn = update_per_layer if cfg.layout == "per_layer" \
                else update_banked
            out, factor_state = step_fn(grads, state, params, stats,
                                        do_inv_fn, so_on)

        # probes are stat taps: never step them, keep backend moments clean
        out = statlib.zero_probes(out)
        updates, backend_state = backend.update(out, state["backend"],
                                                params=params)
        updates = statlib.zero_probes(updates)
        return updates, {
            "count": count + 1,
            **factor_state,
            "hybrid": hybrid,
            "backend": backend_state,
        }

    return GradientTransformation(init, update,
                                  precompute if cfg.staleness else None)


def mkor_h(backend: GradientTransformation,
           cfg: MKORConfig = MKORConfig()) -> GradientTransformation:
    """Hybrid MKOR (§3.2)."""
    return mkor(backend, dataclasses.replace(cfg, hybrid=True))
