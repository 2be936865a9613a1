"""First-order optimizer backends (hand-built, optax-style).

MKOR (Alg. 1 line 14) hands its preconditioned gradients to a *backend*
first-order optimizer.  The paper uses Fused LAMB for BERT and momentum-SGD
for CNNs; both are implemented here, plus Adam/AdamW for completeness and a
``chain``/``scale_by_schedule`` combinator layer.

Convention: ``update`` returns *additive* updates — apply with
``params = tree_add(params, updates)`` (updates already contain the -lr).
Each ``update`` runs under the ``backend`` named scope (repro/scopes.py).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import scopes

Params = Any
State = Any
Schedule = Callable[[jnp.ndarray], jnp.ndarray]


class GradientTransformation(NamedTuple):
    """An optimizer as an (init, update[, precompute]) triple.

    ``precompute`` is the optional pre-step hook of the two-phase protocol
    (DESIGN.md §13): called as ``state = precompute(state, params=params)``
    at the TOP of a train step, BEFORE the gradients exist, it may only
    consume state carried in from previous steps.  Async optimizers (MKOR
    with ``staleness >= 1``) use it to launch next-phase factor inversions
    with no data dependency on the current step's forward/backward, so XLA
    can overlap them with the gradient collectives.  Callers that run
    precompute must pass ``precomputed=True`` to ``update`` (exactly once
    per step); callers that don't — every pre-existing call site — get the
    identical result because ``update`` runs the hook inline when
    ``precomputed`` is false.  First-order backends leave it ``None``.
    """
    init: Callable[[Params], State]
    update: Callable[..., Tuple[Params, State]]
    precompute: Optional[Callable[..., State]] = None


def _tree_zeros(params, dtype=jnp.float32):
    return jax.tree.map(lambda p: jnp.zeros_like(p, dtype=dtype), params)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_scale(s, t):
    return jax.tree.map(lambda x: s * x, t)


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def as_schedule(lr) -> Schedule:
    if callable(lr):
        return lr
    return lambda step: jnp.asarray(lr, jnp.float32)


# ----------------------------------------------------------------------- #
def sgd(lr, momentum: float = 0.0, nesterov: bool = False,
        weight_decay: float = 0.0) -> GradientTransformation:
    lr = as_schedule(lr)

    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "mu": _tree_zeros(params) if momentum else None}

    @scopes.scoped(scopes.BACKEND)
    def update(grads, state, params=None, **_):
        step = state["count"]
        if weight_decay and params is not None:
            grads = jax.tree.map(
                lambda g, p: g + weight_decay * p.astype(g.dtype),
                grads, params)
        if momentum:
            mu = jax.tree.map(
                lambda m, g: momentum * m + g.astype(jnp.float32),
                state["mu"], grads)
            d = jax.tree.map(
                lambda m, g: momentum * m + g.astype(jnp.float32), mu, grads
            ) if nesterov else mu
        else:
            mu, d = None, grads
        lr_t = lr(step)
        updates = jax.tree.map(
            lambda g, p: (-lr_t * g).astype(p.dtype), d,
            params if params is not None else d)
        return updates, {"count": step + 1, "mu": mu}

    return GradientTransformation(init, update)


# ----------------------------------------------------------------------- #
def _adam_moments(grads, state, b1, b2):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                     state["m"], grads)
    v = jax.tree.map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
        state["v"], grads)
    return m, v


def adam(lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0) -> GradientTransformation:
    """Adam; with weight_decay>0 this is AdamW (decoupled)."""
    lr = as_schedule(lr)

    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "m": _tree_zeros(params), "v": _tree_zeros(params)}

    @scopes.scoped(scopes.BACKEND)
    def update(grads, state, params=None, **_):
        step = state["count"] + 1
        m, v = _adam_moments(grads, state, b1, b2)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        lr_t = lr(step - 1)

        def upd(m, v, p):
            d = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if weight_decay and p is not None:
                d = d + weight_decay * p.astype(jnp.float32)
            return (-lr_t * d).astype(p.dtype)

        updates = jax.tree.map(upd, m, v,
                               params if params is not None else m)
        return updates, {"count": step, "m": m, "v": v}

    return GradientTransformation(init, update)


def adamw(lr, weight_decay: float = 0.01, **kw) -> GradientTransformation:
    return adam(lr, weight_decay=weight_decay, **kw)


# ----------------------------------------------------------------------- #
def lamb(lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-6,
         weight_decay: float = 0.01,
         trust_clip: Optional[float] = 10.0) -> GradientTransformation:
    """LAMB (You et al., arXiv:1904.00962) — the paper's first-order baseline
    and MKOR's backend for BERT-scale training."""
    lr = as_schedule(lr)

    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "m": _tree_zeros(params), "v": _tree_zeros(params)}

    @scopes.scoped(scopes.BACKEND)
    def update(grads, state, params=None, **_):
        assert params is not None, "lamb needs params (trust ratio)"
        step = state["count"] + 1
        m, v = _adam_moments(grads, state, b1, b2)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        lr_t = lr(step - 1)

        def upd(m, v, p):
            r = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            r = r + weight_decay * p.astype(jnp.float32)
            pn = jnp.linalg.norm(p.astype(jnp.float32))
            rn = jnp.linalg.norm(r)
            trust = jnp.where((pn > 0) & (rn > 0), pn / jnp.maximum(rn, 1e-12),
                              1.0)
            if trust_clip is not None:
                trust = jnp.minimum(trust, trust_clip)
            return (-lr_t * trust * r).astype(p.dtype)

        updates = jax.tree.map(upd, m, v, params)
        return updates, {"count": step, "m": m, "v": v}

    return GradientTransformation(init, update)


# ----------------------------------------------------------------------- #
def clip_by_global_norm(max_norm: float) -> GradientTransformation:
    def init(params):
        return {}

    @scopes.scoped(scopes.BACKEND)
    def update(grads, state, params=None, **_):
        gn = global_norm(grads)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
        return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                       ).astype(g.dtype), grads), state

    return GradientTransformation(init, update)


def chain(*transforms: GradientTransformation) -> GradientTransformation:
    def init(params):
        return tuple(t.init(params) for t in transforms)

    def update(grads, state, params=None, **extra):
        new_states = []
        for t, s in zip(transforms, state):
            grads, ns = t.update(grads, s, params=params, **extra)
            new_states.append(ns)
        return grads, tuple(new_states)

    return GradientTransformation(init, update)


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: (p.astype(jnp.float32)
                                      + u.astype(jnp.float32)).astype(p.dtype),
                        params, updates)
