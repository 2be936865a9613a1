"""RWKV-6's chunked wkv recurrence against the per-token step it replaces.

``ssm._wkv_chunked`` computes the recurrence of ``ssm._wkv_step`` a chunk
at a time; the per-token scan over ``_wkv_step`` is the oracle.  Both run
in float32, so outputs, final state and every gradient agree to a
relative error (max |a - b| over max |b|) of 1e-5.  Serving is the other
caller of the chunked path: its prefill state has to continue in the
one-token decode exactly where a full-sequence forward goes.
"""
import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as model_lib
from repro.models import ssm
from repro.training import serving

C = ssm.WKV_CHUNK
B, H, N = 2, 2, 64
RTOL = 1e-5


def _per_token(r, k, v, lw, bonus, state):
    """The oracle: `_wkv_step` over every token, w = exp(log w)."""
    seq = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, jnp.exp(lw)))
    state, ys = jax.lax.scan(lambda st, t: ssm._wkv_step(st, t, bonus),
                             state, seq)
    return ys.transpose(1, 0, 2, 3), state


def _chunked(r, k, v, lw, bonus, state):
    return ssm._wkv_chunked(r, k, v, lw, bonus, state)


def _log_decay(rng, s, kind):
    """log w of shape (B,S,H,N): per channel, held over the sequence, or
    drawn anew for every token, log-uniform in magnitude over [1e-4, 30];
    or the cell's initial -exp(-6) everywhere."""
    mag = lambda shape: np.exp(rng.uniform(np.log(1e-4), np.log(30.0), shape))
    if kind == "per_channel":
        return -np.broadcast_to(mag((B, 1, H, N)), (B, s, H, N))
    if kind == "per_token":
        return -mag((B, s, H, N))
    return np.full((B, s, H, N), -np.exp(-6.0))


def _inputs(s, kind, zero_state, seed=0):
    rng = np.random.default_rng(seed)
    r, k, v = (rng.standard_normal((B, s, H, N)) for _ in range(3))
    bonus = rng.uniform(-1.0, 1.0, (H, N))
    state = (np.zeros((B, H, N, N)) if zero_state
             else rng.standard_normal((B, H, N, N)))
    args = (r, k, v, _log_decay(rng, s, kind), bonus, state)
    return tuple(jnp.asarray(a, jnp.float32) for a in args)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)


def _value_and_grads(fn, args, s, seed=1):
    """fn's outputs and the gradient of a random projection of them with
    respect to every argument."""
    rng = np.random.default_rng(seed)
    gy = jnp.asarray(rng.standard_normal((B, s, H, N)), jnp.float32)
    gs = jnp.asarray(rng.standard_normal((B, H, N, N)), jnp.float32)

    def loss(*a):
        y, st = fn(*a)
        return jnp.sum(y * gy) + jnp.sum(st * gs)

    grads = jax.jit(jax.grad(loss, argnums=tuple(range(6))))(*args)
    return jax.jit(fn)(*args), grads


@pytest.mark.parametrize("kind", ["per_channel", "per_token", "cell"])
@pytest.mark.parametrize("zero_state", [True, False],
                         ids=["zero_state", "random_state"])
@pytest.mark.parametrize("s", [1, 37, C, 3 * C + 5])
def test_chunked_wkv_matches_the_per_token_step(s, zero_state, kind):
    args = _inputs(s, kind, zero_state)
    (y, st), grads = _value_and_grads(_chunked, args, s)
    (y0, st0), grads0 = _value_and_grads(_per_token, args, s)
    for got in (y, st) + tuple(grads):
        assert np.isfinite(np.asarray(got)).all()
    assert _rel(y, y0) <= RTOL
    assert _rel(st, st0) <= RTOL
    names = ("r", "k", "v", "log_w", "bonus", "state")
    for name, g, g0 in zip(names, grads, grads0):
        assert _rel(g, g0) <= RTOL, name


def test_strongest_decay_is_finite_and_forgets():
    """log w = -30 everywhere: each state holds only its last token's
    k v^T, and nothing overflows on the way, forward or backward."""
    s = 2 * C + 3
    r, k, v, lw, bonus, state = _inputs(s, "cell", False)
    lw = jnp.full_like(lw, -30.0)
    (y, st), grads = _value_and_grads(_chunked, (r, k, v, lw, bonus, state),
                                      s)
    for got in (y, st) + tuple(grads):
        assert np.isfinite(np.asarray(got)).all()
    last = jnp.einsum("bhi,bhj->bhij", k[:, -1], v[:, -1])
    np.testing.assert_allclose(st, last, rtol=1e-5, atol=1e-6)


def _scan_lengths(jaxpr):
    """The length of every scan in a jaxpr, its sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jex.ClosedJaxpr):
                    out += _scan_lengths(sub.jaxpr)
                elif isinstance(sub, jex.Jaxpr):
                    out += _scan_lengths(sub)
    return out


def test_time_mix_gradient_loops_over_chunks_not_tokens():
    """The forward and backward passes of RWKV time mixing scan over the
    S/C chunks; no loop runs over the S tokens."""
    cfg = registry.get_config("rwkv6-3b").reduced(d_model=128)
    s = 3 * C + 5
    p = ssm.rwkv_init(jax.random.key(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, s, cfg.d_model))

    def loss(p, x):
        y, st = ssm.rwkv_time_mix(p, x, cfg)
        return jnp.sum(y) + jnp.sum(st["wkv"])

    lengths = _scan_lengths(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        p, x).jaxpr)
    assert lengths, "the chunk scan is missing"
    assert max(lengths) <= -(-s // C) < s


def test_rwkv_prefill_then_decode_matches_full_forward():
    """Prefill a prompt through the chunked path (forward with
    build_cache), then decode token by token through
    `rwkv_time_mix_decode`: every decoded position's logits equal those of
    one forward over the whole sequence."""
    cfg = registry.get_config("rwkv6-3b").reduced(d_model=128)
    params = model_lib.init_params(jax.random.key(0), cfg)
    prompt, total = C + 9, C + 14
    tokens = jax.random.randint(jax.random.key(1), (2, total), 0,
                                cfg.vocab_size)
    full, _ = jax.jit(lambda p, t: model_lib.forward(
        p, cfg, {"tokens": t}))(params, tokens)

    prefill = jax.jit(serving.make_prefill_step(cfg))
    step = jax.jit(serving.make_serve_step(cfg))
    logits, cache = prefill(params, {"tokens": tokens[:, :prompt]})
    np.testing.assert_allclose(logits[:, 0], full[:, prompt - 1],
                               rtol=1e-4, atol=1e-4)
    for i in range(prompt, total):
        _, logits, cache = step(params, cache, tokens[:, i:i + 1])
        np.testing.assert_allclose(logits[:, 0], full[:, i],
                                   rtol=1e-4, atol=1e-4)
