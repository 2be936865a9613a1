"""Primitive layers: dense (with MKOR stat capture), norms, embeddings, RoPE.

MKOR stat capture
-----------------
MKOR (Alg. 1 lines 2-4) needs, per linear layer, the token-mean input
activation  ā = E[a]  and the token-mean output pre-activation gradient
ḡ = E[g], synchronised across all workers (the paper's AllReduce).

* ``ā`` is computed in the forward pass and returned through the loss
  function's aux output.  Under pjit the mean over the (sharded) token dims
  is a global mean — GSPMD inserts the all-reduce, i.e. exactly the paper's
  line-4 synchronisation at O(d) volume.
* ``ḡ`` rides the backward pass through a zero "probe" parameter added to
  every dense output: ``y = x @ W + probe``.  For a mean-reduced loss,
  ``dL/dprobe = Σ_t dL/dy_t = E_t[dℓ_t/dy_t] = ḡ`` *exactly* (the 1/N of
  the mean loss turns the sum into the mean).  The probe gradient is
  all-reduced together with the weight gradients — the paper's separate
  AllReduce is fused into the existing gradient collective.

Every dense param dict therefore carries ``{"w", "probe"[, "b"]}``; probes
stay zero forever (the optimizer zeroes their updates).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro import scopes

Params = Dict[str, jnp.ndarray]


# ----------------------------------------------------------------------- #
# Dense
# ----------------------------------------------------------------------- #
def dense_init(
    key: jax.Array,
    d_in: int,
    d_out: int,
    *,
    dtype: jnp.dtype,
    scale: Optional[float] = None,
    bias: bool = False,
) -> Params:
    scale = (1.0 / math.sqrt(d_in)) if scale is None else scale
    p: Params = {
        "w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype),
        "probe": jnp.zeros((d_out,), jnp.float32),
    }
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jnp.ndarray, *, stats: Optional[dict] = None,
          name: str = "") -> jnp.ndarray:
    """y = x @ W (+ b) + probe, recording E[a] into ``stats[name]``."""
    if stats is not None:
        with jax.named_scope(scopes.MKOR_STATS):
            flat = x.reshape(-1, x.shape[-1])
            stats[name] = {"a": jnp.mean(flat.astype(jnp.float32), axis=0)}
    y = jnp.einsum("...i,io->...o", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    y = y + p["probe"].astype(y.dtype)
    return y


def grouped_dense(p: Params, x: jnp.ndarray, *, stats: Optional[dict] = None,
                  name: str = "", per_expert_stats: bool = False) -> jnp.ndarray:
    """Expert-parallel dense: x (E, C, d_in), W (E, d_in, d_out).

    With shared factors (default) E[a] is the mean over all dispatched rows
    (DESIGN.md §4); with ``per_expert_stats`` a per-expert (E, d_in) mean.
    """
    if stats is not None:
        with jax.named_scope(scopes.MKOR_STATS):
            xf = x.astype(jnp.float32)
            if per_expert_stats:
                stats[name] = {"a": jnp.mean(xf, axis=1)}
            else:
                stats[name] = {"a": jnp.mean(xf.reshape(-1, x.shape[-1]),
                                             axis=0)}
    y = jnp.einsum("eci,eio->eco", x, p["w"])
    if "b" in p:
        y = y + p["b"][:, None, :]
    y = y + p["probe"].astype(y.dtype)
    return y


def grouped_dense_init(key, n_experts: int, d_in: int, d_out: int, *,
                       dtype, per_expert_probe: bool = False) -> Params:
    w = jax.random.normal(key, (n_experts, d_in, d_out), jnp.float32)
    probe_shape = (n_experts, 1, d_out) if per_expert_probe else (d_out,)
    return {
        "w": (w / math.sqrt(d_in)).astype(dtype),
        "probe": jnp.zeros(probe_shape, jnp.float32),
    }


# ----------------------------------------------------------------------- #
# Norms
# ----------------------------------------------------------------------- #
def norm_init(d: int, kind: str = "rmsnorm") -> Params:
    p: Params = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x: jnp.ndarray, *, kind: str = "rmsnorm",
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        y = y * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def group_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 64e-5) -> jnp.ndarray:
    """Per-head group norm (RWKV-6 wkv output)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return y.astype(x.dtype)


# ----------------------------------------------------------------------- #
# Embedding
# ----------------------------------------------------------------------- #
def embed_init(key, vocab: int, d: int, *, dtype) -> Params:
    tbl = jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
    return {"table": tbl.astype(dtype)}


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,vd->...v", x, p["table"])


# ----------------------------------------------------------------------- #
# Rotary position embeddings
# ----------------------------------------------------------------------- #
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                               # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------- #
# Activations / MLP
# ----------------------------------------------------------------------- #
def activation(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind}")


def mlp_init(key, d_model: int, d_ff: int, *, dtype, gated: bool = True) -> Params:
    ks = jax.random.split(key, 3)
    p = {
        "in": dense_init(ks[0], d_model, d_ff, dtype=dtype),
        "out": dense_init(ks[1], d_ff, d_model, dtype=dtype,
                          scale=1.0 / math.sqrt(d_ff)),
    }
    if gated:
        p["gate"] = dense_init(ks[2], d_model, d_ff, dtype=dtype)
    return p


def mlp(p: Params, x: jnp.ndarray, *, act: str = "silu",
        stats: Optional[dict] = None, name: str = "") -> jnp.ndarray:
    from repro.sharding import rules
    sub = {} if stats is not None else None
    h = dense(p["in"], x, stats=sub, name="in")
    if "gate" in p:
        g = dense(p["gate"], x, stats=sub, name="gate")
        h = activation(g, act) * h
    else:
        h = activation(h, act)
    h = rules.constrain(h, "batch", None, "model")   # TP hidden dim
    y = dense(p["out"], h, stats=sub, name="out")
    if stats is not None:
        stats[name] = sub
    return y


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)
