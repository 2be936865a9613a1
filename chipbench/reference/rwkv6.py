"""Plain float32 reference of an RWKV-6 ("Finch", arXiv:2404.05892)
language model, written from the equations alone; ``mkor_lamb`` trains it.

It imports nothing of the program under test.  Its inputs are the
benchmark's own seeded weights, laid out as the program's parameter tree,
and the cell's token batches.

Per layer (pre-norm, N = 64 channels per head):
    h = LayerNorm(x);  shift(h)_t = h_(t-1), 0 at t = 0;  dx = shift(h) - h
    token shift, data dependent (ddlerp), for * in w, k, v, r, g:
        m = tanh((h + dx maa_x) W1) W2 + maa;   h_* = h + dx m_*
    r, k, v = h_r Wr, h_k Wk, h_v Wv;  g = silu(h_g Wg)     (each + probe)
    decay w = exp(-exp(w0 + tanh(h_w D1) D2))
    per head, state S (N x N) from 0:
        y_t = r_t (S + diag(u) k_t^T v_t);   S <- diag(w_t) S + k_t^T v_t
    y = GroupNorm per head (eps 64e-5, affine shared by the heads) * g;
    x += y Wo
    h2 = LayerNorm(x);  dx = shift(h2) - h2
    x += sigmoid((h2 + dx maa_r) Wr') * (relu((h2 + dx maa_k) Wk')^2 Wv')
then the final norm, the untied head and the mean next-token loss.
Departures from the published model, as the configuration notes: no
LayerNorm on the embeddings before the first block (ln0), and one
GroupNorm affine pair of N channels shared by every head.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference.layers import F32, dense, head_loss, norm

N = 64
LORA_MIX = 32


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _time_mix(p, x, stats):
    b, s, d = x.shape
    h = d // N
    dx = _shift(x) - x
    xxx = x + dx * p["maa_x"]
    lora = jnp.tanh(xxx @ p["maa_w1"]).reshape(b, s, 5, LORA_MIX)
    mix = jnp.einsum("bsfi,fid->bsfd", lora, p["maa_w2"]) + p["maa"]
    xw, xk, xv, xr, xg = (x + dx * mix[:, :, i] for i in range(5))
    r = dense(p["r"], xr, stats, "r").reshape(b, s, h, N)
    k = dense(p["k"], xk, stats, "k").reshape(b, s, h, N)
    v = dense(p["v"], xv, stats, "v").reshape(b, s, h, N)
    g = jax.nn.silu(dense(p["g"], xg, stats, "g"))
    w = jnp.exp(-jnp.exp(p["decay_w0"] + jnp.tanh(xw @ p["decay_w1"])
                         @ p["decay_w2"])).reshape(b, s, h, N)
    u = p["bonus"]

    @jax.checkpoint
    def step(state, t):
        # y = r (S + diag(u) k^T v) = r S + (r . u k) v: the same sum,
        # written so that the backward pass keeps only the carried state
        rt, kt, vt, wt = t                               # (B, H, N)
        y = jnp.einsum("bhi,bhij->bhj", rt, state) \
            + jnp.sum(rt * u * kt, axis=-1, keepdims=True) * vt
        return state * wt[..., :, None] + kt[..., :, None] * vt[..., None, :], y

    seq = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, N, N), F32), seq)
    y = ys.transpose(1, 0, 2, 3)                         # (B, S, H, N)
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), -1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + 64e-5) * p["ln_x_scale"] \
        + p["ln_x_bias"]
    return dense(p["o"], y.reshape(b, s, d) * g, stats, "o")


def _channel_mix(p, x, stats):
    dx = _shift(x) - x
    kk = jnp.square(jax.nn.relu(dense(p["key"], x + dx * p["maa_k"], stats,
                                      "key")))
    kv = dense(p["value"], kk, stats, "value")
    return jax.nn.sigmoid(dense(p["recept"], x + dx * p["maa_r"], stats,
                                "recept")) * kv


def _layer(p, x, sz):
    st = {"mixer": {}, "mlp": {}}
    x = x + _time_mix(p["mixer"], norm(p["pre_norm"], x, "layernorm",
                                       sz["eps"]), st["mixer"])
    x = x + _channel_mix(p["mlp"], norm(p["mlp_norm"], x, "layernorm",
                                        sz["eps"]), st["mlp"])
    return x, st


def loss_and_stats(params, batch, sz):
    """Mean next-token loss and the per-dense-layer input means E[a]."""
    f32 = partial(jax.tree.map, lambda t: t.astype(F32))
    top = f32({k: v for k, v in params.items() if k != "blocks"})
    x = top["embed"]["table"][batch["tokens"]]

    def body(x, blk):
        return jax.checkpoint(partial(_layer, sz=sz))(f32(blk), x)

    x, st = jax.lax.scan(body, x, params["blocks"][0])
    return head_loss(top, x, batch, sz, {"blocks": [st]})


def flops_per_token(sz, seq_len: int) -> float:
    """Forward plus backward FLOPs per trained token: 6 per weight of the
    projections (r, k, v, g, o), the channel mix (key, value, receptance),
    the token-shift and decay LoRAs and the untied head; plus the wkv
    recurrence, about 5 N^2 per head forward (outer product, read-out and
    state decay), tripled.  No recompute, no optimizer."""
    d, f = sz["d_model"], sz["d_ff"]
    per_layer = 6 * d * d + 2 * d * f + d * (5 * LORA_MIX * 2 + 2 * 64)
    wkv = 5.0 * N * d
    return (6.0 * (sz["n_layers"] * per_layer + d * sz["vocab"])
            + 3.0 * sz["n_layers"] * wkv)
