"""Attention-free sequence mixers: RWKV-6 ("Finch") and Mamba (for Jamba).

Both have an O(1)-state single-step path for decode — this is what makes
`long_500k` (524288-token decode) tractable for the ssm/hybrid
architectures.  For training and prefill Mamba runs a `lax.scan` over time;
RWKV-6 runs its wkv recurrence a chunk of `WKV_CHUNK` tokens at a time
(`_wkv_chunked`): dense f32 contractions within a chunk and a `lax.scan`
that carries the (B, H, N, N) state across chunks, so neither the forward
nor the backward pass loops over tokens or keeps a per-token state.

RWKV-6 follows arXiv:2404.05892: token-shift with data-dependent ("ddlerp")
mixing via a low-rank MLP, per-channel **data-dependent decay**
``w_t = exp(-exp(w0 + tanh(x W1) W2))``, per-head wkv state (N x N), bonus
``u``, group-norm, and a relu^2 channel-mix.

Mamba follows the selective-SSM recurrence (used in Jamba, arXiv:2403.19887):
in-proj -> causal depthwise conv -> data-dependent (dt, B, C) -> discretised
scan -> gated out-proj.

The square projection matrices (r/k/v/g/o, channel-mix, in/out/x/dt proj)
are ordinary dense layers and therefore receive MKOR second-order
preconditioning; the recurrence parameters (decay vectors, A, conv) are
non-matmul parameters and pass through first-order (DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import scopes
from repro.models import layers
from repro.models.config import ModelConfig

# ----------------------------------------------------------------------- #
# RWKV-6
# ----------------------------------------------------------------------- #
RWKV_LORA_MIX = 32
RWKV_LORA_DECAY = 64


def rwkv_init(key, cfg: ModelConfig, *, dtype) -> Dict:
    d = cfg.d_model
    n = cfg.rwkv_head_dim
    h = d // n
    ks = jax.random.split(key, 12)
    u = lambda k, shape, s=1e-2: jax.random.uniform(k, shape, jnp.float32,
                                                    -s, s)
    return {
        "maa_x": u(ks[0], (d,)),
        "maa": u(ks[1], (5, d)),                       # w,k,v,r,g base mixes
        "maa_w1": u(ks[2], (d, 5 * RWKV_LORA_MIX)),
        "maa_w2": u(ks[3], (5, RWKV_LORA_MIX, d)),
        "decay_w0": jnp.zeros((d,), jnp.float32) - 6.0,
        "decay_w1": u(ks[4], (d, RWKV_LORA_DECAY)),
        "decay_w2": u(ks[5], (RWKV_LORA_DECAY, d)),
        "bonus": u(ks[6], (h, n)),                     # time_faaaa
        "r": layers.dense_init(ks[7], d, d, dtype=dtype),
        "k": layers.dense_init(ks[8], d, d, dtype=dtype),
        "v": layers.dense_init(ks[9], d, d, dtype=dtype),
        "g": layers.dense_init(ks[10], d, d, dtype=dtype),
        "o": layers.dense_init(ks[11], d, d, dtype=dtype,
                               scale=1.0 / math.sqrt(d)),
        "ln_x_scale": jnp.ones((n,), jnp.float32),
        "ln_x_bias": jnp.zeros((n,), jnp.float32),
    }


def rwkv_cm_init(key, cfg: ModelConfig, *, dtype) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "maa_k": jax.random.uniform(ks[0], (d,), jnp.float32, -1e-2, 1e-2),
        "maa_r": jax.random.uniform(ks[1], (d,), jnp.float32, -1e-2, 1e-2),
        "key": layers.dense_init(ks[2], d, f, dtype=dtype),
        "value": layers.dense_init(ks[3], f, d, dtype=dtype,
                                   scale=1.0 / math.sqrt(f)),
        "recept": layers.dense_init(jax.random.fold_in(key, 9), d, d,
                                    dtype=dtype),
    }


def _rwkv_mix(p, x, x_prev, cfg, stats):
    """Data-dependent token-shift mixing + r/k/v/g projections and decay.

    x, x_prev: (B, S, d). Returns r,k,v,g heads (B,S,H,N) and the log of
    the decay, ``log w = -exp(w0 + tanh(x W1) W2)`` (B,S,H,N), <= 0.
    """
    d = cfg.d_model
    n = cfg.rwkv_head_dim
    h = d // n
    xx = x_prev - x
    xxx = x + xx * p["maa_x"]
    router = jnp.tanh(xxx.astype(jnp.float32) @ p["maa_w1"])
    router = router.reshape(*x.shape[:-1], 5, RWKV_LORA_MIX)
    mix = jnp.einsum("...fi,fid->...fd", router, p["maa_w2"])
    mix = mix + p["maa"]                               # (...,5,d)
    xw, xk, xv, xr, xg = [
        (x + xx * mix[..., i, :].astype(x.dtype)) for i in range(5)
    ]
    r = layers.dense(p["r"], xr, stats=stats, name="r")
    k = layers.dense(p["k"], xk, stats=stats, name="k")
    v = layers.dense(p["v"], xv, stats=stats, name="v")
    g = jax.nn.silu(layers.dense(p["g"], xg, stats=stats, name="g"))
    dec = p["decay_w0"] + jnp.tanh(xw.astype(jnp.float32) @ p["decay_w1"]) \
        @ p["decay_w2"]
    hd = lambda t: t.reshape(*t.shape[:-1], h, n)
    return hd(r), hd(k), hd(v), g, hd(-jnp.exp(dec))


def _rwkv_projections(p, x, x_prev, cfg, stats):
    """`_rwkv_mix` with the decay itself, w = exp(log w) in (0, 1]."""
    r, k, v, g, lw = _rwkv_mix(p, x, x_prev, cfg, stats)
    return r, k, v, g, jnp.exp(lw)


def _wkv_step(state, rkvw, bonus):
    """state (B,H,N,N); r,k,v,w (B,H,N). y_j = sum_i r_i (S_ij + u_i k_i v_j)."""
    r, k, v, w = rkvw
    kv = jnp.einsum("bhi,bhj->bhij", k, v)
    y = jnp.einsum("bhi,bhij->bhj", r, state + bonus[..., None] * kv)
    state = state * w[..., None] + kv
    return state, y


# The chunked wkv.  Within a chunk, with L the running sum of log w,
#   y_t = r_t (e^{L_{t-1}} S_0 + sum_{s<t} e^{L_{t-1}-L_s} k_s v_s^T)
#         + (r_t u k_t) v_t,
#   S_C = e^{L_C} S_0 + sum_s e^{L_C-L_s} k_s v_s^T.
# Every exponent taken is a sum of log w over a run of tokens, so it is
# <= 0: a strong decay underflows to its true value (about 0), nothing
# overflows, and no cumulative decay is divided by.  A pair (t, s) in
# different sub-chunks splits its weight at the end of s's sub-chunk into
# two such factors, one on r_t and one on k_s, and goes through a matmul;
# their runs are prefix and suffix sums of their own, exact to a few ulp
# however strong the decays before them.  Pairs within a sub-chunk get
# their weight elementwise, masked to s < t, from the difference of two
# running sums that start at the sub-chunk: it rounds like a sum of at
# most WKV_SUB tokens.
WKV_CHUNK = 64       # tokens a chunk: a one-layer timing on a v5e (PERF.md)
WKV_SUB = 16         # tokens a sub-chunk
_HIGHEST = jax.lax.Precision.HIGHEST


def _shift(t, axis, back=False):
    """t moved one place along ``axis``, towards its end (its start where
    ``back``), with a zero let in: an inclusive running sum made
    exclusive."""
    zero = jnp.zeros_like(jax.lax.slice_in_dim(t, 0, 1, axis=axis))
    if back:
        return jnp.concatenate(
            [jax.lax.slice_in_dim(t, 1, None, axis=axis), zero], axis)
    return jnp.concatenate(
        [zero, jax.lax.slice_in_dim(t, 0, -1, axis=axis)], axis)


def _wkv_within_chunks(r, k, v, lw, bonus):
    """The wkv of each chunk on its own, from a zero state.

    r, k, v, lw: (..., C, N) f32, lw = log w <= 0; bonus broadcasts to
    them.  Returns ``(y, r_in, decay, kv)``: the chunk's read-out (...,C,N),
    r weighted by the decay from the chunk's start to each token (the
    read-out of the incoming state is ``r_in @ S_0``), the chunk's whole
    decay (..., N), and what it adds to the state (..., N, N).
    """
    *lead, c, n = r.shape
    e = WKV_SUB
    m = c // e
    sub = lambda t: t.reshape(*lead, m, e, n)
    lws = sub(lw)
    # running sums of log w within each sub-chunk: over the tokens before
    # t, and over those after it
    inc = jnp.cumsum(lws, axis=-2)
    before = _shift(inc, -2)
    after = _shift(jax.lax.cumsum(lws, axis=lws.ndim - 2, reverse=True), -2,
                   back=True)
    total = jnp.sum(lws, axis=-2)                       # (..., m, N)
    ia = np.arange(m)
    lt = ia[None, :] < ia[:, None]                      # [b, a]: a < b
    btw = lt[:, :, None] & (ia[None, :, None] < ia[None, None, :]) \
        & lt[:, None, :]                                # [b, a, a']: a<a'<b
    tot = total[..., None, :, :]
    sum_lt = jnp.sum(jnp.where(lt[:, :, None], tot, 0.0), -2)
    sum_gt = jnp.sum(jnp.where(lt.T[:, :, None], tot, 0.0), -2)
    sum_btw = jnp.sum(jnp.where(btw[..., None], tot[..., None, :, :], 0.0),
                      -2)                               # (..., m, m, N)

    r_in = (sub(r) * jnp.exp(before + sum_lt[..., None, :])).reshape(r.shape)
    k_out = (sub(k) * jnp.exp(after + sum_gt[..., None, :])).reshape(k.shape)
    decay = jnp.exp(jnp.sum(total, axis=-2))
    kv = jnp.einsum("...si,...sj->...ij", k_out, v, precision=_HIGHEST)

    # pairs in different sub-chunks: r_t decayed back to the end of s's
    # sub-chunk a (zero where a is not before t's), k_s decayed to it
    qx = before[..., :, :, None, :] + sum_btw[..., :, None, :, :]
    q = sub(r)[..., None, :] * jnp.exp(
        jnp.where(lt[:, None, :, None], qx, -jnp.inf))  # (..., m, e, m, N)
    ks = sub(k) * jnp.exp(after)
    att = jnp.einsum("...tai,...asi->...tas", q.reshape(*lead, c, m, n), ks,
                     precision=_HIGHEST).reshape(*lead, m, e, m, e)
    # pairs within a sub-chunk: the sum of log w strictly between s and t
    tri = np.tri(e, k=-1, dtype=bool)                   # [t, s]: s < t
    mid = before[..., :, None, :] - inc[..., None, :, :]
    w_ts = jnp.exp(jnp.where(tri[:, :, None], mid, -jnp.inf))
    diag = jnp.sum(sub(r)[..., :, None, :] * w_ts * sub(k)[..., None, :, :],
                   -1)                                  # (..., m, e, e)
    eye = np.eye(m, dtype=bool)[:, None, :, None]
    att = jnp.where(eye, diag[..., :, :, None, :], att).reshape(*lead, c, c)
    y = jnp.einsum("...ts,...sj->...tj", att, v, precision=_HIGHEST)
    y = y + jnp.sum(r * bonus * k, -1, keepdims=True) * v
    return y, r_in, decay, kv


def _wkv_chunked(r, k, v, lw, bonus, state, *, chunk=WKV_CHUNK):
    """The wkv recurrence of `_wkv_step` over a whole sequence, a chunk at
    a time.

    r, k, v, lw: (B,S,H,N) f32 with lw = log w; bonus (H,N); state
    (B,H,N,N).  Returns (y (B,S,H,N), final state).  A sequence shorter
    than ``chunk`` is one chunk of whole sub-chunks; the tail is padded
    with r = k = v = 0 and log w = 0, which leaves the state and the
    outputs kept as they are.
    """
    b, s, h, n = r.shape
    c = min(chunk, -(-s // WKV_SUB) * WKV_SUB)
    pad = -s % c
    nc = (s + pad) // c

    def chunks(t):                                      # -> (B,H,nc,C,N)
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return t.reshape(b, nc, c, h, n).transpose(0, 3, 1, 2, 4)

    y, r_in, decay, kv = _wkv_within_chunks(
        *map(chunks, (r, k, v, lw)), bonus[:, None, None, :])

    def carry(st, dkv):
        d, add = dkv
        return d[..., None] * st + add, st

    state, s_in = jax.lax.scan(carry, state, (jnp.moveaxis(decay, 2, 0),
                                              jnp.moveaxis(kv, 2, 0)))
    y = y + jnp.einsum("bhcti,cbhij->bhctj", r_in, s_in, precision=_HIGHEST)
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, nc * c, h, n)[:, :s]
    return y, state


def rwkv_time_mix(p, x, cfg, *, state=None, x_prev=None,
                  stats: Optional[dict] = None) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence RWKV-6 time mixing.  Returns (y, final_state_dict)."""
    b, s, d = x.shape
    n = cfg.rwkv_head_dim
    h = d // n
    if x_prev is None:
        x_prev = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    r, k, v, g, lw = _rwkv_mix(p, x, x_prev, cfg, stats)
    if state is None:
        state = jnp.zeros((b, h, n, n), jnp.float32)
    f32 = lambda t: t.astype(jnp.float32)
    with jax.named_scope(scopes.WKV):
        y, state = _wkv_chunked(f32(r), f32(k), f32(v), lw, p["bonus"],
                                state)
    y = layers.group_norm(y, p["ln_x_scale"], p["ln_x_bias"])
    y = y.reshape(b, s, d).astype(x.dtype) * g
    out = layers.dense(p["o"], y, stats=stats, name="o")
    return out, {"wkv": state, "x_last": x[:, -1]}


def rwkv_time_mix_decode(p, x, cfg, cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: (B,1,d); cache: {"wkv": (B,H,N,N), "x_last": (B,d)}."""
    b, _, d = x.shape
    n = cfg.rwkv_head_dim
    h = d // n
    x_prev = cache["x_last"][:, None, :]
    r, k, v, g, w = _rwkv_projections(p, x, x_prev, cfg, None)
    sq = lambda t: t[:, 0].astype(jnp.float32)
    state, y = _wkv_step(cache["wkv"],
                         (sq(r), sq(k), sq(v),
                          sq(w.reshape(b, 1, h, n))), p["bonus"])
    y = layers.group_norm(y[:, None], p["ln_x_scale"], p["ln_x_bias"])
    y = y.reshape(b, 1, d).astype(x.dtype) * g
    out = layers.dense(p["o"], y)
    return out, {"wkv": state, "x_last": x[:, 0]}


def rwkv_channel_mix(p, x, *, x_prev=None, stats: Optional[dict] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """relu^2 channel mix with token shift. Returns (y, x_last)."""
    if x_prev is None:
        x_prev = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    xx = x_prev - x
    xk = x + xx * p["maa_k"].astype(x.dtype)
    xr = x + xx * p["maa_r"].astype(x.dtype)
    kk = layers.activation(layers.dense(p["key"], xk, stats=stats,
                                        name="key"), "relu2")
    kv = layers.dense(p["value"], kk, stats=stats, name="value")
    rr = jax.nn.sigmoid(layers.dense(p["recept"], xr, stats=stats,
                                     name="recept"))
    return rr * kv, x[:, -1]


# ----------------------------------------------------------------------- #
# Mamba (selective SSM)
# ----------------------------------------------------------------------- #
def mamba_init(key, cfg: ModelConfig, *, dtype) -> Dict:
    mc = cfg.mamba
    d = cfg.d_model
    di = mc.expand * d
    dt_rank = mc.dt_rank or -(-d // 16)
    ks = jax.random.split(key, 5)
    a = jnp.broadcast_to(jnp.arange(1, mc.d_state + 1, dtype=jnp.float32),
                         (di, mc.d_state))
    return {
        "in": layers.dense_init(ks[0], d, 2 * di, dtype=dtype),
        "conv_w": jax.random.normal(ks[1], (mc.d_conv, di), jnp.float32)
        * (1.0 / math.sqrt(mc.d_conv)),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": layers.dense_init(ks[2], di, dt_rank + 2 * mc.d_state,
                                    dtype=dtype),
        "dt": layers.dense_init(ks[3], dt_rank, di, dtype=dtype, bias=True),
        "A_log": jnp.log(a),
        "D": jnp.ones((di,), jnp.float32),
        "out": layers.dense_init(ks[4], di, d, dtype=dtype,
                                 scale=1.0 / math.sqrt(di)),
    }


def _mamba_ssm_inputs(p, xc, z, cfg, stats):
    """Common data-dependent SSM parameters.  xc: post-conv (B,S,di)."""
    mc = cfg.mamba
    dt_rank = mc.dt_rank or -(-cfg.d_model // 16)
    xdb = layers.dense(p["x_proj"], xc, stats=stats, name="x_proj")
    dt, bmat, cmat = jnp.split(
        xdb, [dt_rank, dt_rank + mc.d_state], axis=-1)
    dt = jax.nn.softplus(layers.dense(p["dt"], dt, stats=stats,
                                      name="dt").astype(jnp.float32))
    a = -jnp.exp(p["A_log"])                            # (di, n)
    da = jnp.exp(dt[..., None] * a)                     # (B,S,di,n)
    dbx = (dt * xc.astype(jnp.float32))[..., None] \
        * bmat.astype(jnp.float32)[..., None, :]        # (B,S,di,n)
    return da, dbx, cmat.astype(jnp.float32)


def _causal_conv(p, x, cfg, *, buf=None):
    """Depthwise causal conv over (B,S,di). buf: (B, d_conv-1, di) history."""
    mc = cfg.mamba
    if buf is None:
        pad = jnp.zeros((x.shape[0], mc.d_conv - 1, x.shape[-1]), x.dtype)
    else:
        pad = buf.astype(x.dtype)
    xe = jnp.concatenate([pad, x], axis=1)
    out = sum(
        xe[:, i:i + x.shape[1]] * p["conv_w"][i].astype(x.dtype)
        for i in range(mc.d_conv)
    ) + p["conv_b"].astype(x.dtype)
    new_buf = xe[:, -(mc.d_conv - 1):] if mc.d_conv > 1 else pad
    return jax.nn.silu(out), new_buf


def mamba_apply(p, x, cfg, *, stats: Optional[dict] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence selective scan.  Returns (y, final_cache)."""
    mc = cfg.mamba
    b, s, _ = x.shape
    di = mc.expand * cfg.d_model
    xz = layers.dense(p["in"], x, stats=stats, name="in")
    x1, z = jnp.split(xz, 2, axis=-1)
    xc, conv_buf = _causal_conv(p, x1, cfg)
    da, dbx, cmat = _mamba_ssm_inputs(p, xc, z, cfg, stats)

    def step(h, t):
        da_t, dbx_t, c_t = t
        h = da_t * h + dbx_t                            # (B,di,n)
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    h0 = jnp.zeros((b, di, mc.d_state), jnp.float32)
    hT, ys = jax.lax.scan(
        step, h0,
        (da.transpose(1, 0, 2, 3), dbx.transpose(1, 0, 2, 3),
         cmat.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2)                           # (B,S,di)
    y = y + p["D"] * xc.astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = layers.dense(p["out"], y, stats=stats, name="out")
    return out, {"h": hT, "conv": conv_buf}


def mamba_decode(p, x, cfg, cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token step. cache: {"h": (B,di,n), "conv": (B,d_conv-1,di)}."""
    xz = layers.dense(p["in"], x)
    x1, z = jnp.split(xz, 2, axis=-1)
    xc, conv_buf = _causal_conv(p, x1, cfg, buf=cache["conv"])
    da, dbx, cmat = _mamba_ssm_inputs(p, xc, z, cfg, None)
    h = da[:, 0] * cache["h"] + dbx[:, 0]
    y = jnp.einsum("bdn,bn->bd", h, cmat[:, 0])[:, None]
    y = y + p["D"] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = layers.dense(p["out"], y)
    return out, {"h": h, "conv": conv_buf}
