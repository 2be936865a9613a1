"""Pieces the plain references share: norms, a dense layer with its stat
tap, and the vocabulary head with the next-token loss.  Float32, except
under ``lower``, the check's control."""
import contextlib

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -2.0 ** 30
CONTROL = {"formats": None}


@contextlib.contextmanager
def lower(formats):
    """While tracing inside, the reference trains in ``formats`` = (weights
    and forward operands, backward gradients), each (exponent bits,
    mantissa bits), as fp8 training does: weights are held in the first,
    rounded after every update; each dense layer rounds its two operands
    to the first and the gradient that reaches its output to the second.
    The check's control: the reference one step below the bfloat16 the
    configuration states."""
    old = CONTROL["formats"]
    CONTROL["formats"] = formats
    try:
        yield
    finally:
        CONTROL["formats"] = old


def rounded(x, fmt):
    """``x`` rounded to the float format ``fmt`` = (exponent bits, mantissa
    bits), scaled per tensor to the format's largest finite value.  An
    explicit ReducePrecision, which the compiler keeps (a round trip
    through a narrow dtype it may drop as excess precision)."""
    e, m = fmt
    top = (2.0 - 2.0 ** -m) * 2.0 ** (2 ** (e - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jax.lax.reduce_precision(x / scale, exponent_bits=e,
                                    mantissa_bits=m) * scale


def stored(x, dtype):
    """``x`` as the weights are held: in ``dtype``, and under ``lower`` in
    its first format too."""
    if CONTROL["formats"] is not None:
        x = rounded(x.astype(F32), CONTROL["formats"][0])
    return x.astype(dtype)


def _matmul(x, w):
    if CONTROL["formats"] is None:
        return x @ w
    fwd, bwd = CONTROL["formats"]

    @jax.custom_vjp
    def mm(x, w):
        return rounded(x, fwd) @ rounded(w, fwd)

    def mm_fwd(x, w):
        xq, wq = rounded(x, fwd), rounded(w, fwd)
        return xq @ wq, (xq, wq)

    def mm_bwd(res, dy):
        xq, wq = res
        d = rounded(dy, bwd)
        return d @ wq.T, jnp.einsum("...i,...o->io", xq, d)

    mm.defvjp(mm_fwd, mm_bwd)
    return mm(x, w)


def norm(p, x, kind, eps):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def dense(p, x, stats, name):
    """y = x W + probe (+ b); records the input's token mean E[a].  The
    zero ``probe``'s gradient is the token sum of dL/dy: MKOR's E[g] for a
    mean loss."""
    stats[name] = jnp.mean(x.reshape(-1, x.shape[-1]), axis=0)
    y = _matmul(x, p["w"]) + p["probe"]
    return y + p["b"] if "b" in p else y


def head_loss(p32, x, batch, sz, stats):
    """Final norm, vocabulary head (or the tied embedding), padded columns
    masked, mean next-token cross-entropy.  Returns (loss, stats)."""
    x = norm(p32["final_norm"], x, sz["norm"], sz["eps"])
    if "lm_head" in p32:
        hs = {}
        logits = dense(p32["lm_head"], x, hs, "lm_head")
        stats["lm_head"] = hs["lm_head"]
    else:
        logits = x @ p32["embed"]["table"].T
    col = jnp.arange(logits.shape[-1])
    logits = jnp.where(col < sz["vocab"], logits, NEG)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - lab), stats
