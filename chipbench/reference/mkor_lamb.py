"""Plain float32 reference of MKOR over LAMB (Algorithm 1, rank 1,
synchronous schedule), written from the equations alone; it trains any of
the plain model references (``loss_and_stats(params, batch, sizes)``).

It imports nothing of the program under test.  Every product runs in
float32 under ``default_matmul_precision("highest")``.  Parameters are
held in the dtype the configuration states (bfloat16) and each step's
update is rounded onto them, as training in that dtype does; optimizer
moments and Kronecker factors stay float32.

For each dense layer ({"w", "probe"}) outside ``exclude``, with input mean
a = E[x] and probe gradient g = E[dL/dy]:
    on its phase step (count % inv_freq == phase of its shape bucket):
        F <- stabilize(F);  F <- gamma F + c (F v)(F v)^T,
        c = (1 - gamma) / (gamma^2 (1 + gamma (1 - gamma) v^T F v)),
        v = g for L (d_out x d_out), a for R (d_in x d_in)
    dW = R G L, rescaled to the Frobenius norm of G per layer slice
stabilize: where max|F| > threshold, F <- zeta F + (1 - zeta) I, then
scaled back to the threshold if still above it.  Shape buckets are the
layers of one (d_in, d_out, depth) signature, ordered by the id string
"{d_in}x{d_out}_s{depth}"; bucket i has phase i mod inv_freq.  Probes are
never stepped.  LAMB then steps every leaf, its trust ratio over the
whole (depth-stacked) leaf.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference.layers import stored

F32 = jnp.float32


def dense_paths(tree) -> List[tuple]:
    """Paths of the dense dicts ({"w", "probe"}) MKOR preconditions."""
    out = []

    def walk(node, path):
        if isinstance(node, dict) and "w" in node and "probe" in node:
            out.append(path)
            return
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], path + (k,))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, path + (i,))

    walk(tree, ())
    return out


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def path_key(path) -> str:
    return "/".join(str(k) for k in path)


def mkor_layers(params, opt) -> Dict[str, Dict]:
    """{layer key: {"path", "phase", "d_in", "d_out"}} for every
    preconditioned layer, with its bucket's phase."""
    layers = {}
    for path in dense_paths(params):
        if any(str(k) in opt["exclude"] for k in path):
            continue
        w = _get(params, path)["w"]
        d_in, d_out = w.shape[-2:]
        if not (opt["min_factor_dim"] <= min(d_in, d_out)
                and max(d_in, d_out) <= opt["max_factor_dim"]):
            continue
        bucket = f"{d_in}x{d_out}" + (
            "_s" + "x".join(map(str, w.shape[:-2])) if w.ndim > 2 else "")
        layers[path_key(path)] = {"path": path, "bucket": bucket,
                                  "d_in": d_in, "d_out": d_out}
    order = sorted({v["bucket"] for v in layers.values()})
    for v in layers.values():
        v["phase"] = order.index(v["bucket"]) % opt["inv_freq"]
    return layers


def _stabilize(f, thr, zeta):
    n = jnp.max(jnp.abs(f))
    f = jnp.where(n > thr, zeta * f + (1 - zeta) * jnp.eye(f.shape[-1]), f)
    n2 = jnp.max(jnp.abs(f))
    return jnp.where(n2 > thr, f * (thr / jnp.maximum(n2, 1e-30)), f)


def _smw(f, v, gamma):
    u = f @ v
    c = (1 - gamma) / (gamma ** 2 * (1 + gamma * (1 - gamma) * (v @ u)))
    return gamma * f + c * jnp.outer(u, u)


def _precondition(l, r, g):
    d = r @ g @ l
    gn = jnp.sqrt(jnp.sum(g * g))
    return d * (gn / jnp.maximum(jnp.sqrt(jnp.sum(d * d)), 1e-30))


def init_state(params, opt):
    layers = mkor_layers(params, opt)
    factors = {}
    for key, v in layers.items():
        lead = _get(params, v["path"])["w"].shape[:-2]
        factors[key] = {
            "l": jnp.broadcast_to(jnp.eye(v["d_out"], dtype=F32),
                                  lead + (v["d_out"],) * 2),
            "r": jnp.broadcast_to(jnp.eye(v["d_in"], dtype=F32),
                                  lead + (v["d_in"],) * 2)}
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    return {"count": jnp.zeros((), jnp.int32), "factors": factors,
            "m": zeros, "v": jax.tree.map(jnp.copy, zeros)}


def _zero_probes(tree):
    if isinstance(tree, dict):
        return {k: jnp.zeros_like(v) if k == "probe" else _zero_probes(v)
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_zero_probes(v) for v in tree)
    return tree


def _set(tree, path, value):
    if not path:
        return value
    k = path[0]
    if isinstance(tree, dict):
        return {**tree, k: _set(tree[k], path[1:], value)}
    lst = list(tree)
    lst[k] = _set(tree[k], path[1:], value)
    return type(tree)(lst)


def make_step(loss_and_stats, sz, opt):
    """One reference training step: (params, state, batch) ->
    (params, state, loss)."""
    def step(params, state, batch):
        with jax.default_matmul_precision("highest"):
            (loss, stats), grads = jax.value_and_grad(
                loss_and_stats, has_aux=True)(params, batch, sz)
            grads = jax.tree.map(lambda g: g.astype(F32), grads)
            count = state["count"]
            layers = mkor_layers(params, opt)
            factors = {}
            for key, lay in layers.items():
                path = lay["path"]
                a = _get(stats, path)
                g = _get(grads, path)["probe"]
                f = state["factors"][key]
                do = count % opt["inv_freq"] == lay["phase"]
                stab = jax.vmap(partial(_stabilize, thr=opt["threshold"],
                                        zeta=opt["zeta"]))
                smw = jax.vmap(partial(_smw, gamma=opt["gamma"]))
                l = jnp.where(do, smw(stab(f["l"]), g), f["l"])
                r = jnp.where(do, smw(stab(f["r"]), a), f["r"])
                factors[key] = {"l": l, "r": r}
                gw = _get(grads, path)["w"]
                grads = _set(grads, path, {**_get(grads, path),
                                           "w": jax.vmap(_precondition)(
                                               l, r, gw)})
            grads = _zero_probes(grads)
            b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
            t = (count + 1).astype(F32)
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                             state["m"], grads)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                             state["v"], grads)

            def upd(m, v, p):
                pf = p.astype(F32)
                r = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                r = r + opt["weight_decay"] * pf
                pn, rn = jnp.linalg.norm(pf), jnp.linalg.norm(r)
                trust = jnp.where((pn > 0) & (rn > 0),
                                  pn / jnp.maximum(rn, 1e-12), 1.0)
                trust = jnp.minimum(trust, opt["trust_clip"])
                return -opt["lr"] * trust * r

            updates = _zero_probes(jax.tree.map(upd, m, v, params))
            params = jax.tree.map(
                lambda p, u: stored(p.astype(F32) + u, p.dtype),
                params, updates)
        return params, {"count": count + 1, "factors": factors,
                        "m": m, "v": v}, loss

    return jax.jit(step, donate_argnums=(0, 1))


def offdiag_norms(f):
    """Per-slice Frobenius norm of a factor stack without its diagonal."""
    d = f.shape[-1]
    f = f.astype(F32) * (1 - jnp.eye(d, dtype=F32))
    return jnp.sqrt(jnp.sum(f * f, axis=(-2, -1)))


def run(model, weights, batches, sz, opt) -> Dict:
    """Follows ``len(batches)`` steps of ``model`` (a reference module)
    from ``weights`` (host arrays in the program's tree layout).  Returns the per-step losses and, per leaf or
    per layer slice, the numbers the comparison reads."""
    params = jax.jit(lambda w: jax.tree.map(
        lambda x: stored(x, x.dtype), w))(jax.device_put(weights))
    state = jax.jit(partial(init_state, opt=opt))(params)
    step = make_step(model.loss_and_stats, sz, opt)
    losses = []
    for batch in batches:
        params, state, loss = step(params, state, jax.device_put(batch))
        losses.append(loss)
    norms = jax.jit(lambda p, p0, s: {
        "grad": {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(v))
                 for k, v in jax.tree_util.tree_leaves_with_path(s["v"])},
        "update": {jax.tree_util.keystr(k): jnp.linalg.norm(
            a.astype(F32) - b.astype(F32)) for (k, a), b in zip(
                jax.tree_util.tree_leaves_with_path(p),
                jax.tree.leaves(p0))},
        "factor": {f"{key}/{side}": offdiag_norms(f[side])
                   for key, f in s["factors"].items() for side in "lr"}})
    out = jax.device_get(norms(params, jax.device_put(weights), state))
    out["loss"] = [float(x) for x in jax.device_get(losses)]
    out["grad"] = {k: float(v) / math.sqrt(1 - opt["b2"])
                   for k, v in out["grad"].items()}
    out["update"] = {k: float(v) for k, v in out["update"].items()}
    out["factor"] = {k: np.asarray(v, np.float64)
                     for k, v in out["factor"].items()}
    return out
