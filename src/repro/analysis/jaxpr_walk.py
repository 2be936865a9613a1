"""Recursive jaxpr walkers for the linter.

Works directly on ``jax.make_jaxpr`` output (no compile needed), so the
dtype and comm checkers run in milliseconds even for bert-large.  The
walk descends every sub-jaxpr it finds in ``eqn.params`` — scan/while
bodies, cond branches, pjit/shard_map/custom-vjp inner jaxprs — and
tags each record with its structural context:

* ``gated``   — inside a ``cond`` branch.  MKOR's inversion work (the
  O(d^2) owner gathers, the SMW refresh) is phase-gated behind
  ``lax.cond``; anything NOT gated executes every step and must obey
  the O(d) wire contract.
* ``in_loop`` — inside a scan/while body (payload repeats per trip).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# jaxpr-level collective primitives (lax.psum -> "psum",
# lax.psum_scatter -> "reduce_scatter", ...).  Under jax.shard_map with
# check_vma=True a psum of device-varying values lowers to
# "psum_invariant" (plus "pvary" markers on its inputs); older jax wrote
# "psum2"/"pmax2"/"pmin2" (plus "pbroadcast").  They are the same wire
# traffic, so the walker records them under the plain name (_canon_prim).
_RENAMED = {"psum_invariant": "psum", "psum2": "psum", "pmax2": "pmax",
            "pmin2": "pmin"}
COLLECTIVE_PRIMS = ("psum", "all_gather", "reduce_scatter", "all_to_all",
                    "ppermute", "pmax", "pmin", "all_gather_invariant",
                    *_RENAMED)


def _canon_prim(name: str) -> str:
    return _RENAMED.get(name, name)

# primitives that merely re-arrange data; producer-chain walks look
# through them when tracing a collective payload back to its origin
_TRANSPARENT = ("reshape", "transpose", "broadcast_in_dim", "squeeze",
                "slice", "concatenate", "copy", "convert_element_type",
                "mul", "add", "div", "pbroadcast", "pvary")


def _aval_info(v) -> Tuple[Tuple[int, ...], str, int]:
    """(shape, dtype name, bytes) of a jaxpr atom; ((), '?', 0) if opaque."""
    aval = getattr(v, "aval", None)
    shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return shape, "?", 0
    n = int(np.prod(shape)) if shape else 1
    return shape, str(dtype), n * np.dtype(dtype).itemsize


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


@dataclass(frozen=True)
class JaxprCollective:
    prim: str                       # psum / all_gather / ...
    axes: Tuple[Any, ...]           # axis names from eqn params
    shapes: Tuple[Tuple[int, ...], ...]   # operand shapes
    dtypes: Tuple[str, ...]         # operand dtype names
    payload_bytes: int              # sum of operand bytes
    gated: bool                     # inside a cond branch
    in_loop: bool                   # inside a scan/while body
    bf16_origin: bool               # payload produced by bf16->f32 convert
    int8_origin: bool               # payload is int8 or int8->wider convert
    path: str                       # breadcrumb, e.g. "shard_map/cond[1]"


@dataclass(frozen=True)
class ConvertRecord:
    from_dtype: str
    to_dtype: str
    shape: Tuple[int, ...]
    gated: bool
    path: str


@dataclass(frozen=True)
class EpsGuard:
    prim: str                       # max (jnp.maximum lowers to max)
    eps: float                      # the literal floor value
    dtype: str                      # dtype the guard computes in
    path: str


@dataclass(frozen=True)
class ScanRecord:
    length: Optional[int]
    num_carry: int
    num_consts: int
    path: str


@dataclass
class WalkResult:
    collectives: List[JaxprCollective] = field(default_factory=list)
    converts: List[ConvertRecord] = field(default_factory=list)
    f64_sites: List[str] = field(default_factory=list)   # paths w/ float64
    eps_guards: List[EpsGuard] = field(default_factory=list)
    scans: List[ScanRecord] = field(default_factory=list)
    prim_counts: Dict[str, int] = field(default_factory=dict)


def _sub_jaxprs(eqn):
    """(key, jaxpr) pairs for every sub-jaxpr in an eqn's params."""
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for i, item in enumerate(vals):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                key = k if len(vals) == 1 else f"{k}[{i}]"
                yield key, inner


def _bf16_origin(jaxpr, var, depth: int = 6) -> bool:
    """True if ``var`` (an f32 payload) traces back, through transparent
    ops, to a convert from bfloat16 — i.e. the wire format is bf16 and
    the f32 is only the reduction accumulator width."""
    if depth <= 0 or _is_literal(var):
        return False
    producer = None
    for eqn in jaxpr.eqns:
        if any(ov is var for ov in eqn.outvars):
            producer = eqn
            break
    if producer is None:
        return False
    name = producer.primitive.name
    if name == "convert_element_type":
        src = producer.invars[0]
        _, dt, _ = _aval_info(src)
        if dt == "bfloat16":
            return True
        return _bf16_origin(jaxpr, src, depth - 1)
    if name in _TRANSPARENT or name == "pjit":
        return any(_bf16_origin(jaxpr, iv, depth - 1)
                   for iv in producer.invars if not _is_literal(iv))
    return False


def _int8_origin(jaxpr, var, depth: int = 6) -> bool:
    """True if ``var`` is int8 on the wire, or traces back through
    transparent ops to an int8 source — the quantized owner-gather
    contract (DESIGN.md §16): factor codes ship as int8 and any widening
    is only the masked-psum accumulator."""
    _, dt, _ = _aval_info(var)
    if dt == "int8":
        return True
    if depth <= 0 or _is_literal(var):
        return False
    producer = None
    for eqn in jaxpr.eqns:
        if any(ov is var for ov in eqn.outvars):
            producer = eqn
            break
    if producer is None:
        return False
    name = producer.primitive.name
    if name == "convert_element_type":
        src = producer.invars[0]
        _, sdt, _ = _aval_info(src)
        if sdt == "int8":
            return True
        return _int8_origin(jaxpr, src, depth - 1)
    if name in _TRANSPARENT or name == "pjit" \
            or name == "dynamic_update_slice":
        return any(_int8_origin(jaxpr, iv, depth - 1)
                   for iv in producer.invars if not _is_literal(iv))
    return False


def walk(closed_jaxpr) -> WalkResult:
    """Collect all lint-relevant records from a (closed) jaxpr."""
    res = WalkResult()
    inner = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk(inner, res, gated=False, in_loop=False, path="")
    return res


def _walk(jaxpr, res: WalkResult, gated: bool, in_loop: bool,
          path: str) -> None:
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        _, dt, _ = _aval_info(v)
        if dt in ("float64", "complex128", "int64") and dt == "float64":
            res.f64_sites.append(path or "<entry>")
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        res.prim_counts[name] = res.prim_counts.get(name, 0) + 1

        if name in COLLECTIVE_PRIMS:
            shapes, dtypes, total = [], [], 0
            for iv in eqn.invars:
                s, d, b = _aval_info(iv)
                shapes.append(s)
                dtypes.append(d)
                total += b
            axes = eqn.params.get("axes",
                                  eqn.params.get("axis_name", ()))
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            res.collectives.append(JaxprCollective(
                prim=_canon_prim(name), axes=tuple(axes),
                shapes=tuple(shapes),
                dtypes=tuple(dtypes), payload_bytes=total, gated=gated,
                in_loop=in_loop,
                bf16_origin=any(_bf16_origin(jaxpr, iv)
                                for iv in eqn.invars
                                if not _is_literal(iv)),
                int8_origin=any(_int8_origin(jaxpr, iv)
                                for iv in eqn.invars
                                if not _is_literal(iv)),
                path=path or "<entry>"))

        elif name == "convert_element_type":
            s_in, d_in, _ = _aval_info(eqn.invars[0])
            _, d_out, _ = _aval_info(eqn.outvars[0])
            res.converts.append(ConvertRecord(d_in, d_out, s_in, gated,
                                              path or "<entry>"))
            if d_out == "float64":
                res.f64_sites.append(path or "<entry>")

        elif name in ("max", "maximum"):
            for iv in eqn.invars:
                if _is_literal(iv):
                    try:
                        val = float(np.asarray(iv.val))
                    except (TypeError, ValueError):
                        continue
                    if 0.0 < val <= 1e-12:
                        _, dt, _ = _aval_info(eqn.outvars[0])
                        res.eps_guards.append(EpsGuard(
                            name, val, dt, path or "<entry>"))

        if name == "scan":
            res.scans.append(ScanRecord(
                length=eqn.params.get("length"),
                num_carry=eqn.params.get("num_carry", 0),
                num_consts=eqn.params.get("num_consts", 0),
                path=path or "<entry>"))

        # any float64 among the eqn's avals (canonicalized away unless
        # x64 is enabled, so a hit means a genuine f64 leak)
        for v in list(eqn.invars) + list(eqn.outvars):
            _, dt, _ = _aval_info(v)
            if dt == "float64":
                res.f64_sites.append(f"{path or '<entry>'}/{name}")
                break

        for key, sub in _sub_jaxprs(eqn):
            sub_gated = gated or name == "cond"
            sub_loop = in_loop or name in ("scan", "while")
            # a cond's first branch is the "no-op" arm of lax.cond in
            # jaxpr ordering; both are gated either way
            sub_path = f"{path}/{name}:{key}" if path else f"{name}:{key}"
            _walk(sub, res, sub_gated, sub_loop, sub_path)
