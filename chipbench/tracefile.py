"""From a profiler trace to the few event lists the metrics read.

``load`` reduces the JAX profiler's ``.xplane.pb`` to plain lists:

    {"devices": {"0": [[op name, start ns, duration ns], ...], ...},
     "host":    [[span name, start ns, duration ns], ...]}

``devices`` holds the operations that ran on each TPU (the "XLA Ops"
line of its plane); ``host`` holds the benchmark's own spans
(``HOST_SPANS``).  Both are on the profiler's one clock.  The same lists,
saved as JSON, are the recorded trace the tests read.

An op event names its instruction, not the named scopes it ran under;
those are in the ``op_name`` metadata of the instruction in the compiled
program's text.  ``op_names`` reads them from that text;
``scope_seconds`` adds up the device time under any named scopes by them
(``stage_seconds``: the training step's stages), and ``kernel_seconds``
that of Pallas kernels by their names.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Sequence, Tuple

HOST_SPANS = ("stack_batches", "dispatch", "device_get")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# An operation's event is named by its HLO text, "%name = <shape> kind(...)".
# A Pallas kernel is a custom call to "tpu_custom_call"; loops and calls
# also have events of their own that span their bodies.
HLO = re.compile(r"%?([\w.\-]+) = .*? ([\w\-]+)\(")
PALLAS = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("while", "conditional", "call")
# The program's Pallas kernels, by the names it gives them
# (``pl.pallas_call(name=...)``; its scopes.*_KERNEL, copied here).
SMW_KERNEL = "mkor_smw"
BLOCK_SMW_KERNEL = "mkor_block_smw"
PRECOND_KERNEL = "mkor_precond"
MATMUL_KERNEL = "mkor_matmul"
# JAX's transforms, as an instruction's name spells them around a
# kernel's name ("vmap(mkor_matmul)" becomes "vmap_mkor_matmul_.76").
TRANSFORM_PREFIX = re.compile(r"^(?:(?:vmap|jvp|transpose|remat|checkpoint)"
                              r"_)+")
# The training step's stages: the names of the program's named scopes
# (its scopes.STAGES, copied here: the benchmark imports nothing of the
# program), the backward pass (a forward op under ``transpose(``) and ops
# under none of them.
FORWARD = "forward"
STAGES = (FORWARD, "mkor_stats", "mkor_smw", "mkor_precondition",
          "backend", "apply", "grad_allreduce", "stat_allreduce",
          "owner_gather")
BACKWARD = "backward"
UNSCOPED = "unscoped"
SCOPE_WORD = re.compile(r"[^/()]+")
# an instruction of the compiled text and the op_name of its metadata
INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                   re.M)

Events = Dict[str, object]


def load(trace_dir) -> Events:
    import jax
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile" /
                                 "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    devices: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(m.group(1), []).extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events)
            elif not m:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def save(events: Events, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path) -> Events:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -------------------------------------------------------------------- #
def merge(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> List[List[float]]:
    """a minus b, both merged."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def window(events: Events) -> Tuple[float, float]:
    """The traced window in ns: from the first host span's start to the
    last one's end (the device's span where no host span was kept)."""
    spans = events["host"] or [e for ops in events["devices"].values()
                               for e in ops]
    return (min(s for _, s, _ in spans), max(s + d for _, s, d in spans))


def busy(events: Events, device: str) -> List[List[float]]:
    lo, hi = window(events)
    return clip(merge([(s, s + d) for _, s, d in events["devices"][device]]),
                lo, hi)


def busy_and_window(events: Events) -> Tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = window(events)
    devs = sorted(events["devices"])
    b = sum(length(busy(events, d)) for d in devs) / max(len(devs), 1)
    return b * 1e-9, (hi - lo) * 1e-9


def device_span(events: Events, device: str) -> float:
    """Seconds from the first operation's start to the last one's end."""
    ops = events["devices"][device]
    return (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) * 1e-9


def op_name(event_name: str) -> Tuple[str, str]:
    """(instruction name, kind) of an operation's HLO text; Pallas kernels
    have the kind "tpu_custom_call"."""
    m = HLO.match(event_name)
    if not m:
        return event_name[:80], ""
    kind = "tpu_custom_call" if PALLAS in event_name else m.group(2)
    return m.group(1), kind


def breakdown(events: Events, device: str = "0", top: int = 10) -> Dict:
    """The device operations that took most time (loops and calls left out,
    their bodies counted instead), and the longest idle gaps, each named by
    the host span it falls in."""
    per: Dict[str, float] = {}
    for n, _, d in events["devices"][device]:
        name, kind = op_name(n)
        if kind in CONTAINERS:
            continue
        key = f"{name} ({kind})" if kind else name
        per[key] = per.get(key, 0.0) + d * 1e-9
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    lo, hi = window(events)
    gaps = subtract([[lo, hi]], busy(events, device))
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        host = [n for n, hs, hd in events["host"] if hs <= mid < hs + hd]
        named.append([f"idle during {host[-1] if host else 'no span'}",
                      (e - s) * 1e-9])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": named}


# -------------------------------------------------------------------- #
def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: its op_name} of a compiled program's text."""
    return dict(INSTR.findall(hlo_text))


def scope_of(path: str, words: Sequence[str]) -> str:
    """The scope of an op by its ``op_name``: the innermost of ``words``
    among its path's words, ``backward_part`` of it where ``transpose``
    comes before it, ``unscoped`` for none."""
    found = SCOPE_WORD.findall(path)
    for i in range(len(found) - 1, -1, -1):
        if found[i] in words:
            if "transpose" in found[:i]:
                return backward_part(found[i])
            return found[i]
    return UNSCOPED


def backward_part(word: str) -> str:
    """The key of the time under ``word`` in the backward pass."""
    return f"{word}.{BACKWARD}"


def stage_of(path: str) -> str:
    """The stage of an op by its ``op_name``: ``scope_of`` over
    ``STAGES``, where only the forward scope has a backward part
    (``backward``); the other stages keep their ops under ``transpose(``."""
    scope = scope_of(path, STAGES)
    if scope == backward_part(FORWARD):
        return BACKWARD
    return scope.split(".")[0]


def _seconds(events: Events, names: Dict[str, str], device: str,
             key: Callable[[str], str]) -> Dict[str, float]:
    """Device seconds on ``device`` by ``key`` of each op's ``op_name``
    in ``names``; loops and calls are left out and their bodies counted."""
    out: Dict[str, float] = {}
    for n, _, d in events["devices"][device]:
        name, kind = op_name(n)
        if kind in CONTAINERS:
            continue
        k = key(names.get(name, ""))
        out[k] = out.get(k, 0.0) + d * 1e-9
    return out


def scope_seconds(events: Events, names: Dict[str, str],
                  words: Sequence[str], device: str = "0"
                  ) -> Dict[str, float]:
    """Device seconds on ``device`` under each of the named scopes
    ``words``, each op counted once by ``scope_of`` the ``op_name`` of its
    instruction in ``names`` (``op_names``): keys are the words, their
    ``backward_part``s and ``unscoped``."""
    return _seconds(events, names, device, lambda p: scope_of(p, words))


def stage_seconds(events: Events, names: Dict[str, str],
                  device: str = "0") -> Dict[str, float]:
    """Device seconds of each stage (``stage_of``) on ``device``."""
    return _seconds(events, names, device, stage_of)


def stage_share(stages: Dict, names: Sequence[str]):
    """Percent of device busy time in the stages ``names`` of ``stages``
    ({"seconds": stage_seconds(...), "busy_s": ...}); None where none of
    them ran."""
    s = sum(stages["seconds"].get(n, 0.0) for n in names)
    if s <= 0 or stages["busy_s"] <= 0:
        return None
    return 100.0 * s / stages["busy_s"]


def scope_share(ctx: Dict, words: Sequence[str]):
    """Percent of device 0's busy time under the named scopes ``words``,
    forward and backward parts together, in a traced run's ``ctx``; None
    where none of them ran."""
    seconds = scope_seconds(ctx["events"], ctx["op_names"], words)
    return stage_share({"seconds": seconds,
                        "busy_s": ctx["stages"]["busy_s"]},
                       [k for w in words for k in (w, backward_part(w))])


def kernel_of(instruction: str, path: str) -> str:
    """The name of a Pallas kernel: the innermost component of its
    ``op_name`` before ``pallas_call``, JAX's transforms around it
    stripped ("…/mkor_smw/vmap(mkor_smw)/pallas_call": "mkor_smw"); where
    the text gives no ``op_name``, its instruction's name without them and
    without its number ("vmap_mkor_matmul_.76": "mkor_matmul").  "" for
    a kernel that was given no name ("vmap()", "vmap__.88")."""
    if path:
        part = re.sub(r"/pallas_call$", "", path).rsplit("/", 1)[-1]
        while "(" in part:
            part = part[part.index("(") + 1:part.rindex(")")]
        return part
    stem = re.sub(r"\.\d+$", "", instruction)
    return TRANSFORM_PREFIX.sub("", stem).rstrip("_")


def kernel_seconds(events: Events, names: Dict[str, str],
                   kernels: Sequence[str], device: str = "0") -> float:
    """Summed device seconds on ``device`` of the Pallas kernels named in
    ``kernels`` (``kernel_of``, by ``names``: ``op_names``)."""
    total = 0.0
    for n, _, d in events["devices"][device]:
        if PALLAS not in n:
            continue
        name = op_name(n)[0]
        if kernel_of(name, names.get(name, "")) in kernels:
            total += d
    return total * 1e-9
