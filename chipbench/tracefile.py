"""From a profiler trace to the few event lists the metrics read.

``load`` reduces the JAX profiler's ``.xplane.pb`` to plain lists:

    {"devices": {"0": [[op name, start ns, duration ns], ...], ...},
     "host":    [[span name, start ns, duration ns], ...]}

``devices`` holds the operations that ran on each TPU (the "XLA Ops"
line of its plane); ``host`` holds the benchmark's own spans
(``HOST_SPANS``).  Both are on the profiler's one clock.  The same lists,
saved as JSON, are the recorded trace the tests read.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

HOST_SPANS = ("stack_batches", "dispatch", "device_get")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# An operation's event is named by its HLO text, "%name = <shape> kind(...)".
# A Pallas kernel is a custom call to "tpu_custom_call"; loops and calls
# also have events of their own that span their bodies.
HLO = re.compile(r"%?([\w.\-]+) = .*? ([\w\-]+)\(")
PALLAS = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("while", "conditional", "call")
# The fused SMW kernel's operands: the bank, then the vector twice, (d, 1)
# in float32.  The program names no kernel in the trace, so the readers
# tell the SMW kernel from the precondition's matmuls by this signature.
SMW_OPERANDS = re.compile(r"custom-call\(.*?f32\[[\d,]+,1\].*?"
                          r"f32\[[\d,]+,1\]")

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


def pallas_seconds(events: Events, device: str, smw: bool) -> float:
    """Summed duration of the Pallas kernels that are (``smw``) or are not
    the fused SMW kernel."""
    return sum(d for n, _, d in events["devices"][device]
               if PALLAS in n
               and (SMW_OPERANDS.search(n) is not None) == smw) * 1e-9


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
