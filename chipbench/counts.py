"""Operation and byte counts, from shapes alone.

These are what the algorithm needs, not what an implementation happens
to do: a kernel's roofline share divides the least time these counts
allow on the chip by the time the kernel took.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def smw_cost(d: int, n_slices: int, itemsize: int):
    """(FLOPs, bytes) of rank-1 Sherman-Morrison updates of ``n_slices``
    (d, d) factors: the product F v (2 d^2), the scalar v.u (2 d) and the
    rank-1 write g F + c u u^T (3 d^2); each factor read once and written
    once, the vector read in float32."""
    flops = n_slices * (5.0 * d * d + 2.0 * d)
    nbytes = n_slices * (2.0 * d * d * itemsize + 4.0 * d)
    return flops, nbytes


def precond_cost(d_in: int, d_out: int, n_slices: int, factor_itemsize: int,
                 grad_itemsize: int):
    """(FLOPs, bytes) of the two-sided precondition R G L of ``n_slices``
    gradients (d_in, d_out): 2 d_in d_out (d_in + d_out) FLOPs; bytes of
    both factors, the gradient and the output in the gradient's dtype."""
    flops = n_slices * 2.0 * d_in * d_out * (d_in + d_out)
    nbytes = n_slices * (factor_itemsize * (d_in * d_in + d_out * d_out)
                         + 2.0 * grad_itemsize * d_in * d_out)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: Dict[str, float]):
    """(seconds, bound): the larger of FLOPs over the bf16 peak and bytes
    over the HBM bandwidth, and which of the two it is."""
    tc, tm = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
