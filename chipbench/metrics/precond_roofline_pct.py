"""Roofline share of MKOR's two-sided precondition R G L: its work
counted from the algorithm (counts.precond_cost: 2 d_in d_out (d_in +
d_out) FLOPs per layer slice per step; bytes of both factors, the
gradient and the output) over the device time on device 0 of the Pallas
kernels that carry it out, found by name: the fused ``mkor_precond``
kernel and, where a slice does not fit its VMEM plan (every slice at
rwkv6-3b's widths), the ``mkor_matmul`` kernels of its fallback."""
import counts
import tracefile


def read(ctx):
    t = tracefile.kernel_seconds(ctx["events"], ctx["op_names"],
                                 (tracefile.PRECOND_KERNEL,
                                  tracefile.MATMUL_KERNEL))
    if t <= 0:
        return None
    least, bound = counts.least_time(*ctx["work"]["precond"], ctx["peaks"])
    ctx["log"](f"precond_roofline: kernels {t:.6f} s, least {least:.6f} s, "
               f"{bound} bound")
    return 100.0 * least / t
