"""Roofline share of MKOR's two-sided precondition R G L: its work
counted from the algorithm (counts.precond_cost: 2 d_in d_out (d_in +
d_out) FLOPs per layer slice per step; bytes of both factors, the
gradient and the output) over the device time of the operations that
carry it out on device 0.  At these widths the fused kernel does not fit
its VMEM plan and the work runs as the fallback pallas_matmul kernels:
every Pallas call that is not the SMW kernel.  The program gives the
precondition no named scope yet; when it has one, read the scope here."""
import counts
import tracefile


def read(ctx):
    t = tracefile.pallas_seconds(ctx["events"], "0", smw=False)
    if t <= 0:
        return None
    least, bound = counts.least_time(*ctx["work"]["precond"], ctx["peaks"])
    ctx["log"](f"precond_roofline: kernels {t:.6f} s, least {least:.6f} s, "
               f"{bound} bound")
    return 100.0 * least / t
