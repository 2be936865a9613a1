"""Roofline share of the fused Sherman-Morrison kernel
(kernels/rank1_smw.py): the least time the chip allows for the rank-1
factor updates of the traced window's phase steps (counts.smw_cost over
the bank shapes; memory bound at these sizes) over the device time of the
kernel's events on device 0, found by its operands
(``tracefile.SMW_OPERANDS``): the program gives it no name in the trace."""
import counts
import tracefile


def read(ctx):
    t = tracefile.pallas_seconds(ctx["events"], "0", smw=True)
    if t <= 0:
        return None
    least, bound = counts.least_time(*ctx["work"]["smw"], ctx["peaks"])
    ctx["log"](f"smw_roofline: kernel {t:.6f} s, least {least:.6f} s, "
               f"{bound} bound")
    return 100.0 * least / t
