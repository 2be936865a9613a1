"""Roofline share of the fused Sherman-Morrison kernel
(kernels/rank1_smw.py): the least time the chip allows for the rank-1
factor updates of the traced window's phase steps (counts.smw_cost over
the bank shapes; memory bound at these sizes) over the device time on
device 0 of the Pallas kernel named ``mkor_smw``."""
import counts
import tracefile


def read(ctx):
    t = tracefile.kernel_seconds(ctx["events"], ctx["op_names"],
                                 (tracefile.SMW_KERNEL,))
    if t <= 0:
        return None
    least, bound = counts.least_time(*ctx["work"]["smw"], ctx["peaks"])
    ctx["log"](f"smw_roofline: kernel {t:.6f} s, least {least:.6f} s, "
               f"{bound} bound")
    return 100.0 * least / t
