"""Share of device 0's busy time in MKOR's own stages: the statistic
capture (``mkor_stats``), stabilize and the factor updates
(``mkor_smw``), and the two-sided precondition with its rescale
(``mkor_precondition``), by the op_name of each op's instruction.  XLA's
copies of the factor banks carry no scope and are not counted."""
import tracefile


def read(ctx):
    return tracefile.stage_share(ctx["stages"], ("mkor_stats", "mkor_smw",
                                                 "mkor_precondition"))
