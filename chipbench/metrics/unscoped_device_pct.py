"""Share of device 0's busy time under none of the training step's
stages: the ops whose instruction carries no stage in its op_name, XLA's
copies of the factor banks around the phase step's conditional most of
them (``tracefile.stage_seconds``)."""
import tracefile


def read(ctx):
    return tracefile.stage_share(ctx["stages"], (tracefile.UNSCOPED,))
