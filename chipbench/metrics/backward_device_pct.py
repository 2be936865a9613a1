"""Share of device 0's busy time in the backward pass: the ops of the
forward scope under ``transpose(`` (``tracefile.stage_seconds``), the
recomputed forward of a checkpointed scan among them."""
import tracefile


def read(ctx):
    return tracefile.stage_share(ctx["stages"], (tracefile.BACKWARD,))
