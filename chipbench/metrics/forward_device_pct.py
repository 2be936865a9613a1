"""Share of device 0's busy time in the forward pass: the ops whose
instruction's op_name has ``forward`` as its innermost stage and no
``transpose(`` before it (``tracefile.stage_seconds``), loops and calls
left out and their bodies counted."""
import tracefile


def read(ctx):
    return tracefile.stage_share(ctx["stages"], (tracefile.FORWARD,))
