"""Bytes of MKOR's optimizer state beyond the LAMB twin's, summed over
the two state trees as built for this cell (a count, from shapes)."""


def read(ctx):
    return (ctx["mkor_state_bytes"] - ctx["lamb_state_bytes"]) / 2.0 ** 30
