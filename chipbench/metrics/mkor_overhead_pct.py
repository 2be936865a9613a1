"""MKOR's cost over its own first-order backend: (chunk time with MKOR -
chunk time of a LAMB-only twin) / twin, in percent.  Both are host-clock
means over the same number of whole chunks of the same cell, with the
profiler off; the twin is build_optimizer("lamb") through the same
runner (the dist step on four chips)."""


def read(ctx):
    return 100.0 * (ctx["mkor_chunk_s"] - ctx["lamb_chunk_s"]) \
        / ctx["lamb_chunk_s"]
