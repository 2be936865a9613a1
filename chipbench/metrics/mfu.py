"""Model FLOP/s utilization of the whole step: the forward and backward
FLOPs the model needs per token (``flops_per_token`` of the
configuration's reference module: no recompute, no optimizer) times the
tokens trained in the traced window, over the window's device-clock span,
over chips times the bf16 peak.  MKOR's work is cost here, not
utilization."""
import statistics

import tracefile


def read(ctx):
    ev = ctx["events"]
    span = statistics.mean(tracefile.device_span(ev, d)
                           for d in sorted(ev["devices"]))
    flops = ctx["tokens"] * ctx["flops_per_token"]
    return 100.0 * flops / span / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
