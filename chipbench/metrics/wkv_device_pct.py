"""Share of device 0's busy time in RWKV-6's wkv recurrence: the ops
under the named scope ``wkv`` (the program's ``scopes.WKV``, copied here),
the forward and the backward parts together
(``tracefile.scope_share``).  None where no op ran under it, as in a
program without the scope."""
import tracefile

WKV = "wkv"


def read(ctx):
    return tracefile.scope_share(ctx, (WKV,))
