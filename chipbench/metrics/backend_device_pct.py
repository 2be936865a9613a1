"""Share of device 0's busy time in the first-order backend's update
(LAMB: moments, trust-ratio norms), the ``backend`` scope."""
import tracefile


def read(ctx):
    return tracefile.stage_share(ctx["stages"], ("backend",))
