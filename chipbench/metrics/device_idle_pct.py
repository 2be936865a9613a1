"""Share of the traced window in which the device ran no operation: one
minus the union of its operations' intervals over the window, averaged
over the chips.  The window is whole chunks of the launcher's loop, so
the host's batch stacking and metrics fetch between chunks show here."""
import tracefile


def read(ctx):
    busy, window = tracefile.busy_and_window(ctx["events"])
    return 100.0 * (1.0 - busy / window)
