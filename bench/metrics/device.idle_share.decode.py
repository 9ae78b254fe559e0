"""Share of the traced decode window in which no operation ran on the
device: 1 - (union of operation intervals) / window, averaged over the
chips, as ``device.idle_share.prim`` reads it.  Device trace."""


def read(run):
    if (run.trace is None or not run.trace.chips
            or not run.trace.window_s):
        return None
    return 100.0 * run.trace.idle_share
