"""Device: share of the traced window in which no operation ran on the
device, 1 - (union of device op intervals) / window, from the trace."""


def read(view):
    if view.trace is None:
        return None
    return view.trace.idle_pct
