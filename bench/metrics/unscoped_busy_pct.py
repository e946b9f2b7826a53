"""Device: own device time of ops under no `pq.*` scope (the scan's own
ops, statistics, carry copies, the hold body) in % of device busy time in
the traced segment (`bench/trace_scopes.py`)."""

from bench import trace_scopes


def read(view):
    return trace_scopes.share(view, "unscoped_busy_pct")
