"""Fused window: own device time under `pq.presort` and `pq.eliminate`
(the elimination pre-pass) in % of device busy time in the traced
segment, by the program's named scopes (`bench/trace_scopes.py`)."""

from bench import trace_scopes


def read(view):
    return trace_scopes.share(view, "eliminate_busy_pct")
