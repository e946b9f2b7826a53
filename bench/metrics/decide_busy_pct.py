"""Fused window: own device time under `pq.decide` (statistics, features,
the decision tree, the mode) in % of device busy time in the traced
segment, by the program's named scopes (`bench/trace_scopes.py`)."""

from bench import trace_scopes


def read(view):
    return trace_scopes.share(view, "decide_busy_pct")
