"""Tiered state: own device time under `pq.compact` (the tail compaction,
from an insert or a refill) in % of device busy time in the traced
segment, by the program's named scopes (`bench/trace_scopes.py`)."""

from bench import trace_scopes


def read(view):
    return trace_scopes.share(view, "compact_busy_pct")
