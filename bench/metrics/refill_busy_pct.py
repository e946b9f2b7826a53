"""Tiered state: own device time under `pq.refill` (the guarded head
refill), compaction excluded, in % of device busy time in the traced
segment, by the program's named scopes (`bench/trace_scopes.py`)."""

from bench import trace_scopes


def read(view):
    return trace_scopes.share(view, "refill_busy_pct")
