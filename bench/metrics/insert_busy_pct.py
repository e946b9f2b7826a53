"""Tiered state: own device time under `pq.insert`, compaction excluded,
in % of device busy time in the traced segment, by the program's named
scopes (`bench/trace_scopes.py`)."""

from bench import trace_scopes


def read(view):
    return trace_scopes.share(view, "insert_busy_pct")
