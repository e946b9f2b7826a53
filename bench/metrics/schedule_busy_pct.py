"""Schedules: own device time under `pq.schedule` and its branch scopes
`pq.schedule.<mode>` in % of device busy time in the traced segment, by
the program's named scopes (`bench/trace_scopes.py`)."""

from bench import trace_scopes


def read(view):
    return trace_scopes.share(view, "schedule_busy_pct")
