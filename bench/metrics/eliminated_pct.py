"""Fused window: share of the completed operations served by the
elimination pre-pass, 2 x `SmartPQStats.eliminated` / ops completed."""


def read(view):
    if view.ops_completed <= 0:
        return None
    return 100.0 * 2 * view.counters["eliminated"] / view.ops_completed
