"""Tiered state: guarded head refills (`SmartPQStats.head_refills`) per
1000 steps of the timed window."""


def read(view):
    if view.steps <= 0:
        return None
    return 1e3 * view.counters["head_refills"] / view.steps
