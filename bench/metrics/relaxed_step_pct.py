"""Schedules: share of the timed window's steps run in a relaxed mode
(spray or MultiQueue), from `SmartPQStats.mode_steps`."""


def read(view):
    if view.steps <= 0:
        return None
    relaxed = sum(int(view.counters["mode_steps"][i])
                  for i in view.relaxed_modes)
    return 100.0 * relaxed / view.steps
