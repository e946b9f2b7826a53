"""Fused window: 95th percentile of the device time of one execution of
the window program (the program that takes most device time in the
traced segment), from the trace's module events."""


def read(view):
    t = view.trace
    if t is None or not t.modules:
        return None
    durations = max(t.modules.values(), key=sum)
    durations = sorted(durations)
    return 1e3 * durations[min(len(durations) - 1,
                               int(0.95 * len(durations)))]
