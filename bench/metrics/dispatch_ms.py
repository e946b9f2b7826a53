"""Host driver: mean host time for the jitted window call to return, from
the harness's own span around the call in the timed window."""


def read(view):
    if not view.dispatch_s:
        return None
    return 1e3 * sum(view.dispatch_s) / len(view.dispatch_s)
