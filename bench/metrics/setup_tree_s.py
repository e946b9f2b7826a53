"""Set-up: host seconds to build the queue, its decision tree trained in
every process (`harness.build_queue`)."""


def read(view):
    return view.setup.get("tree")
