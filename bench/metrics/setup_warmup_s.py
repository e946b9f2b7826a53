"""Set-up: host seconds of the two warm-up windows on a fresh queue, which
compile or load from the persistent cache every program the timed
window runs."""


def read(view):
    return view.setup.get("warmup")
