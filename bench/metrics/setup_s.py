"""Process start to window start: imports, weights, compile or cache
load, warm-up and the ramp."""


def read(view):
    return view.setup_s
