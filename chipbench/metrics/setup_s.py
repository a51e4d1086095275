"""Process start to the window's first timed request: weights, engine,
compilation or compile-cache loads, warm-up and the traffic's lead-in."""


def read(res):
    return res.setup_s
