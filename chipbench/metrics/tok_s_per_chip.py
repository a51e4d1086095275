"""Output tokens that reached the host inside the window, per second of the
window, per chip of the cell."""

from chipbench.metrics import in_window


def read(res):
    n = sum(1 for s in res.window.served for t in s.stamps if in_window(res, t))
    return n / res.window.seconds / res.chips
