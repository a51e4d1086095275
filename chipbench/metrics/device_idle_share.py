"""Device: share of the traced window in which no operation ran, averaged
over the cell's chips (%)."""


def read(res):
    if res.trace is None:
        return None
    tr = res.trace
    return 100.0 * sum(1.0 - c.busy_s / tr.window_s for c in tr.chips) / len(tr.chips)
