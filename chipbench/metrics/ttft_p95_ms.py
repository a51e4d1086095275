"""95th percentile, over every request due (open loop) or sent (closed loop)
inside the window, of the time from then to its first token on the host (ms).
A request without a first token when the window closes counts with the time
it has waited."""

from chipbench.harness import percentile
from chipbench.metrics import in_window


def read(res):
    w = res.window
    waits = []
    for s in w.served:
        if not in_window(res, s.sent):
            continue
        first = s.stamps[0] if s.stamps and s.stamps[0] <= w.t_close else w.t_close
        waits.append(first - s.sent)
    return percentile(waits, 95) * 1e3 if waits else None
