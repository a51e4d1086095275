"""Scheduler: mean share of the batch slots active in the window's decode
calls (%)."""

from chipbench.metrics import spans


def read(res):
    calls = spans(res, "decode")
    if not calls:
        return None
    return 100.0 * sum(s.active for s in calls) / (len(calls) * res.serving["max_batch"])
