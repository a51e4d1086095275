"""Executor: mean wall time of the window's decode calls, from the call to
its tokens on the host (ms)."""

from chipbench.metrics import spans


def read(res):
    calls = spans(res, "decode")
    if not calls:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in calls) / len(calls)
