"""Executor host loop: mean over the window's ``janus.decode`` spans of the
span's duration less its ``janus.decode.device`` child -- the iteration's
own time plus its ``prepare``, ``sample`` and ``advance`` phases (ms)."""

import bisect

from chipbench.program_spans import named


def read(res):
    calls = named(res, "janus.decode")
    if not calls:
        return None
    device = named(res, "janus.decode.device")
    starts = [d.start_ns for d in device]
    host = 0.0
    for c in calls:
        i = bisect.bisect_left(starts, c.start_ns)
        j = bisect.bisect_right(starts, c.end_ns)
        host += c.seconds - sum(d.seconds for d in device[i:j])
    return 1e3 * host / len(calls)
