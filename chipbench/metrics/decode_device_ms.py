"""Executor device step: mean duration of the window's
``janus.decode.device`` spans, from the step's dispatch to its logits ready
on the device, the host waiting (ms)."""

from chipbench.program_spans import named


def read(res):
    steps = named(res, "janus.decode.device")
    if not steps:
        return None
    return 1e3 * sum(s.seconds for s in steps) / len(steps)
