"""Prefill: share of the window spent in the harness's admission and prefill
calls into the engine (%)."""

from chipbench.metrics import clipped_seconds


def read(res):
    return 100.0 * clipped_seconds(res, ("admit", "poll")) / res.window.seconds
