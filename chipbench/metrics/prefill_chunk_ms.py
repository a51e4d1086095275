"""Prefill: mean duration of the window's ``janus.prefill.chunk`` spans, one
chunk call from its dispatch to its logits ready on the device (ms)."""

from chipbench.program_spans import named


def read(res):
    chunks = named(res, "janus.prefill.chunk")
    if not chunks:
        return None
    return 1e3 * sum(s.seconds for s in chunks) / len(chunks)
