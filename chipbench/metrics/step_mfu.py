"""Whole step: FLOPs the window's work requires -- every prompt token
prefilled and every token decoded, at its real context, through routed top-k
and shared experts only -- over window seconds x chips x the chip's bf16 peak
(%)."""

from chipbench import flops
from chipbench.metrics import spans


def read(res):
    if res.hw is None:
        return None
    m = res.model
    total = sum(flops.prompt_flops(m, s.tokens) for s in spans(res, "admit"))
    for s in spans(res, "decode"):
        total += sum(flops.token_flops(m, int(L), head=True) for L in s.lengths)
    return 100.0 * total / (res.window.seconds * res.chips * res.hw["peak_bf16_flops"])
