"""Whole step: FLOPs the window's work requires -- every prompt token
prefilled and every token decoded, at its real context, as the
configuration's arch module counts them (by default through routed top-k and
shared experts only) -- over window seconds x chips x the chip's bf16 peak
(%)."""

from chipbench.metrics import spans


def read(res):
    if res.hw is None:
        return None
    m, count = res.model, res.arch
    total = sum(count.prompt_flops(m, s.tokens) for s in spans(res, "admit"))
    for s in spans(res, "decode"):
        total += sum(count.token_flops(m, int(L), head=True) for L in s.lengths)
    return 100.0 * total / (res.window.seconds * res.chips * res.hw["peak_bf16_flops"])
