"""Kernels: the paged decode attention kernel's share of its roofline in the
traced window (%): the least time its calls could take -- per layer call the
larger of FLOPs over peak and bytes over HBM bandwidth, for the pages of the
active slots' real lengths -- over the kernel's summed device time."""

from chipbench import flops
from chipbench.metrics import spans

KERNEL = ("paged_decode_attention",)  # the Pallas call's instruction name


def read(res):
    if res.trace is None or res.hw is None:
        return None
    t = res.trace.kernel_seconds(KERNEL)
    if t <= 0:
        return None
    least = 0.0
    for s in spans(res, "decode"):
        f, b = flops.paged_attention_call(res.model, s.lengths, res.serving["kv_page_size"])
        least += res.model["num_layers"] * flops.roofline_seconds(f, b, res.hw)
    return 100.0 * least / t
