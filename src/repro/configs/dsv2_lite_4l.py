"""dsv2-lite at its published widths, cut in depth only: the chip configuration.

Every width is dsv2-lite's (d_model 2048, 16 heads × 128, 64 routed experts of
f 1408 with 2 shared, top-6, the full 102,400-token vocabulary).  Only depth is
cut, from 27 layers to 4, so the model fits one TPU v5e chip (16 GB) beside
its KV cache and the disaggregated executor's expert-slot copies:

  parameters     ~2.56 B  → ~5.1 GB in bf16
  expert slots   2 instances × 33 slots × 4 layers → ~4.6 GB more (aliased disagg)
  KV cache       32 KiB per token → 16 slots × 2,048 tokens = 1 GiB
"""

import dataclasses

from repro.configs.dsv2_lite import CONFIG as DSV2_LITE

CONFIG = dataclasses.replace(
    DSV2_LITE,
    name="dsv2-lite-4l",
    num_layers=4,
    cuts=(("num_layers", DSV2_LITE.num_layers, 4),),
    source=DSV2_LITE.source + "; depth cut 27 → 4 layers to fit one TPU v5e chip",
)
