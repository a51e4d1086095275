"""Aggregates the per-architecture config modules into registries.

Each assigned architecture lives in its own module (one ``<arch>.py`` per
architecture, per the framework layout) and exposes a single ``CONFIG``.
"""

from __future__ import annotations

from typing import Dict

from repro.configs.base import ModelConfig
from repro.configs.gemma_7b import CONFIG as GEMMA_7B
from repro.configs.yi_34b import CONFIG as YI_34B
from repro.configs.pixtral_12b import CONFIG as PIXTRAL_12B
from repro.configs.falcon_mamba_7b import CONFIG as FALCON_MAMBA_7B
from repro.configs.gemma2_2b import CONFIG as GEMMA2_2B
from repro.configs.phi4_mini_3_8b import CONFIG as PHI4_MINI_38B
from repro.configs.qwen2_moe_a2_7b import CONFIG as QWEN2_MOE_A27B
from repro.configs.zamba2_2_7b import CONFIG as ZAMBA2_27B
from repro.configs.whisper_tiny import CONFIG as WHISPER_TINY
from repro.configs.phi3_5_moe_42b import CONFIG as PHI35_MOE_42B
from repro.configs.dsv2_lite import CONFIG as DSV2_LITE
from repro.configs.dsv2_lite_4l import CONFIG as DSV2_LITE_4L
from repro.configs.dsv2 import CONFIG as DSV2
from repro.configs.scaled_ds_1 import CONFIG as SCALED_DS_1
from repro.configs.scaled_ds_2 import CONFIG as SCALED_DS_2

ASSIGNED: Dict[str, ModelConfig] = {
    c.name: c
    for c in (
        GEMMA_7B,
        YI_34B,
        PIXTRAL_12B,
        FALCON_MAMBA_7B,
        GEMMA2_2B,
        PHI4_MINI_38B,
        QWEN2_MOE_A27B,
        ZAMBA2_27B,
        WHISPER_TINY,
        PHI35_MOE_42B,
    )
}

PAPER_MODELS: Dict[str, ModelConfig] = {
    c.name: c for c in (DSV2_LITE, DSV2_LITE_4L, DSV2, SCALED_DS_1, SCALED_DS_2)
}

REGISTRY: Dict[str, ModelConfig] = {**ASSIGNED, **PAPER_MODELS}
