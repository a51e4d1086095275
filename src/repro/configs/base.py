"""Configuration system for the repro framework.

``ModelConfig`` is a frozen dataclass that can describe every architecture
family this framework supports (dense GQA transformers, MoE transformers,
Mamba-1/2 SSMs, hybrid SSM+attention stacks, encoder-decoder audio models and
VLM text backbones).  Each assigned architecture lives in its own module under
``repro.configs`` and registers itself in ``repro.configs.REGISTRY``.

``InputShape`` describes one of the assigned workload shapes (train_4k,
prefill_32k, decode_32k, long_500k).  ``input_specs`` builds
``jax.ShapeDtypeStruct`` stand-ins for every model input of a given
(config, shape) pair — these are what the multi-pod dry-run lowers against,
so they must never allocate device memory.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """A single description language for every supported architecture."""

    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- attention variants -------------------------------------------------
    rope_theta: float = 10_000.0
    use_rope: bool = True
    sliding_window: Optional[int] = None  # window for local layers
    attn_pattern: str = "global"  # "global" | "local_global" (alternating)
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    use_qk_norm: bool = False

    # --- FFN -----------------------------------------------------------------
    ffn_activation: str = "swiglu"  # swiglu | geglu | gelu

    # --- MoE -----------------------------------------------------------------
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    moe_every: int = 1  # a layer l is MoE iff num_experts>0 and l % moe_every == 0
    router_jitter: float = 0.0
    capacity_factor: float = 1.25

    # --- SSM (Mamba) ----------------------------------------------------------
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_version: int = 1  # 1 = Mamba-1 (falcon-mamba), 2 = Mamba-2 (zamba2)
    ssm_head_dim: int = 64  # Mamba-2 head dim

    # --- hybrid (zamba2-style shared attention blocks) ------------------------
    hybrid_attn_every: int = 0  # insert shared attn block every N ssm layers

    # --- encoder-decoder (whisper) ---------------------------------------------
    encoder_layers: int = 0
    encoder_seq: int = 0  # fixed encoder sequence length (audio frames)

    # --- modality frontend stub -------------------------------------------------
    frontend: Optional[str] = None  # None | "audio_frames" | "vision_patches"
    num_patch_tokens: int = 0  # VLM: prompt prefix of image-patch embeddings

    # --- numerics ----------------------------------------------------------------
    dtype: str = "bfloat16"
    kv_quant: bool = False  # int8 KV cache (per-token-per-head absmax scales)
    norm_eps: float = 1e-6

    # --- provenance ----------------------------------------------------------------
    source: str = ""  # citation
    # every size cut from the published config: (key, published, here)
    cuts: Tuple[Tuple[str, Any, Any], ...] = ()

    # -------------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def d_inner(self) -> int:
        """Mamba inner dimension."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_num_heads(self) -> int:
        """Mamba-2 head count."""
        return max(1, self.d_inner // self.ssm_head_dim)

    @property
    def conv_dim(self) -> int:
        """Channels covered by the depthwise conv in the mamba block.

        Mamba-1 convolves x only; Mamba-2 convolves [x, B, C] (n_groups=1).
        """
        if self.ssm_version == 2:
            return self.d_inner + 2 * self.ssm_state
        return self.d_inner

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-decoder-layer kind: attn+ffn composition for this family.

        Returns a tuple of strings, one per layer, drawn from:
          "dense"        attention + dense FFN
          "dense_local"  sliding-window attention + dense FFN
          "moe"          attention + MoE FFN
          "ssm"          mamba block (no attention)
          "ssm_hybrid"   mamba block + shared attention block
        """
        kinds = []
        for l in range(self.num_layers):
            if self.family == "ssm":
                kinds.append("ssm")
            elif self.family == "hybrid":
                if self.hybrid_attn_every and l % self.hybrid_attn_every == 0:
                    kinds.append("ssm_hybrid")
                else:
                    kinds.append("ssm")
            elif self.has_moe and l % self.moe_every == 0:
                kinds.append("moe")
            elif self.attn_pattern == "local_global":
                # even layers local (sliding window), odd layers global
                kinds.append("dense_local" if l % 2 == 0 else "dense")
            else:
                kinds.append("dense")
        return tuple(kinds)

    # -------------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests.

        ≤2 layers, d_model ≤ 512, ≤4 experts, small vocab.
        """
        d_model = min(self.d_model, 256)
        num_heads = min(self.num_heads, 4)
        num_kv = max(1, min(self.num_kv_heads, num_heads))
        # keep the GQA ratio flavour: if the full config is GQA, stay GQA
        if self.num_kv_heads < self.num_heads:
            num_kv = max(1, num_heads // 2)
        head_dim = 64
        changes: Dict[str, Any] = dict(
            name=self.name + "-reduced",
            num_layers=2,
            d_model=d_model,
            num_heads=num_heads,
            num_kv_heads=num_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else None,
        )
        if self.has_moe:
            changes.update(
                num_experts=min(self.num_experts, 4),
                top_k=min(self.top_k, 2),
                d_ff_expert=min(self.d_ff_expert, 128),
                num_shared_experts=min(self.num_shared_experts, 1),
            )
        if self.family in ("ssm", "hybrid"):
            changes.update(ssm_state=min(self.ssm_state, 16), ssm_head_dim=32)
        if self.family == "hybrid":
            changes.update(hybrid_attn_every=1)
        if self.encoder_layers:
            changes.update(encoder_layers=1, encoder_seq=min(self.encoder_seq, 64))
        if self.num_patch_tokens:
            changes.update(num_patch_tokens=16)
        return dataclasses.replace(self, **changes)

    # -------------------------------------------------------------------------
    # Parameter / memory accounting (used by Table-1 bench + scaling model)
    # -------------------------------------------------------------------------
    def param_counts(self) -> Dict[str, int]:
        """Approximate parameter counts per subsystem (embedding, attention,
        dense ffn, expert ffn, ssm)."""
        d = self.d_model
        hd = self.resolved_head_dim
        nh, nkv = self.num_heads, self.num_kv_heads
        counts = dict(embed=0, attn=0, ffn=0, expert=0, ssm=0, norm=0)
        counts["embed"] = self.vocab_size * d * (2 if self.encoder_layers else 1)
        attn_p = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
        glu_mult = 3 if self.ffn_activation in ("swiglu", "geglu") else 2
        ffn_p = glu_mult * d * self.d_ff if self.d_ff else 0
        kinds = self.layer_kinds()
        n_attn = sum(1 for k in kinds if k in ("dense", "dense_local", "moe", "ssm_hybrid"))
        n_dense_ffn = sum(1 for k in kinds if k in ("dense", "dense_local", "ssm_hybrid"))
        n_moe = sum(1 for k in kinds if k == "moe")
        n_ssm = sum(1 for k in kinds if k.startswith("ssm"))
        counts["attn"] = n_attn * attn_p
        counts["ffn"] = n_dense_ffn * ffn_p
        if n_moe:
            expert_p = glu_mult * d * self.d_ff_expert
            routed = self.num_experts * expert_p
            shared = self.num_shared_experts * expert_p
            router = d * self.num_experts
            counts["expert"] = n_moe * routed
            counts["ffn"] += n_moe * (shared + router)
        if n_ssm:
            di = self.d_inner
            ssm_p = (
                d * 2 * di  # in_proj
                + di * self.ssm_conv  # conv
                + di * d  # out_proj
            )
            if self.ssm_version == 1:
                dt_rank = max(1, math.ceil(d / 16))
                ssm_p += di * (dt_rank + 2 * self.ssm_state) + dt_rank * di + di * self.ssm_state + di
            else:
                nh2 = self.ssm_num_heads
                ssm_p += d * (2 * self.ssm_state + nh2) + nh2 * 2 + di
            counts["ssm"] = n_ssm * ssm_p
        if self.encoder_layers:
            counts["attn"] += self.encoder_layers * attn_p * 2  # self+cross approx
            counts["ffn"] += self.encoder_layers * ffn_p
        counts["norm"] = self.num_layers * 4 * d
        return counts

    def total_params(self) -> int:
        return sum(self.param_counts().values())

    def expert_param_fraction(self) -> float:
        c = self.param_counts()
        tot = sum(c.values())
        return c["expert"] / tot if tot else 0.0

    def bytes_per_param(self) -> int:
        return 2 if self.dtype == "bfloat16" else 4

    def kv_bytes_per_token(self) -> int:
        """KV-cache bytes per token across all attention layers."""
        kinds = self.layer_kinds()
        n_attn = sum(1 for k in kinds if k != "ssm")
        return n_attn * 2 * self.num_kv_heads * self.resolved_head_dim * self.bytes_per_param()


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

# Sub-quadratic families allowed to run long_500k.  gemma2 qualifies because
# its local layers use a sliding window (see DESIGN.md §4).
LONG_CONTEXT_ARCHS = ("falcon-mamba-7b", "zamba2-2.7b", "gemma2-2b")


def shape_supported(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """Whether (arch, shape) runs, and the reason if not (recorded in DESIGN)."""
    if shape.name == "long_500k" and cfg.name not in LONG_CONTEXT_ARCHS:
        if cfg.name.endswith("-reduced") and cfg.name[: -len("-reduced")] in LONG_CONTEXT_ARCHS:
            return True, ""
        return False, "pure full-attention arch: long_500k requires sub-quadratic attention"
    return True, ""


# ---------------------------------------------------------------------------
# Input specs — ShapeDtypeStruct stand-ins (dry-run safe: no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, jax.ShapeDtypeStruct]:
    """Build the exact abstract inputs that train_step / prefill_step /
    serve_step of this architecture consume."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    specs: Dict[str, jax.ShapeDtypeStruct] = {}

    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    else:  # decode: one new token against a KV cache of length S
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        specs["cache_index"] = jax.ShapeDtypeStruct((), i32)
        specs.update(_cache_specs(cfg, B, S, dt))

    # modality frontend stubs — precomputed embeddings of the right shape
    if cfg.frontend == "audio_frames" and shape.kind != "decode":
        # decode consumes the cached encoder output (`enc_out`) instead
        specs["encoder_frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), dt)
    elif cfg.frontend == "vision_patches" and shape.kind != "decode":
        specs["patch_embeds"] = jax.ShapeDtypeStruct((B, cfg.num_patch_tokens, cfg.d_model), dt)

    return specs


def _cache_specs(cfg: ModelConfig, B: int, S: int, dt) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract decode-state (KV caches / SSM states) for serve_step."""
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    kinds = cfg.layer_kinds()
    hd = cfg.resolved_head_dim
    n_full = sum(1 for k in kinds if k in ("dense", "moe"))
    n_local = sum(1 for k in kinds if k == "dense_local")
    n_ssm = sum(1 for k in kinds if k.startswith("ssm"))
    n_hyb = sum(1 for k in kinds if k == "ssm_hybrid")
    kv_dt = jnp.int8 if cfg.kv_quant else dt

    def kv(name, n, L):
        specs[f"kv_k{name}"] = jax.ShapeDtypeStruct((n, B, L, cfg.num_kv_heads, hd), kv_dt)
        specs[f"kv_v{name}"] = jax.ShapeDtypeStruct((n, B, L, cfg.num_kv_heads, hd), kv_dt)
        if cfg.kv_quant:
            specs[f"kv_k{name}_scale"] = jax.ShapeDtypeStruct((n, B, L, cfg.num_kv_heads), jnp.float32)
            specs[f"kv_v{name}_scale"] = jax.ShapeDtypeStruct((n, B, L, cfg.num_kv_heads), jnp.float32)

    if n_full:
        kv("", n_full, S)
    if n_local:
        kv("_local", n_local, min(S, cfg.sliding_window or S))
    if n_hyb:
        kv("_hybrid", n_hyb, S)
    if n_ssm:
        di = cfg.d_inner
        if cfg.ssm_version == 1:
            specs["ssm_state"] = jax.ShapeDtypeStruct((n_ssm, B, di, cfg.ssm_state), jnp.float32)
        else:
            specs["ssm_state"] = jax.ShapeDtypeStruct(
                (n_ssm, B, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
            )
        specs["conv_state"] = jax.ShapeDtypeStruct((n_ssm, B, cfg.ssm_conv - 1, cfg.conv_dim), dt)
    if cfg.encoder_layers:
        specs["enc_out"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), dt)
    return specs
