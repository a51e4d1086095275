"""The default layer equations: a dense or MoE stack of grouped-query (or
multi-head) attention with whole-head RoPE, judged by the float32 reference
of ``reference.py`` and counted by ``flops.py``."""

from __future__ import annotations

from typing import Dict, Tuple

from chipbench.flops import paged_attention_call, prompt_flops, token_flops  # noqa: F401
from chipbench.reference import compare_served  # noqa: F401

# the program's full-attention paged caches -> the reference's K and V
CACHE_KEYS = {"kv_k": "k", "kv_v": "v"}

# ``program`` keys checked against the published names the file also holds
_SAME = (
    ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
    ("num_kv_heads", "num_key_value_heads"), ("num_layers", "num_hidden_layers"),
    ("vocab_size", "vocab_size"), ("norm_eps", "rms_norm_eps"), ("rope_theta", "rope_theta"),
)
_SAME_MOE = (
    ("num_experts", "n_routed_experts"), ("top_k", "num_experts_per_tok"),
    ("d_ff_expert", "moe_intermediate_size"), ("num_shared_experts", "n_shared_experts"),
)


def published_pairs(m: Dict) -> Tuple[Tuple[str, str], ...]:
    return _SAME + (_SAME_MOE if m.get("num_experts") else (("d_ff", "intermediate_size"),))


def reduced_program(m: Dict) -> Dict:
    """The configuration's ``program`` block at CPU-test sizes (the same
    shrink as ``ModelConfig.reduced``: 2 layers, d 256, 4 heads of 64, a
    512-token vocabulary, 4 experts of 128, top-2, 1 shared)."""
    r = dict(m, num_layers=2, d_model=256, num_heads=4, head_dim=64, vocab_size=512)
    r["num_kv_heads"] = 2 if m["num_kv_heads"] < m["num_heads"] else 4
    if m.get("num_experts"):
        r.update(num_experts=4, top_k=2, d_ff_expert=128, num_shared_experts=1)
    else:
        r["d_ff"] = 512
    return r


def paged_attention_calls(m: Dict, lengths, page_size: int) -> Tuple[int, float, float]:
    """One ``paged_decode_attention`` call per layer, all alike."""
    return (m["num_layers"], *paged_attention_call(m, lengths, page_size))
