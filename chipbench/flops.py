"""Operations and bytes the served models require, from their sizes.

``m`` is a configuration file's ``program`` block.  Counts are what the
algorithm needs: routed top-k plus shared experts (never capacity padding),
attention over a token's real context (never the cache length), and weights
and KV pages at bf16 (2 bytes).
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, nh, nkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    if m.get("num_experts", 0):
        f = m["d_ff_expert"]
        ffn = d * m["num_experts"] + (m["top_k"] + m.get("num_shared_experts", 0)) * 3 * d * f
    else:
        ffn = 3 * d * m["d_ff"]
    return attn + ffn


def token_flops(m: Dict, context: int, head: bool) -> float:
    """FLOPs of one token at a position that attends ``context`` keys (its own
    included), through every layer, plus the LM head when it yields a token."""
    per_layer = 2 * layer_matmul_params(m) + 4 * m["num_heads"] * m["head_dim"] * context
    total = m["num_layers"] * per_layer
    if head:
        total += 2 * m["vocab_size"] * m["d_model"]
    return float(total)


def prompt_flops(m: Dict, n: int) -> float:
    """A prompt of ``n`` tokens: every position through every layer, causal
    attention over positions 1..n, and one LM head for its first token."""
    per_layer = 2 * layer_matmul_params(m) * n + 4 * m["num_heads"] * m["head_dim"] * n * (n + 1) / 2
    return float(m["num_layers"] * per_layer + 2 * m["vocab_size"] * m["d_model"])


def paged_attention_call(m: Dict, lengths: Iterable[int], page_size: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged decode attention over the active
    slots: QK and PV over each slot's real length, the pages that hold it
    (K and V), and the query and output rows."""
    nh, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 0.0
    nbytes = 0.0
    for L in lengths:
        L = int(L)
        pages = -(-L // page_size)
        flops += 4 * nh * hd * L
        nbytes += 2 * pages * page_size * nkv * hd * BF16 + 2 * nh * hd * BF16
    return flops, nbytes


def expert_ffn_call(m: Dict, tokens_per_expert: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one grouped expert-FFN call: SwiGLU (three d x f
    matmuls) for each routed token, and the weights of every expert that
    received a token, read once, plus the tokens in and out.  No metric reads
    it yet: the expert-FFN kernel is on no cell's path (§7 of PERF.md)."""
    d, f = m["d_model"], m["d_ff_expert"]
    counts = [int(c) for c in tokens_per_expert]
    flops = 2.0 * 3 * d * f * sum(counts)
    nbytes = 3.0 * d * f * BF16 * sum(1 for c in counts if c) + 2.0 * d * BF16 * sum(counts)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, hw: Dict) -> float:
    """The least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / hw["peak_bf16_flops"], nbytes / hw["hbm_bytes_per_s"])
