"""Jit'd wrapper for the flash-decode attention kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import (
    decode_attention_int8_pallas,
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.decode_attention.ref import paged_decode_attention_ref


@functools.partial(jax.jit, static_argnames=("block_kv", "logit_cap"))
def decode_attention(q, k_cache, v_cache, valid_len, block_kv: int = 512, logit_cap: float = 0.0):
    S = k_cache.shape[1]
    bk = min(block_kv, S)
    while S % bk:
        bk //= 2
    return decode_attention_pallas(
        q, k_cache, v_cache, valid_len,
        block_kv=bk, logit_cap=logit_cap,
    )


@functools.partial(jax.jit, static_argnames=("block_kv", "logit_cap"))
def decode_attention_int8(
    q, k_cache, v_cache, k_scale, v_scale, valid_len,
    block_kv: int = 512, logit_cap: float = 0.0,
):
    """Flash decode over an int8 KV cache (in-VMEM dequantisation)."""
    S = k_cache.shape[1]
    bk = min(block_kv, S)
    while S % bk:
        bk //= 2
    return decode_attention_int8_pallas(
        q, k_cache, v_cache, k_scale, v_scale, valid_len,
        block_kv=bk, logit_cap=logit_cap,
    )


@functools.partial(jax.jit, static_argnames=("logit_cap", "backend"))
def paged_decode_attention(
    q, k_pages, v_pages, block_tables, lengths,
    logit_cap: float = 0.0, backend: str = "pallas",
):
    """Paged flash decode over a block-table-indirect page pool.

    ``backend="jnp"`` selects the gather-based fallback (the oracle) for
    platforms without a Pallas lowering; the default runs the kernel,
    interpreted off-TPU."""
    if backend == "jnp":
        return paged_decode_attention_ref(
            q, k_pages, v_pages, block_tables, lengths, logit_cap=logit_cap
        )
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, block_tables, lengths,
        logit_cap=logit_cap,
    )
