"""Flash-decode GQA attention Pallas kernel (decode_32k / long_500k path).

One query token per sequence against a long KV cache:

  grid = (batch, kv_heads, kv_blocks)   (kv_blocks innermost → sequential)

Per (b, h): the G query heads sharing kv-head h stream KV blocks from HBM
through VMEM, maintaining the online-softmax running max / denominator /
accumulator in VMEM scratch.  Positions ≥ valid_len are masked.  The final
block normalises and writes the [G, head_dim] output tile.

This is the memory-bound half of decode (KV bytes dominate); the roofline
term it addresses is c_kv·b·S_ctx of Eq. 1b.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1.0e30


def _decode_attn_kernel(
    valid_ref,  # [1, 1] int32 — number of valid cache entries
    q_ref,  # [1, 1, G, hd]
    k_ref,  # [1, SB, 1, hd]
    v_ref,  # [1, SB, 1, hd]
    o_ref,  # [1, 1, G, hd]
    m_scr,  # VMEM [G, 1] f32
    l_scr,  # VMEM [G, 1] f32
    acc_scr,  # VMEM [G, hd] f32
    *,
    num_kv_blocks: int,
    block_kv: int,
    logit_cap: float,
):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, 0].astype(jnp.float32)  # [G, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)  # [SB, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)  # [SB, hd]
    hd = q.shape[-1]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (hd**-0.5)  # [G, SB]
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    pos = sb * block_kv + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
    s = jnp.where(pos < valid_ref[0, 0], s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]  # [G,1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)  # [G, SB]
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(sb == num_kv_blocks - 1)
    def _finish():
        o_ref[0, 0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _decode_attn_int8_kernel(
    valid_ref,  # [1, 1] int32
    q_ref,  # [1, 1, 1, G, hd]
    k_ref,  # [1, SB, 1, hd] int8
    v_ref,  # [1, SB, 1, hd] int8
    ks_ref,  # [1, SB, 1] f32 — per-(token, head) scales
    vs_ref,  # [1, SB, 1] f32
    o_ref,  # [1, 1, 1, G, hd]
    m_scr,
    l_scr,
    acc_scr,
    *,
    num_kv_blocks: int,
    block_kv: int,
    logit_cap: float,
):
    """int8-KV flash decode: dequantisation happens in VMEM, fused into the
    streaming loop — HBM sees only int8 cache bytes (the §Perf P3b fix)."""
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, 0].astype(jnp.float32)  # [G, hd]
    k = k_ref[0, :, 0].astype(jnp.float32) * ks_ref[0, :, 0][:, None]  # [SB, hd]
    v = v_ref[0, :, 0].astype(jnp.float32) * vs_ref[0, :, 0][:, None]
    hd = q.shape[-1]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (hd**-0.5)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    pos = sb * block_kv + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
    s = jnp.where(pos < valid_ref[0, 0], s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(sb == num_kv_blocks - 1)
    def _finish():
        o_ref[0, 0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_int8_pallas(
    q: jax.Array,  # [B, n_heads, hd]
    k_cache: jax.Array,  # [B, S, n_kv, hd] int8
    v_cache: jax.Array,
    k_scale: jax.Array,  # [B, S, n_kv] f32
    v_scale: jax.Array,
    valid_len: jax.Array,
    *,
    block_kv: int = 512,
    logit_cap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, nh, hd = q.shape
    _, S, nkv, _ = k_cache.shape
    G = nh // nkv
    SB = min(block_kv, S)
    if S % SB:
        raise ValueError(f"cache len {S} not divisible by block_kv {SB}")
    nblk = S // SB
    qg = q.reshape(B, nkv, G, hd)[:, :, None, :, :]
    valid = jnp.broadcast_to(valid_len.astype(jnp.int32), (1, 1))

    out = pl.pallas_call(
        functools.partial(
            _decode_attn_int8_kernel,
            num_kv_blocks=nblk,
            block_kv=SB,
            logit_cap=logit_cap,
        ),
        grid=(B, nkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, s: (0, 0)),
            pl.BlockSpec((1, 1, 1, G, hd), lambda b, h, s: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, SB, 1, hd), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, SB, 1, hd), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, SB, 1), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, SB, 1), lambda b, h, s: (b, s, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, G, hd), lambda b, h, s: (b, h, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nkv, 1, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(valid, qg, k_cache, v_cache, k_scale, v_scale)

    return out.reshape(B, nkv, G, hd).reshape(B, nh, hd)


def _paged_decode_attn_kernel(
    bt_ref,  # scalar-prefetch [B, nblk] int32 — per-slot block table
    len_ref,  # scalar-prefetch [B] int32 — per-slot valid lengths
    q_ref,  # [1, n_kv, G, hd]
    k_ref,  # [1, ps, n_kv, hd] — page bt[b, j] of the pool, every KV head
    v_ref,  # [1, ps, n_kv, hd]
    o_ref,  # [1, n_kv, G, hd]
    m_scr,  # VMEM [n_kv, G, 1] f32
    l_scr,  # VMEM [n_kv, G, 1] f32
    acc_scr,  # VMEM [n_kv, G, hd] f32
    *,
    num_kv_blocks: int,
    page_size: int,
    logit_cap: float,
):
    """Page-indirect flash decode: the grid walks each slot's *virtual* KV
    blocks in order, and the scalar-prefetched block table redirects the K/V
    BlockSpecs to the physical page (the slot-indirect `expert_ffn` idiom).
    Unbacked table entries point at the null page; per-slot ``len_ref``
    masking zeroes whatever garbage lives there.

    A block is a whole page — all KV heads — because the TPU tiles the last
    two dims ``(n_kv, hd)`` of the pool: a one-head block ``(ps, 1, hd)``
    breaks the 8×128 tiling rule.  Each head is a strided read of the page."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_kv = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    valid = pos < len_ref[b]
    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32)  # [G, hd]
        k = k_ref[0, :, h, :].astype(jnp.float32)  # [ps, hd]
        v = v_ref[0, :, h, :].astype(jnp.float32)  # [ps, hd]
        hd = q.shape[-1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (hd**-0.5)  # [G, ps]
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_scr[h], l_scr[h]  # [G,1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [G, ps]
        l_scr[h] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(j == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_pallas(
    q: jax.Array,  # [B, n_heads, hd] — one token per sequence
    k_pages: jax.Array,  # [P, ps, n_kv, hd] — page pool
    v_pages: jax.Array,  # [P, ps, n_kv, hd]
    block_tables: jax.Array,  # [B, nblk] int32 — slot → page map
    lengths: jax.Array,  # [B] int32 — per-slot valid lengths
    *,
    logit_cap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged flash decode.  Returns attention output [B, n_heads, hd].

    Virtual block j of slot b streams physical page ``block_tables[b, j]``
    through VMEM; pages are the KV blocks (block_kv == page_size), so the
    online-softmax loop is identical to the contiguous kernel's."""
    B, nh, hd = q.shape
    P, ps, nkv, _ = k_pages.shape
    G = nh // nkv
    nblk = block_tables.shape[1]
    qg = q.reshape(B, nkv, G, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((1, nkv, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, nkv, hd), lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, nkv, hd), lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nkv, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, G, 1), jnp.float32),
            pltpu.VMEM((nkv, G, 1), jnp.float32),
            pltpu.VMEM((nkv, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_attn_kernel,
            num_kv_blocks=nblk,
            page_size=ps,
            logit_cap=logit_cap,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, G, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pages, v_pages)

    return out.reshape(B, nh, hd)


def decode_attention_pallas(
    q: jax.Array,  # [B, n_heads, hd] — one token per sequence
    k_cache: jax.Array,  # [B, S, n_kv, hd]
    v_cache: jax.Array,  # [B, S, n_kv, hd]
    valid_len: jax.Array,  # scalar int32 (entries < valid_len attend)
    *,
    block_kv: int = 512,
    logit_cap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns attention output [B, n_heads, hd]."""
    B, nh, hd = q.shape
    _, S, nkv, _ = k_cache.shape
    G = nh // nkv
    SB = min(block_kv, S)
    if S % SB:
        raise ValueError(f"cache len {S} not divisible by block_kv {SB}")
    nblk = S // SB
    qg = q.reshape(B, nkv, G, hd)[:, :, None, :, :]  # [B, nkv, 1, G, hd]
    valid = jnp.broadcast_to(valid_len.astype(jnp.int32), (1, 1))

    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel,
            num_kv_blocks=nblk,
            block_kv=SB,
            logit_cap=logit_cap,
        ),
        grid=(B, nkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, s: (0, 0)),
            pl.BlockSpec((1, 1, 1, G, hd), lambda b, h, s: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, SB, 1, hd), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, SB, 1, hd), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, G, hd), lambda b, h, s: (b, h, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nkv, 1, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(valid, qg, k_cache, v_cache)

    return out.reshape(B, nkv, G, hd).reshape(B, nh, hd)
