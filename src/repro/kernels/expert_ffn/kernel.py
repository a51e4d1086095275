"""Grouped expert-FFN Pallas kernel — the MoE hot loop (§2.2 / Fig. 2).

The paper's central performance fact is that MoE-layer latency is set by the
number of *distinct activated experts* per instance, because each activated
expert's weights must be streamed from HBM regardless of its token count.
This kernel makes that structure explicit on TPU:

  grid = (num_slots, d_ff_tiles)
  * weights are read *slot-indirectly*: the flat ``slot_to_expert`` map is a
    scalar-prefetch operand and the weight BlockSpec index_maps dereference it,
    so the kernel streams gate/up/down blocks straight out of the logical
    ``[E, d, f]`` arrays — replica slots never materialise a weight copy;
  * inactive expert slots skip all compute via ``@pl.when`` (their weight
    index_maps degenerate to expert 0's blocks, which the pipeline elides
    for consecutive inactive steps), so per-instance FLOPs ∝ activated-slot
    count — the β·a_max model of Eq. 1c.  Hosts where a compiled kernel is
    unavailable get the same activated-only behaviour from the stream-loop
    fallback (``repro.models.moe.stream_slot_ffn``), which iterates over
    active slots exclusively;
  * active slots run a double GEMM (gate/up) + SwiGLU + down-projection over
    their capacity-packed token block, tiled along d_ff so every working set
    fits VMEM with MXU-aligned (multiples of 128) matmul dims;
  * the down-projection accumulates across d_ff tiles into the output block
    (the d_ff grid axis iterates innermost → sequential on TPU).

When ``slot_to_expert`` is the identity the kernel degenerates to the old
stacked-weights form (weights [S, d, f], one slab per slot), which is how the
pinned-replica deployment path (launch.steps.materialize_slot_params) and the
pre-existing tests drive it.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _expert_ffn_kernel(
    s2e_ref,  # [S] int32 scalar-prefetch — slot → logical expert
    active_ref,  # [S] int32 scalar-prefetch — slot activation bitmap
    x_ref,  # [1, CAP, d]
    wg_ref,  # [1, d, FT]  (block of w_gate[s2e[s]])
    wu_ref,  # [1, d, FT]
    wd_ref,  # [1, FT, d]
    out_ref,  # [1, CAP, d]
):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active_ref[s] > 0)
    def _compute():
        x = x_ref[0]  # [CAP, d]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)  # [CAP, FT]
        acc = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
        out_ref[0] = (out_ref[0].astype(jnp.float32) + acc).astype(out_ref.dtype)


def expert_ffn_pallas(
    x: jax.Array,  # [S, CAP, d] capacity-packed tokens per slot
    w_gate: jax.Array,  # [E, d, f] logical (or [S, d, f] stacked w/ identity map)
    w_up: jax.Array,  # [E, d, f]
    w_down: jax.Array,  # [E, f, d]
    active: jax.Array,  # [S] int32/bool — slot activation bitmap
    slot_to_expert: jax.Array | None = None,  # [S] int32, -1 → skip; None = identity
    *,
    ff_tile: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """SwiGLU expert FFN per slot with slot-indirect weight reads.

    Inactive slots (``active == 0`` or ``slot_to_expert == -1``) yield zeros
    and stream no weights.
    """
    S, CAP, d = x.shape
    f = w_gate.shape[-1]
    FT = min(ff_tile, f)
    if f % FT:
        raise ValueError(f"d_ff={f} not divisible by ff_tile={FT}")
    nft = f // FT
    if slot_to_expert is None:
        if w_gate.shape[0] != S:
            raise ValueError(
                f"identity slot map needs stacked weights: {w_gate.shape[0]} != {S}"
            )
        slot_to_expert = jnp.arange(S, dtype=jnp.int32)
    slot_to_expert = slot_to_expert.astype(jnp.int32)
    active = (active.astype(jnp.int32) * (slot_to_expert >= 0)).astype(jnp.int32)

    def _wslab(s, j, s2e, act):
        return (jnp.maximum(s2e[s], 0), 0, j)

    def _wslab_t(s, j, s2e, act):
        return (jnp.maximum(s2e[s], 0), j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nft),
        in_specs=[
            pl.BlockSpec((1, CAP, d), lambda s, j, s2e, act: (s, 0, 0)),
            pl.BlockSpec((1, d, FT), _wslab),
            pl.BlockSpec((1, d, FT), _wslab),
            pl.BlockSpec((1, FT, d), _wslab_t),
        ],
        out_specs=pl.BlockSpec((1, CAP, d), lambda s, j, s2e, act: (s, 0, 0)),
    )
    return pl.pallas_call(
        _expert_ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, CAP, d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(slot_to_expert, active, x, w_gate, w_up, w_down)
