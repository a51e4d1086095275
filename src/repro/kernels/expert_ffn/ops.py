"""Jit'd wrappers for the grouped expert-FFN kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.expert_ffn.kernel import expert_ffn_pallas


def _ff_tile(f: int, ff_tile: int) -> int:
    ft = ff_tile
    while f % ft:
        ft //= 2
    return ft


@functools.partial(jax.jit, static_argnames=("ff_tile",))
def expert_ffn(x, w_gate, w_up, w_down, active, ff_tile: int = 512):
    """Stacked-weights form: weights [S, d, f], one slab per slot."""
    return expert_ffn_pallas(
        x, w_gate, w_up, w_down, active,
        ff_tile=_ff_tile(w_gate.shape[-1], ff_tile),
    )


@functools.partial(jax.jit, static_argnames=("ff_tile",))
def expert_ffn_grouped(x, w_gate, w_up, w_down, slot_to_expert, active, ff_tile: int = 512):
    """Slot-indirect form: logical weights [E, d, f] + flat slot→expert map.

    No per-slot weight copy is ever materialised — the kernel's BlockSpec
    index_maps dereference ``slot_to_expert`` (a scalar-prefetch operand)
    to stream each activated slot's expert weights directly.
    """
    return expert_ffn_pallas(
        x, w_gate, w_up, w_down, active, slot_to_expert,
        ff_tile=_ff_tile(w_gate.shape[-1], ff_tile),
    )
