"""AEBS as a Pallas TPU kernel — the paper's §3.4 GPU kernel, TPU-native.

The scheduling workflow is three ``pallas_call``s:

Kernel 1 (``_collect``) — grid over token blocks, vector units: each block
  folds its activated-expert bitmap into a lane-major ``[1, E]`` output
  accumulator via max (grid iterations on TPU run sequentially per core, so
  the accumulation is well-defined).

Kernel 2 (``_greedy``) — no grid, scalar core: the greedy two-pass replica
  selection of Algorithm 1 over ≤E experts.  Every table lives in SMEM and is
  read one scalar at a time at a data-dependent index, which the vector units
  cannot do (a dynamic vector slice does not lower on the TPU).  Produces
  ``act_rep`` and ``load``.

Kernel 3 (``_rewrite``) — grid over token blocks: rewrite per-token logical
  EIDs to physical replica slots as a masked select-and-sum against the
  ``[1, E]`` replica row (exact in int32, no gather).

All run identically on every MoE shard — Janus's synchronisation-free
redundant-compute trick carries over unchanged.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_BIG = 2**30  # load of a missing replica: never the argmin


def _collect_kernel(eids_ref, act_ref):
    """eids_ref [TB, k] (padded items = -1) → act_ref [1, E] bitmap (max-fold)."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        act_ref[...] = jnp.zeros_like(act_ref)

    blk = eids_ref[...]
    E = act_ref.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
    hits = act_ref[...]
    for j in range(blk.shape[1]):
        col = blk[:, j : j + 1]  # [TB, 1]
        hits = jnp.maximum(
            hits, (col == eye).astype(jnp.int32).max(axis=0, keepdims=True)
        )
    act_ref[...] = hits


def _greedy_kernel(
    act_ref,  # SMEM [E] int32 activation bitmap
    hosts_ref,  # SMEM [E * R] int32, -1 = no replica
    counts_ref,  # SMEM [E] int32 replica counts
    slot_of_ref,  # SMEM [E * n_e] int32 global slot of expert e on instance g
    rep_ref,  # SMEM out [E] int32 chosen slot per expert (-1 = inactive)
    load_ref,  # SMEM out [n_e] int32 activated experts per instance
    *,
    E: int,
    R: int,
    n_e: int,
):
    for g in range(n_e):
        load_ref[g] = jnp.int32(0)

    def _clear(e, c):
        rep_ref[e] = jnp.int32(-1)
        return c

    jax.lax.fori_loop(0, E, _clear, 0)

    def assign_pass(want_multi: bool):
        def body(e, c):
            cnt = counts_ref[e]
            eligible = (act_ref[e] > 0) & ((cnt > 1) == want_multi) & (cnt >= 1)

            @pl.when(eligible)
            def _assign():
                # argmin over the expert's hosts of their current load; the
                # first minimum wins, as jnp.argmin's does in the reference
                best_load = jnp.int32(_BIG)
                best_g = jnp.int32(0)
                for r in range(R):
                    h = hosts_ref[e * R + r]
                    hs = jnp.maximum(h, 0)
                    ld = jnp.where(h >= 0, load_ref[hs], jnp.int32(_BIG))
                    better = ld < best_load
                    best_load = jnp.where(better, ld, best_load)
                    best_g = jnp.where(better, hs, best_g)
                load_ref[best_g] = load_ref[best_g] + 1
                rep_ref[e] = slot_of_ref[e * n_e + best_g]

            return c

        jax.lax.fori_loop(0, E, body, 0)

    assign_pass(False)
    assign_pass(True)


def _rewrite_kernel(eids_ref, rep_ref, out_ref):
    """slot_ids = act_rep[eids]: one replica per expert, so a masked sum over
    the expert lanes selects it exactly; padded items stay -1."""
    blk = eids_ref[...]  # [TB, k]
    rep = rep_ref[...]  # [1, E]
    eye = jax.lax.broadcasted_iota(jnp.int32, (1, rep.shape[1]), 1)
    for j in range(blk.shape[1]):
        col = blk[:, j : j + 1]  # [TB, 1]
        val = jnp.where(col == eye, rep, 0).sum(axis=1, keepdims=True)
        out_ref[:, j : j + 1] = jnp.where(col < 0, -1, val)


def aebs_pallas(
    eids: jax.Array,  # [T, k] int32 (pad items with -1)
    hosts: jax.Array,  # [E, R]
    counts: jax.Array,  # [E]
    slot_of: jax.Array,  # [E, n_e]
    *,
    block_tokens: int = 256,
    interpret: Optional[bool] = None,
):
    """Returns (slot_ids [T, k], load [n_e], act_rep [E])."""
    interpret = resolve_interpret(interpret)
    T, k = eids.shape
    E, n_e = slot_of.shape
    R = hosts.shape[1]
    TB = min(block_tokens, T)
    pad = (-T) % TB
    if pad:
        eids = jnp.concatenate([eids, jnp.full((pad, k), -1, jnp.int32)], axis=0)
    Tp = eids.shape[0]
    num_blocks = Tp // TB

    act = pl.pallas_call(
        _collect_kernel,
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((TB, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, E), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, E), jnp.int32),
        interpret=interpret,
    )(eids)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    actrep, load = pl.pallas_call(
        functools.partial(_greedy_kernel, E=E, R=R, n_e=n_e),
        in_specs=[smem] * 4,
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((E,), jnp.int32),
            jax.ShapeDtypeStruct((n_e,), jnp.int32),
        ],
        interpret=interpret,
    )(
        act.reshape(E),
        hosts.astype(jnp.int32).reshape(E * R),
        counts.astype(jnp.int32),
        slot_of.astype(jnp.int32).reshape(E * n_e),
    )

    slot_ids = pl.pallas_call(
        _rewrite_kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((TB, k), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TB, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, k), jnp.int32),
        interpret=interpret,
    )(eids, actrep.reshape(1, E))

    return slot_ids[:T], load, actrep
