"""Jit'd public wrapper for the AEBS kernel.

On a TPU the kernel compiles to Mosaic; on any other backend it runs in the
Pallas interpreter (``repro.kernels.resolve_interpret``).  The wrapper
handles padding and exposes the same (slot_ids, load, act_rep) contract as
``repro.core.aebs.aebs_assign``.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax

from repro.kernels.aebs.kernel import aebs_pallas


@functools.partial(jax.jit, static_argnames=("num_instances", "block_tokens"))
def aebs_schedule(
    eids: jax.Array,
    tables: Dict[str, jax.Array],
    num_instances: int,
    block_tokens: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return aebs_pallas(
        eids,
        tables["expert_hosts"],
        tables["replica_counts"],
        tables["slot_of"],
        block_tokens=block_tokens,
    )


# same Algorithm-1 semantics as aebs_assign: one replica per activated expert
aebs_schedule.single_active_replica = True
