"""Pallas kernels of the serving hot path: paged decode attention, the grouped
expert FFN and AEBS.  Each compiles for the TPU; elsewhere it runs in the
Pallas interpreter (correctness only — never time an interpreted kernel)."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs in the interpreter.

    ``None`` follows the backend: compiled on a TPU, interpreted elsewhere.
    An explicit ``False`` compiles for the TPU from any host — which is how an
    ahead-of-time compile for a described, unattached chip is made.  Asking
    for the interpreter on a TPU is refused: the chip path never falls back."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret mode is for hosts without a TPU; this one has one")
    return bool(interpret)
