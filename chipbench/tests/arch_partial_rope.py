"""A test-only arch module: the default's equations with RoPE on the first
three quarters of each head only, the rest passed through, as Phi-4-mini
publishes it (``partial_rotary_factor`` 0.75).  The program rotates whole
heads, so a run judged by this module has to fail its cached K.

The reference is a second copy of ``reference.py`` with its ``_rope``
replaced, so that the default module is left as it is."""

import importlib.util

import jax.numpy as jnp

from chipbench import reference
from chipbench.archs.gqa import (  # noqa: F401
    CACHE_KEYS, paged_attention_calls, prompt_flops, published_pairs, reduced_program, token_flops,
)

ROTARY_FACTOR = 0.75

_spec = importlib.util.spec_from_file_location(f"{__name__}.reference", reference.__file__)
_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ref)
_whole_head = _ref._rope


def _partial_rope(x, theta):
    """Half-split RoPE over the first ``ROTARY_FACTOR`` of each head (its
    frequencies over that width), the other dims unrotated."""
    rot = int(x.shape[-1] * ROTARY_FACTOR)
    return jnp.concatenate([_whole_head(x[..., :rot], theta), x[..., rot:]], axis=-1)


_ref._rope = _partial_rope
compare_served = _ref.compare_served
