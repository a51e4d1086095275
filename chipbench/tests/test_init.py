"""Weights: the harness's jitted init makes the program's eager init's values,
and the reference, which imports nothing of the program, makes them too.

Not to the bit: XLA fuses the scaling by 1/sqrt(fan_in) into the normal
sampler inside a jitted program, so an f32 value may round differently in
its last bit and, rarely, its bf16 value by one unit in the last place.  The
check allows exactly that: no value more than one bf16 (or f32) ulp apart,
and at most one value in a thousand apart at all."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference
from chipbench.archs.gqa import reduced_program

SEED = 3_000_000_017  # above 2**31, as the benchmark's seeds are


def assert_same_to_an_ulp(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    if a.dtype == jnp.bfloat16:
        ia, ib = a.view(np.int16).astype(np.int64), b.view(np.int16).astype(np.int64)
    else:
        ia, ib = a.view(np.int32).astype(np.int64), b.view(np.int32).astype(np.int64)
    assert np.max(np.abs(ia - ib)) <= 1
    assert np.mean(ia != ib) <= 1e-3


def _program(name):
    return reduced_program(harness.load_config(name)["program"])


@pytest.mark.parametrize("config", ["dsv2-lite-4l", "phi4-mini-3.8b"])
def test_jitted_init_makes_the_eager_values(config):
    from repro.models import model as model_mod

    cfg = harness.model_config({"program": _program(config)})
    eager = model_mod.init_params(cfg, SEED)
    jitted = harness.make_params(cfg, SEED)
    assert jax.tree.structure(eager) == jax.tree.structure(jitted)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        assert_same_to_an_ulp(a, b)


@pytest.mark.parametrize("config", ["dsv2-lite-4l", "phi4-mini-3.8b"])
def test_reference_makes_the_program_weights(config):
    m = _program(config)
    params = harness.make_params(harness.model_config({"program": m}), SEED)
    frozen = reference._Frozen(m)
    assert_same_to_an_ulp(reference.embed_weights(frozen, reference.root_keys(SEED)[0]), params["embed"])
    keys = reference.layer_keys(m, SEED)
    for l in range(m["num_layers"]):
        ours = reference.layer_weights(frozen, keys[l])
        p = jax.tree.map(lambda a: a[l], params["blocks"]["pos0"])
        pairs = [(ours[k], p["attn"][k]) for k in ("wq", "wk", "wv", "wo")]
        if "experts" in ours:
            pairs += [(ours["router"], p["moe"]["router"])]
            pairs += [(ours["experts"][k], p["moe"][k]) for k in ("w_gate", "w_up", "w_down")]
            pairs += [(ours["shared"][k], p["moe"]["shared"][k]) for k in ("w_gate", "w_up", "w_down")]
        else:
            pairs += [(ours["ffn"][k], p["ffn"][k]) for k in ("w_gate", "w_up", "w_down")]
        for a, b in pairs:
            assert_same_to_an_ulp(a, b)
