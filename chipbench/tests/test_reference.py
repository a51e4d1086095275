"""The float32 reference computes the program's model: at a reduced size, the
program run in float32 on the same (bf16-valued) weights gives the same
logits, and drop-free MoE routing matches."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference
from chipbench.rehearse import reduced_program

SEED = 11


@pytest.mark.parametrize("config", ["dsv2-lite-4l", "phi4-mini-3.8b"])
def test_reference_logits_match_program_in_float32(config):
    from repro.models import model as model_mod

    m = reduced_program(harness.load_config(config)["program"])
    cfg = harness.model_config({"program": m})
    params = harness.make_params(cfg, SEED)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    tokens = np.random.default_rng(0).integers(0, m["vocab_size"], size=(2, 40)).astype(np.int32)
    extra = {"moe_ctx": {"capacity": tokens.size}} if cfg.has_moe else None  # drop-free
    with jax.default_matmul_precision("highest"):
        want, _ = model_mod.logits_fn(p32, jnp.asarray(tokens), cfg32, extra=extra)
    got = reference.forward_logits(m, SEED, tokens)
    want = np.asarray(want)
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


def test_gap_is_zero_on_the_references_own_greedy_tokens():
    m = reduced_program(harness.load_config("dsv2-lite-4l")["program"])
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, m["vocab_size"], size=20).astype(np.int32)
    seq = list(prompt)
    for _ in range(6):  # greedy continuation by the reference itself
        seq.append(int(np.argmax(reference.forward_logits(m, SEED, np.asarray([seq], np.int32))[0, -1])))
    served = np.asarray(seq[20:], np.int32)
    gaps = reference.compare_served(m, SEED, [prompt], [served])["gaps"]["f32"][0]
    assert len(gaps) == 6 and np.max(gaps) <= 1e-5
