"""Config registry, parameter accounting (Table 1), input specs, shape skips."""

import jax.numpy as jnp
import pytest

from repro.configs import (
    ASSIGNED,
    REGISTRY,
    SHAPES,
    get_config,
    input_specs,
    shape_supported,
)

EXPECTED = {
    "gemma-7b": dict(family="dense", num_layers=28, d_model=3072, num_heads=16, num_kv_heads=16, d_ff=24576, vocab_size=256_000),
    "yi-34b": dict(family="dense", num_layers=60, d_model=7168, num_heads=56, num_kv_heads=8, d_ff=20480, vocab_size=64_000),
    "pixtral-12b": dict(family="vlm", num_layers=40, d_model=5120, num_heads=32, num_kv_heads=8, d_ff=14336, vocab_size=131_072),
    "falcon-mamba-7b": dict(family="ssm", num_layers=64, d_model=4096, ssm_state=16, vocab_size=65_024),
    "gemma2-2b": dict(family="dense", num_layers=26, d_model=2304, num_heads=8, num_kv_heads=4, d_ff=9216, vocab_size=256_000),
    "phi4-mini-3.8b": dict(family="dense", num_layers=32, d_model=3072, num_heads=24, num_kv_heads=8, d_ff=8192, vocab_size=200_064),
    "qwen2-moe-a2.7b": dict(family="moe", num_layers=24, d_model=2048, num_heads=16, num_kv_heads=16, d_ff_expert=1408, vocab_size=151_936, num_experts=60, top_k=4, num_shared_experts=4),
    "zamba2-2.7b": dict(family="hybrid", num_layers=54, d_model=2560, num_heads=32, num_kv_heads=32, d_ff=10240, vocab_size=32_000, ssm_state=64),
    "whisper-tiny": dict(family="audio", num_layers=4, d_model=384, num_heads=6, num_kv_heads=6, d_ff=1536, vocab_size=51_865),
    "phi3.5-moe-42b-a6.6b": dict(family="moe", num_layers=32, d_model=4096, num_heads=32, num_kv_heads=8, d_ff_expert=6400, vocab_size=32_064, num_experts=16, top_k=2),
}


def test_all_assigned_present():
    assert set(EXPECTED) == set(ASSIGNED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_exact_dims(name):
    cfg = REGISTRY[name]
    for k, v in EXPECTED[name].items():
        assert getattr(cfg, k) == v, (name, k, getattr(cfg, k), v)
    assert cfg.source  # every config cites its source


def test_dsv2_lite_4l_cuts_depth_only():
    """The chip configuration keeps every dsv2-lite width; only depth is cut,
    and the cut is listed in the config itself."""
    full, cut = REGISTRY["dsv2-lite"], get_config("dsv2-lite-4l")
    assert cut.num_layers == 4
    assert cut.cuts == (("num_layers", full.num_layers, 4),)
    for key in ("d_model", "num_heads", "num_kv_heads", "head_dim", "vocab_size",
                "num_experts", "num_shared_experts", "top_k", "d_ff_expert"):
        assert getattr(cut, key) == getattr(full, key), key
    assert cut.kv_bytes_per_token() == 32 * 1024  # 32 KiB per token at 4 layers
    assert 2.5e9 < cut.total_params() < 2.6e9


def test_expert_memory_dominates():
    """Table 1: expert params dominate MoE model memory (≥85% for big MoE)."""
    assert REGISTRY["phi3.5-moe-42b-a6.6b"].expert_param_fraction() > 0.9
    assert REGISTRY["qwen2-moe-a2.7b"].expert_param_fraction() > 0.85
    assert REGISTRY["scaled-ds-2"].expert_param_fraction() > 0.95
    assert REGISTRY["yi-34b"].expert_param_fraction() == 0.0


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_reduced_constraints(name):
    r = get_config(name + "-reduced")
    assert r.num_layers <= 2
    assert r.d_model <= 512
    assert r.num_experts <= 4
    assert r.family == REGISTRY[name].family


def test_long_context_skips():
    long = SHAPES["long_500k"]
    runs = [a for a in ASSIGNED if shape_supported(REGISTRY[a], long)[0]]
    assert sorted(runs) == ["falcon-mamba-7b", "gemma2-2b", "zamba2-2.7b"]
    ok, why = shape_supported(REGISTRY["yi-34b"], long)
    assert not ok and "sub-quadratic" in why


def test_combo_count():
    n = sum(
        1
        for a in ASSIGNED
        for s in SHAPES.values()
        if shape_supported(REGISTRY[a], s)[0]
    )
    assert n == 33  # 10×3 + 3 long-context


@pytest.mark.parametrize("name", sorted(ASSIGNED))
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_input_specs_abstract(name, shape_name):
    cfg, shape = REGISTRY[name], SHAPES[shape_name]
    if not shape_supported(cfg, shape)[0]:
        pytest.skip("unsupported combo")
    specs = input_specs(cfg, shape)
    assert "tokens" in specs
    B = shape.global_batch
    if shape.kind == "decode":
        assert specs["tokens"].shape == (B, 1)
        assert any(k.startswith(("kv_", "ssm_")) for k in specs)
    else:
        assert specs["tokens"].shape == (B, shape.seq_len)
    for v in specs.values():
        assert isinstance(v, type(specs["tokens"]))  # ShapeDtypeStruct: no allocation
    if cfg.family == "audio" and shape.kind != "decode":
        assert specs["encoder_frames"].shape == (B, cfg.encoder_seq, cfg.d_model)
    if cfg.attn_pattern == "local_global" and shape.kind == "decode":
        W = min(shape.seq_len, cfg.sliding_window)
        assert specs["kv_k_local"].shape[2] == W


def test_kv_bytes_per_token():
    cfg = REGISTRY["yi-34b"]
    assert cfg.kv_bytes_per_token() == 60 * 2 * 8 * 128 * 2
    assert REGISTRY["falcon-mamba-7b"].kv_bytes_per_token() == 0
