"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU; the kernels target TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aebs import aebs_numpy
from repro.core.amax import make_routing_trace
from repro.core.placement import build_layout
from repro.kernels.aebs.ops import aebs_schedule
from repro.kernels.aebs.ref import aebs_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.expert_ffn.ops import expert_ffn
from repro.kernels.expert_ffn.ref import expert_ffn_ref


# ---------------------------------------------------------------------------
# AEBS kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,n_e,C,T,k", [
    (16, 4, 5, 64, 2),
    (64, 8, 12, 300, 6),   # non-multiple of block → padding path
    (60, 16, 4, 128, 4),   # qwen-like
    (256, 16, 17, 512, 8), # dsv3-scale routing
])
def test_aebs_kernel_vs_oracles(E, n_e, C, T, k):
    trace = make_routing_trace(max(T, 512), E, k, skew=0.8, seed=E)
    layout = build_layout(trace, E, n_e, C)
    eids = jnp.asarray(trace[:T])
    t = layout.device_tables()
    s_k, load_k, rep_k = aebs_schedule(eids, t, n_e, block_tokens=128)
    s_r, load_r, _ = aebs_ref(eids, t["expert_hosts"], t["replica_counts"], t["slot_of"])
    s_n, load_n, _ = aebs_numpy(np.asarray(eids), layout)
    assert np.array_equal(np.asarray(s_k), np.asarray(s_r))
    assert np.array_equal(np.asarray(load_k), np.asarray(load_r))
    assert np.array_equal(np.asarray(s_k), s_n)


def test_aebs_kernel_padding_neutral():
    """Padded items (-1) must not activate experts or affect loads."""
    E, n_e, C, k = 32, 4, 9, 4
    trace = make_routing_trace(512, E, k, skew=0.5, seed=9)
    layout = build_layout(trace, E, n_e, C)
    t = layout.device_tables()
    e1 = jnp.asarray(trace[:100])
    _, load_100, _ = aebs_schedule(e1, t, n_e, block_tokens=64)  # pads 100→128
    _, load_full, _ = aebs_schedule(jnp.asarray(trace[:128]), t, n_e, block_tokens=64)
    sub, _, _ = aebs_numpy(trace[:100], layout)
    assert np.array_equal(np.asarray(load_100), aebs_numpy(trace[:100], layout)[1])


# ---------------------------------------------------------------------------
# Expert FFN kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,CAP,d,f", [
    (4, 16, 128, 256),
    (8, 64, 256, 1024),
    (16, 8, 512, 1408),   # qwen expert dims (non-pow2 f)
    (3, 32, 256, 512),    # odd slot count
])
def test_expert_ffn_sweep(S, CAP, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S * f), 5)
    x = (jax.random.normal(ks[0], (S, CAP, d), jnp.float32) * 0.5).astype(dtype)
    wg = (jax.random.normal(ks[1], (S, d, f), jnp.float32) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (S, d, f), jnp.float32) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (S, f, d), jnp.float32) * 0.05).astype(dtype)
    act = jax.random.bernoulli(ks[4], 0.6, (S,)).astype(jnp.int32)
    got = expert_ffn(x, wg, wu, wd, act)
    want = expert_ffn_ref(x, wg, wu, wd, act)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )
    # inactive slots are exactly zero (no weight streaming)
    inact = np.asarray(act) == 0
    assert (np.asarray(got, np.float32)[inact] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,E,CAP,d,f", [
    (6, 4, 16, 128, 256),     # replica slots > experts
    (10, 3, 8, 256, 512),     # heavy replication + an empty slot
])
def test_expert_ffn_slot_indirect_sweep(S, E, CAP, d, f, dtype):
    """Slot-indirect form: logical [E, d, f] weights + flat slot→expert map
    as a scalar-prefetch operand — no stacked weight copy is ever built."""
    from repro.kernels.expert_ffn.ops import expert_ffn_grouped
    from repro.kernels.expert_ffn.ref import expert_ffn_grouped_ref

    ks = jax.random.split(jax.random.PRNGKey(S * f + 1), 5)
    x = (jax.random.normal(ks[0], (S, CAP, d), jnp.float32) * 0.5).astype(dtype)
    wg = (jax.random.normal(ks[1], (E, d, f), jnp.float32) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, d, f), jnp.float32) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, f, d), jnp.float32) * 0.05).astype(dtype)
    m = np.arange(S) % (E + 1)
    s2e = jnp.asarray(np.where(m == E, -1, m), jnp.int32)  # sprinkle empty slots
    act = jax.random.bernoulli(ks[4], 0.7, (S,)).astype(jnp.int32)
    got = expert_ffn_grouped(x, wg, wu, wd, s2e, act)
    want = expert_ffn_grouped_ref(x, wg, wu, wd, s2e, act)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )
    # inactive or empty slots are exactly zero
    dead = (np.asarray(act) == 0) | (np.asarray(s2e) < 0)
    assert (np.asarray(got, np.float32)[dead] == 0).all()


# ---------------------------------------------------------------------------
# Flash-decode attention kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,nh,nkv,hd,S", [
    (2, 8, 4, 64, 1024),
    (1, 16, 16, 128, 512),  # MHA
    (4, 8, 1, 64, 2048),    # MQA
    (2, 6, 6, 64, 768),     # whisper-like, non-pow2 seq
])
@pytest.mark.parametrize("frac", [0.3, 1.0])
def test_decode_attention_sweep(B, nh, nkv, hd, S, dtype, frac):
    ks = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = (jax.random.normal(ks[0], (B, nh, hd), jnp.float32)).astype(dtype)
    kc = (jax.random.normal(ks[1], (B, S, nkv, hd), jnp.float32)).astype(dtype)
    vc = (jax.random.normal(ks[2], (B, S, nkv, hd), jnp.float32)).astype(dtype)
    vl = jnp.int32(max(1, int(S * frac)))
    got = decode_attention(q, kc, vc, vl)
    want = decode_attention_ref(q, kc, vc, vl)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_decode_attention_softcap():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 8, 64), jnp.float32) * 3
    kc = jax.random.normal(ks[1], (2, 512, 4, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (2, 512, 4, 64), jnp.float32)
    got = decode_attention(q, kc, vc, jnp.int32(400), logit_cap=30.0)
    want = decode_attention_ref(q, kc, vc, jnp.int32(400), logit_cap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Kernel-as-scheduler integration: the Pallas AEBS kernel is a drop-in
# replacement for the jnp scheduler inside the scheduled MoE layer.
# ---------------------------------------------------------------------------


def test_aebs_kernel_drop_in_moe_layer():
    import jax
    from repro.configs import get_config
    from repro.core.aebs import ReplicaLayout, aebs_assign
    from repro.models import moe as moe_mod

    cfg = get_config("qwen2-moe-a2.7b-reduced")
    params = moe_mod.init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model), jnp.float32) * 0.3
    layout = ReplicaLayout.round_robin(cfg.num_experts, 2, 3)
    kw = dict(
        layout_tables=layout.device_tables(),
        slot_to_expert=jnp.asarray(layout.slot_to_expert.reshape(-1)),
        num_instances=2,
        capacity=64,
    )
    y_jnp = moe_mod.moe_layer(params, x, cfg, scheduler=aebs_assign, **kw)
    y_krn = moe_mod.moe_layer(
        params, x, cfg, scheduler=lambda e, t, n: aebs_schedule(e, t, n), **kw
    )
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_krn), atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# int8-KV flash-decode kernel (in-VMEM dequant — §Perf P3b)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,nh,nkv,hd,S", [
    (2, 8, 4, 64, 1024),
    (1, 16, 8, 128, 512),
    (2, 6, 6, 64, 768),
])
def test_decode_attention_int8_sweep(B, nh, nkv, hd, S):
    from repro.kernels.decode_attention.ops import decode_attention_int8
    from repro.kernels.decode_attention.ref import decode_attention_int8_ref
    from repro.models.attention import quantize_kv

    ks = jax.random.split(jax.random.PRNGKey(B * S + 1), 3)
    q = jax.random.normal(ks[0], (B, nh, hd), jnp.float32)
    kc_f = jax.random.normal(ks[1], (B, S, nkv, hd), jnp.float32)
    vc_f = jax.random.normal(ks[2], (B, S, nkv, hd), jnp.float32)
    kc, ksc = quantize_kv(kc_f)
    vc, vsc = quantize_kv(vc_f)
    vl = jnp.int32(int(0.7 * S))
    got = decode_attention_int8(q, kc, vc, ksc, vsc, vl)
    want = decode_attention_int8_ref(q, kc, vc, ksc, vsc, vl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-3)
    # and close to the unquantised full-precision result
    full = decode_attention_ref(q, kc_f, vc_f, vl)
    err = np.abs(np.asarray(got) - np.asarray(full)).max()
    assert err < 0.05


def test_interpret_mode_follows_backend():
    """Off the TPU the kernels interpret by default; an explicit False still
    compiles for the TPU (the described-chip compile path)."""
    from repro.kernels import resolve_interpret

    assert jax.default_backend() != "tpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
