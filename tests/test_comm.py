"""Adaptive two-phase communication model (§3.3) behavioural tests."""

import pytest

from _hypo import given, settings, st

from repro.core.comm import (
    H100,
    TPU_V5E,
    CommConfig,
    adaptive_two_phase,
    agate_cost,
    hardware_for,
    layer_comm_time,
    one_phase_cost,
    two_phase_case1,
    two_phase_case2,
)


def _cfg(m, n, B=256, d=4096, hw=H100):
    return CommConfig(n_attn=m, n_moe=n, bytes_per_token=2 * d, batch=B, hw=hw)


def test_two_phase_beats_one_phase_at_scale():
    """§3.3: many small m×n transfers dominate — aggregation wins."""
    for m, n in [(8, 16), (16, 32), (4, 12)]:
        c = _cfg(m, n)
        t2, _ = adaptive_two_phase(c)
        assert t2 < one_phase_cost(c)


def test_adaptive_picks_min():
    for m, n in [(2, 2), (8, 8), (16, 64), (64, 8)]:
        c = _cfg(m, n)
        t, regime = adaptive_two_phase(c)
        assert t == min(two_phase_case1(c), two_phase_case2(c))
        assert regime in ("case1", "case2")


def test_case2_wins_with_many_destinations():
    """Fig. 6: large destination counts favour one-to-one + local multicast."""
    big = _cfg(32, 64, B=2048)
    assert two_phase_case2(big) < two_phase_case1(big)


def test_roundtrip_scales_with_batch():
    t_small = layer_comm_time(4, 8, 64, 4096, H100)
    t_big = layer_comm_time(4, 8, 4096, 4096, H100)
    assert t_big > t_small


def test_egate_vs_agate_regimes():
    """§5.3 / Fig. 12: with two-phase aggregation, MoE-side gating (full
    activations, no metadata) competes with attention-side gating even though
    it ships more bytes, because it avoids the per-destination messages."""
    c = _cfg(8, 16, B=128, d=5120)
    t_2pc_egate, _ = adaptive_two_phase(c)
    t_agate = agate_cost(c, top_k=8, num_experts=160)
    assert t_2pc_egate < t_agate * 2.5  # same order; aggregation pays for bytes


def test_tpu_constants_sane():
    assert TPU_V5E.peak_flops == 197e12
    assert TPU_V5E.hbm_bw == 819e9
    assert H100.fast_bw > H100.slow_bw


# ---------------------------------------------------------------------------
# Property sweeps: regime selection never regresses
# ---------------------------------------------------------------------------


@st.composite
def comm_case(draw, at_scale: bool = False):
    hw = draw(st.sampled_from([H100, TPU_V5E]))
    n_lo = 2 * hw.devices_per_node if at_scale else 1
    m = draw(st.integers(min_value=1, max_value=64))
    n = draw(st.integers(min_value=n_lo, max_value=128))
    batch = draw(st.integers(min_value=1, max_value=4096))
    d = draw(st.integers(min_value=64, max_value=8192))
    return CommConfig(n_attn=m, n_moe=n, bytes_per_token=2 * d, batch=batch, hw=hw)


@given(comm_case())
@settings(max_examples=120, deadline=None)
def test_adaptive_is_min_of_cases_prop(c):
    """adaptive_two_phase is exactly min(case1, case2), regime consistent."""
    t, regime = adaptive_two_phase(c)
    t1, t2 = two_phase_case1(c), two_phase_case2(c)
    assert t == min(t1, t2)
    assert regime == ("case1" if t1 <= t2 else "case2")
    assert t > 0.0


@given(comm_case(at_scale=True))
@settings(max_examples=120, deadline=None)
def test_adaptive_never_regresses_vs_one_phase_prop(c):
    """With ≥2 destination nodes, intra-node aggregation always pays:
    adaptive_two_phase(c)[0] <= min(one_phase, case1, case2) — the §3.3
    regression bound the strawman comparison benchmarks rely on."""
    t, _ = adaptive_two_phase(c)
    assert t <= min(one_phase_cost(c), two_phase_case1(c), two_phase_case2(c)) * (1 + 1e-12)


@given(comm_case(), st.integers(min_value=2, max_value=8))
@settings(max_examples=60, deadline=None)
def test_cost_monotone_in_batch_prop(c, factor):
    """More tokens never get cheaper to move (both regimes)."""
    import dataclasses

    bigger = dataclasses.replace(c, batch=c.batch * factor)
    assert adaptive_two_phase(bigger)[0] >= adaptive_two_phase(c)[0]
    assert one_phase_cost(bigger) >= one_phase_cost(c)


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_hardware_spec_by_device_kind():
    """A TPU is priced by its own kind; an unknown TPU kind is an error, not
    a default; other backends keep the modeled v5e spec."""
    assert hardware_for(_FakeDevice("tpu", "TPU v5 lite")) is TPU_V5E
    assert hardware_for(_FakeDevice("cpu", "cpu")) is TPU_V5E
    with pytest.raises(ValueError, match="TPU v9"):
        hardware_for(_FakeDevice("tpu", "TPU v9"))
