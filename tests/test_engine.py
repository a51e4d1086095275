"""Continuous-batching serving engine: end-to-end behaviour."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.aebs import ReplicaLayout
from repro.core.amax import make_routing_trace
from repro.core.placement import build_layout
from repro.models import model as model_mod
from repro.serving.engine import ServingEngine
from repro.serving.request import Request, WorkloadSpec, sample_requests
from repro.serving.trace import poisson_arrivals


@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_config("qwen2-moe-a2.7b-reduced")
    params = model_mod.init_params(cfg, 0)
    trace = make_routing_trace(512, cfg.num_experts, cfg.top_k, skew=0.8, seed=0)
    layout = build_layout(trace, cfg.num_experts, num_instances=2, capacity=3)
    return cfg, params, layout


def _requests(cfg, n=8, seed=0):
    spec = WorkloadSpec(mean_input=6, mean_output=10, vocab_size=cfg.vocab_size,
                        max_input=16, max_output=16, seed=seed)
    arr = poisson_arrivals(100.0, n / 100.0, seed=seed)[:n]
    if len(arr) < n:
        arr = np.linspace(0, 0.1, n)
    return sample_requests(spec, arr, with_prompts=True)


def test_engine_completes_all_requests(moe_setup):
    cfg, params, layout = moe_setup
    reqs = _requests(cfg, 6)
    eng = ServingEngine(cfg, params, max_batch=3, cache_len=64, layout=layout, scheduler="aebs")
    m = eng.run(reqs, max_steps=2000)
    assert m["completed"] == 6
    assert m["tokens"] == sum(r.generated for r in eng.completed)
    assert m["tpot_mean"] > 0
    for r in eng.completed:
        assert r.generated >= 1
        assert len(r.token_times) == r.generated + 1  # prefill token + decodes


def test_scheduler_does_not_change_tokens(moe_setup):
    """AEBS only relocates replica computation — greedy decode tokens must be
    identical with and without scheduling (numerical transparency, e2e)."""
    cfg, params, layout = moe_setup
    outs = {}
    for sched in ("none", "aebs"):
        reqs = _requests(cfg, 4, seed=3)
        eng = ServingEngine(
            cfg, params, max_batch=2, cache_len=64,
            layout=layout if sched != "none" else None,
            scheduler=sched, capacity_tokens=64,
        )
        eng.run(reqs, max_steps=1000)
        outs[sched] = [r.generated for r in sorted(eng.completed, key=lambda r: r.rid)]
    assert outs["none"] == outs["aebs"]


def test_engine_dense_arch():
    cfg = get_config("phi4-mini-3.8b-reduced")
    params = model_mod.init_params(cfg, 0)
    reqs = _requests(cfg, 4)
    eng = ServingEngine(cfg, params, max_batch=2, cache_len=64, scheduler="none")
    m = eng.run(reqs, max_steps=1000)
    assert m["completed"] == 4


def test_engine_modeled_clock(moe_setup):
    """step_time_fn drives the clock deterministically (simulation mode)."""
    cfg, params, layout = moe_setup
    reqs = _requests(cfg, 4)
    eng = ServingEngine(
        cfg, params, max_batch=4, cache_len=64, layout=layout,
        step_time_fn=lambda n_active: 0.01,
    )
    m = eng.run(reqs, max_steps=1000)
    gaps = np.diff(eng.completed[0].token_times)
    assert np.allclose(gaps, 0.01, atol=1e-9)


# ---------------------------------------------------------------------------
# the decode step's input tokens: written on the host, uploaded once a step
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dsv2_setup():
    cfg = get_config("dsv2-lite-reduced")
    params = model_mod.init_params(cfg, 0)
    layout = ReplicaLayout.round_robin(cfg.num_experts, 2, 3)
    return cfg, params, layout


def _paged_engine(cfg, params, layout, executor="mono", **kw):
    if executor == "disagg":
        kw.update(n_attn=2, n_prefill=1)
    return ServingEngine(
        cfg, params, max_batch=3, cache_len=64, layout=layout, scheduler="aebs",
        capacity_tokens=64, kv_page_size=16, prefill_chunk=8,
        executor=executor, step_time_fn=lambda n: 2e-3, **kw,
    )


def _staggered_requests(cfg):
    """Seven requests of mixed lengths through three slots: admissions,
    completions and slot reuse interleave with decode."""
    rng = np.random.default_rng(5)
    lens = [(9, 6), (14, 3), (5, 9), (11, 2), (7, 5), (16, 4), (6, 7)]
    return [
        Request(rid=i, arrival=0.001 * i, input_len=n, output_len=out,
                prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32), token_times=[])
        for i, (n, out) in enumerate(lens)
    ]


@pytest.mark.parametrize("executor,spec_k", [("mono", 0), ("disagg", 0), ("mono", 2)],
                         ids=["mono", "disagg", "mono_spec"])
def test_one_token_upload_per_decode_iteration(dsv2_setup, executor, spec_k):
    """Each decode iteration uploads the step's tokens exactly once, whatever
    the number of active slots, and every active slot is fed its newest
    emitted token."""
    cfg, params, layout = dsv2_setup
    spec = dict(draft_config=cfg, spec_k=spec_k) if spec_k else {}
    eng = _paged_engine(cfg, params, layout, executor, **spec)
    active_counts, fed_steps, owners = [], [], {}

    inner_iter = eng._decode_iteration

    def counting():
        active_counts.append(eng.slots.num_active)
        for s in eng.slots.active_slots:
            owners.setdefault(s, set()).add(eng.slots.slot_req[s].rid)
        inner_iter()

    inner_once = eng._decode_once

    def recording(positions, spec=None):
        col = np.asarray(spec[0] if spec is not None else eng._tokens_in)[:, 0]
        fed_steps.append(
            [(col[s], eng.slots.slot_req[s].tokens_out[-1]) for s in eng.slots.active_slots]
        )
        return inner_once(positions, spec)

    eng._decode_iteration = counting
    eng._decode_once = recording
    m = eng.run(_staggered_requests(cfg), max_steps=2000)
    assert m["completed"] == 7
    assert m["token_uploads"] == eng.token_uploads == len(active_counts) > 0
    assert len(fed_steps) == len(active_counts)
    assert len(set(active_counts)) > 1  # the batch filled and drained
    assert any(len(rids) > 1 for rids in owners.values())  # slots were reused
    for step in fed_steps:
        for fed, newest in step:
            assert fed == newest


def test_mono_decode_iteration_makes_no_implicit_upload(dsv2_setup):
    """The mono decode iteration moves data to the device only by explicit
    transfers: no eager per-slot token write, no NumPy argument to a jitted
    step."""
    cfg, params, layout = dsv2_setup
    eng = _paged_engine(cfg, params, layout, admission="blocking")
    inner = eng._decode_iteration

    def under_guard():
        with jax.transfer_guard_host_to_device("disallow"):
            inner()

    eng._decode_iteration = under_guard
    m = eng.run(_staggered_requests(cfg), max_steps=2000)
    assert m["completed"] == 7
    assert eng.steps_done >= 10
