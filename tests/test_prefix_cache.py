"""Prefix cache: allocator refcounts and radix-index invariants (property
tests), splice/COW semantics, prefix-hit-vs-cold bit-identical streams on
both executors (including a mid-run attention kill and a prefill-device
requeue), the page-leak guard under a cancel/reject storm, and the operator
surface (CLI flags, shared-prefix workload, autoscaler prefill discount).

The load-bearing claim everywhere: serving a prefix hit is *block-table
splicing only* — the shared span's pages hold rows any prompt with that
token prefix would have produced bit-identically, so warm streams equal
cold streams by construction, and the only thing that changes is who pays
for prefill.
"""

import sys

import numpy as np
import pytest
from _hypo import given, settings, st

from repro.configs import get_config
from repro.models import model as model_mod
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PageAllocator, PagedKVCache, PrefixIndex
from repro.serving.request import (
    WorkloadSpec,
    sample_requests,
    shared_prefix_spec,
)

PS = 16  # page size used throughout the engine-level tests


# ---------------------------------------------------------------------------
# allocator refcounts
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=99), min_size=1, max_size=120))
def test_page_allocator_refcount_roundtrip(ops):
    """Any alloc/ref/free interleaving against a model of per-page refcounts:
    a page leaves the free list at first alloc, survives every free but the
    last, and the free + in-use split always accounts for the whole pool."""
    num_pages = 8
    alloc = PageAllocator(num_pages)
    model = {}  # page -> refcount
    for op in ops:
        kind = op % 3
        if kind == 0:
            try:
                p = alloc.alloc()
            except RuntimeError:
                assert alloc.num_free == 0
                continue
            assert p not in model  # never hand out a held page
            model[p] = 1
        elif model:
            p = sorted(model)[op % len(model)]
            if kind == 1:
                alloc.ref(p)
                model[p] += 1
            else:
                alloc.free(p)
                model[p] -= 1
                if model[p] == 0:
                    del model[p]
                    assert alloc.refcount(p) == 0
                else:
                    assert alloc.refcount(p) == model[p]  # still held
        assert alloc.in_use == len(model)
        assert alloc.num_free + alloc.in_use == num_pages - 1
        for p, r in model.items():
            assert alloc.refcount(p) == r
    for p, r in list(model.items()):
        for _ in range(r):
            alloc.free(p)
    assert alloc.in_use == 0 and alloc.num_free == num_pages - 1


def test_page_allocator_ref_errors():
    alloc = PageAllocator(4)
    with pytest.raises(RuntimeError, match="unallocated"):
        alloc.ref(1)
    p = alloc.alloc()
    alloc.ref(p)
    alloc.free(p)
    assert alloc.refcount(p) == 1  # second holder keeps it alive
    alloc.free(p)
    with pytest.raises(RuntimeError, match="double free"):
        alloc.free(p)


# ---------------------------------------------------------------------------
# splice / copy-on-write
# ---------------------------------------------------------------------------


def test_splice_adopts_full_pages_and_cows_partial():
    pager = PagedKVCache(4, 64, 16)
    pager.ensure(0, 39)  # rows 0..39 → 3 pages, last one 8 rows deep
    src = pager.slot_pages(0)
    cow = pager.splice(1, src, 40)
    assert cow is not None
    src_pg, dst_pg, rows = cow
    assert src_pg == src[2] and rows == 40 - 2 * 16
    assert dst_pg not in src  # partial boundary gets a private page
    assert pager.slot_pages(1)[:2] == src[:2]  # full pages adopted by ref
    assert pager.allocator.refcount(src[0]) == 2
    assert pager.allocator.refcount(src[2]) == 1  # partial page NOT shared
    assert pager.hiwater[1] == 40
    # page-aligned splice needs no COW
    assert pager.splice(2, src, 32) is None
    assert pager.allocator.refcount(src[1]) == 3
    # releasing a splicer only drops its pins
    pager.release(1)
    pager.release(2)
    assert pager.allocator.refcount(src[0]) == 1
    assert sorted(pager.pages_of([0])) == sorted(src)
    with pytest.raises(RuntimeError, match="fresh slot"):
        pager.splice(0, src, 16)
    with pytest.raises(ValueError, match="need"):
        pager.splice(3, src[:1], 40)


# ---------------------------------------------------------------------------
# radix index: publish / lookup / evict
# ---------------------------------------------------------------------------


def _publish(index, pager, tokens, slot=0):
    """Prefill ``slot`` far enough to back ``tokens`` and publish the
    chunk-aligned prefix, then release the slot (index pins survive)."""
    upto = (len(tokens) // index.chunk) * index.chunk
    if upto:
        pager.ensure(slot, upto - 1)
        index.publish(np.asarray(tokens, np.int32), upto, slot)
    pager.release(slot)
    return upto


def test_prefix_index_publish_lookup_roundtrip():
    pager = PagedKVCache(2, 64, 16)
    index = PrefixIndex(8, pager)
    tokens = np.arange(32, dtype=np.int32)
    pager.ensure(0, 31)
    owned = pager.slot_pages(0)
    assert index.publish(tokens, 32, 0) == 4  # one node per chunk
    pager.release(0)
    match, pages = index.lookup(tokens)
    assert match == 32 and pages == owned
    # diverging tail: only the shared chunks match
    fork = tokens.copy()
    fork[20:] += 1000
    match, pages = index.lookup(fork)
    assert match == 16 and pages == owned[:1]
    # limit caps the walk (the full-hit cap in the engine)
    match, _ = index.lookup(tokens, limit=24)
    assert match == 24
    assert index.lookup(tokens + 7)[0] == 0
    s = index.stats()
    assert s["hits"] == 3 and s["misses"] == 1
    # chunk (8) < page (16): consecutive chunk nodes pin the same page, so
    # shared_pages counts *pins* (4 nodes × 1 page), not unique pages
    assert s["shared_pages"] == 4 and s["nodes"] == 4
    assert s["saved_tokens"] == 32 + 16 + 24


@settings(max_examples=40, deadline=None)
@given(
    st.lists(
        st.lists(st.integers(min_value=0, max_value=1), min_size=1, max_size=24),
        min_size=1,
        max_size=10,
    )
)
def test_prefix_index_matches_longest_prefix_model(prompts):
    """Against a brute-force model over a 2-token vocab (maximal prefix
    collisions): lookup always returns the longest chunk-aligned prefix
    shared with *some* published prompt, and allocator accounting matches
    the index's pin count exactly."""
    chunk = 4
    pager = PagedKVCache(1, 32, 4, num_pages=257)
    index = PrefixIndex(chunk, pager)
    published = []
    for prompt in prompts:
        prompt = prompt[: (len(prompt) // chunk) * chunk + chunk - 1][:24]
        toks = np.asarray(prompt, np.int32)
        want = 0
        for p in published:
            n = 0
            while (
                n + chunk <= min(len(p), len(toks))
                and list(p[n : n + chunk]) == list(toks[n : n + chunk])
            ):
                n += chunk
            want = max(want, n)
        got, pages = index.lookup(toks)
        assert got == want
        assert len(pages) == (got + pager.page_size - 1) // pager.page_size
        _publish(index, pager, toks)
        if (len(toks) // chunk) * chunk:
            published.append(list(toks))
        # every pin the index holds is a live allocator ref; nothing else is
        assert pager.allocator.in_use >= index.stats()["nodes"] * 0
        assert index.held_pages == sum(len(n.pages) for n in index._nodes)
    index.drop_all()
    assert pager.allocator.in_use == 0


def test_prefix_index_lru_leaf_eviction_respects_splices():
    """Over-budget inserts evict least-recently-used *leaves*; eviction only
    drops the index's pin, so a page still spliced into a live block table
    survives until that slot releases it."""
    chunk, ps = 4, 4
    pager = PagedKVCache(2, 16, 4, num_pages=40)
    index = PrefixIndex(chunk, pager, max_pages=4)
    a = np.arange(16, dtype=np.int32)
    b = np.arange(16, dtype=np.int32) + 100
    _publish(index, pager, a)
    match, a_pages = index.lookup(a)
    assert match == 16
    assert pager.splice(1, a_pages, 16) is None  # page-aligned, live holder
    _publish(index, pager, b)  # held 8 > budget 4 → A's chain evicted
    s = index.stats()
    assert s["shared_pages"] == 4 and s["evicted_pages"] == 4
    assert index.lookup(a)[0] == 0  # A is gone from the index...
    for p in a_pages:  # ...but its pages live on in slot 1's table
        assert pager.allocator.refcount(p) == 1
    assert index.lookup(b)[0] == 16  # B (recently used) survived
    pager.release(1)
    assert pager.allocator.in_use == 4  # only B's pins remain


# ---------------------------------------------------------------------------
# engine level: warm == cold, on both executors
# ---------------------------------------------------------------------------


def _shared_reqs(cfg, n=6, stagger=0.5, seed=123, shared=10):
    """Shared system prompt + unique tails, staggered so request i publishes
    before request i+1 submits."""
    spec = WorkloadSpec(mean_input=6, mean_output=6, vocab_size=cfg.vocab_size,
                        max_input=12, max_output=8, seed=seed)
    rs = sample_requests(spec, np.arange(n) * stagger, with_prompts=True)
    rng = np.random.default_rng(seed)
    head = rng.integers(0, cfg.vocab_size, size=shared, dtype=np.int32)
    for i, r in enumerate(rs):
        tail = rng.integers(0, cfg.vocab_size, size=4 + i % 3, dtype=np.int32)
        r.prompt = np.concatenate([head, tail])
        r.input_len = len(r.prompt)
    return rs


def _streams(eng):
    return {r.rid: tuple(r.tokens_out) for r in eng.completed}


def _assert_no_leaks(eng):
    """After a drain, the only live pages are prefix-index pins, and every
    page's refcount equals exactly the number of nodes pinning it."""
    from collections import Counter

    if getattr(eng, "disagg", None) is not None:
        pairs = zip(eng.disagg._indexes or [], eng.disagg._pagers)
    else:
        pairs = [(eng.prefix, eng.paged)]
    for idx, pager in pairs:
        pins = Counter(p for node in idx._nodes for p in node.pages)
        assert pager.allocator.in_use == len(pins)
        for p, c in pins.items():
            assert pager.allocator.refcount(p) == c


@pytest.fixture(scope="module")
def phi4():
    cfg = get_config("phi4-mini-3.8b-reduced")
    return cfg, model_mod.init_params(cfg, 0)


def _mono_engine(cfg, params, **kw):
    return ServingEngine(
        cfg, params, max_batch=3, cache_len=64, scheduler="none",
        n_prefill=1, prefill_chunk=8, kv_page_size=PS,
        step_time_fn=lambda n: 2e-3,
        prefill_time_fn=lambda n: 1e-3 + n * 1e-3, **kw,
    )


def test_mono_prefix_hit_streams_bit_identical(phi4):
    """Cold vs warm vs warm+batched on the mono engine: identical streams,
    a real hit rate, faster warm TTFT, and a drained pool afterwards (only
    the index's own pins remain in use)."""
    cfg, params = phi4
    runs = {}
    for name, kw in (
        ("cold", {}),
        ("warm", dict(prefix_cache=True)),
        ("batched", dict(prefix_cache=True, prefill_batch=3)),
    ):
        eng = _mono_engine(cfg, params, **kw)
        m = eng.run(_shared_reqs(cfg), max_steps=4000)
        assert m["completed"] == 6
        runs[name] = (_streams(eng), m, eng)
    assert runs["warm"][0] == runs["cold"][0]
    assert runs["batched"][0] == runs["cold"][0]
    for name in ("warm", "batched"):
        s = runs[name][1]["prefix_cache"]
        assert s["hits"] >= 4 and s["saved_tokens"] > 0
        _assert_no_leaks(runs[name][2])
    assert runs["warm"][1]["ttft_mean"] < runs["cold"][1]["ttft_mean"]


def test_prefix_hit_with_speculation_streams_bit_identical(phi4):
    """A warm prefix splice hands the verify path a KV cache the engine never
    prefilled itself (adopted pages + CoW tail); the draft model rebuilds its
    own cache from the prompt tokens, and the streams stay bit-identical to
    the cold non-speculative run."""
    cfg, params = phi4
    eng_cold = _mono_engine(cfg, params)
    m_cold = eng_cold.run(_shared_reqs(cfg), max_steps=4000)
    eng_spec = _mono_engine(cfg, params, prefix_cache=True,
                            draft_config=cfg, spec_k=2)
    m_spec = eng_spec.run(_shared_reqs(cfg), max_steps=4000)
    assert m_cold["completed"] == m_spec["completed"] == 6
    assert _streams(eng_spec) == _streams(eng_cold)
    s = m_spec["prefix_cache"]
    assert s["hits"] >= 4 and s["saved_tokens"] > 0  # splices actually happened
    assert m_spec["spec"]["accepted_per_step"] > 1.0  # speculation ran on them
    _assert_no_leaks(eng_spec)


def test_prefix_cache_requires_paged_kv(phi4):
    cfg, params = phi4
    with pytest.raises(ValueError, match="paged KV"):
        ServingEngine(cfg, params, max_batch=2, cache_len=64,
                      scheduler="none", prefix_cache=True)


@pytest.fixture(scope="module")
def dsv2():
    from repro.core.aebs import ReplicaLayout

    cfg = get_config("dsv2-lite-reduced")
    params = model_mod.init_params(cfg, 0)
    layout = ReplicaLayout.round_robin(cfg.num_experts, 2, 3)
    return cfg, params, layout


def _disagg_engine(cfg, params, layout, **kw):
    from repro.serving.faults import RetryPolicy

    return ServingEngine(
        cfg, params, max_batch=4, cache_len=64, layout=layout,
        scheduler="aebs", capacity_tokens=64, executor="disagg",
        n_attn=2, n_prefill=1, prefill_chunk=4, kv_page_size=PS,
        step_time_fn=lambda n: 2e-3,
        prefill_time_fn=lambda n: 1e-3 + n * 1e-3,
        retry_policy=RetryPolicy(recovery_charge_s=0.01), **kw,
    )


def test_disagg_prefix_hit_streams_bit_identical(dsv2):
    """Per-shard indexes on the disagg executor: warm streams equal cold,
    and the splices survive a mid-run attention-device kill (replay) and a
    prefill-device kill (requeue → release → re-splice) bit-identically."""
    from repro.serving.faults import DEVICE_LOSS, FaultPlan, FaultSpec

    cfg, params, layout = dsv2

    def reqs():
        # request 0 publishes the prefix at ~0.02s then decodes for a long
        # window; 1..5 arrive at ~0.04s, splice, and queue on the single
        # prefill device — so a prefill-pool kill at step 14 (~0.05s of
        # decode clock) catches live PREFILLING slots and must requeue them
        rs = _shared_reqs(cfg, seed=9, shared=10, stagger=0.0)
        rs[0].output_len = 40
        for i, r in enumerate(rs[1:]):
            r.arrival = 0.04 + 0.001 * i
        return rs

    runs = {}
    for name, kw in (
        ("cold", {}),
        ("warm", dict(prefix_cache=True)),
        ("warm_attn_kill", dict(
            prefix_cache=True,
            fault_plan=FaultPlan(faults=[
                FaultSpec(DEVICE_LOSS, pool="attn", index=1, at_step=6)]),
        )),
        ("warm_prefill_kill", dict(
            prefix_cache=True,
            fault_plan=FaultPlan(faults=[
                FaultSpec(DEVICE_LOSS, pool="prefill", index=0, at_step=14)]),
        )),
    ):
        eng = _disagg_engine(cfg, params, layout, **kw)
        m = eng.run(reqs(), max_steps=4000)
        assert m["completed"] == 6, name
        runs[name] = (_streams(eng), m, eng)
    for name in ("warm", "warm_attn_kill", "warm_prefill_kill"):
        assert runs[name][0] == runs["cold"][0], name
        assert runs[name][1]["prefix_cache"]["hits"] >= 2, name
    f = runs["warm_attn_kill"][1]["faults"]
    assert f["recoveries"] == 1 and f["degraded"] == 0
    f = runs["warm_prefill_kill"][1]["faults"]
    assert f["recoveries"] == 1 and f["requeued"] >= 1 and f["degraded"] == 0
    # requeue replayed through splice without leaking reserved pages
    _assert_no_leaks(runs["warm_prefill_kill"][2])


# ---------------------------------------------------------------------------
# page-leak guard: cancel / reject storm
# ---------------------------------------------------------------------------


def test_cancel_reject_storm_releases_pages(phi4):
    """Requests cancelled *mid-prefill* (deadline lapses while the slot is
    RESERVED/PREFILLING, after their prefix splice) must release every
    reserved page and drop their pins: after the storm the allocator is back
    to baseline — the only pages in use are the index's own."""
    cfg, params = phi4
    eng = _mono_engine(cfg, params, prefix_cache=True)
    reqs = _shared_reqs(cfg, n=8, stagger=0.1)
    rng = np.random.default_rng(7)
    for i, r in enumerate(reqs):
        if 2 <= i < 6:
            # long doomed prompts: ~5 chunks ≈ 45ms of modeled prefill, so
            # the 20ms deadline lapses while the slot is mid-prefill
            tail = rng.integers(0, cfg.vocab_size, size=30, dtype=np.int32)
            r.prompt = np.concatenate([reqs[0].prompt[:10], tail])
            r.input_len = len(r.prompt)
            r.deadline = r.arrival + 0.02
    m = eng.run(reqs, max_steps=6000)
    assert m["completed"] == 4 and m["rejected"] == 4
    s = m["prefix_cache"]
    assert s["hits"] >= 4  # the doomed requests spliced before dying
    _assert_no_leaks(eng)


def test_cancel_slot_api_releases_pages(phi4):
    """Direct cancel_slot on an in-flight prefill: the worker drops the
    in-flight work, the splice's pages free, and the request comes back."""
    cfg, params = phi4
    eng = _mono_engine(cfg, params, prefix_cache=True)
    r0, r1 = _shared_reqs(cfg, n=2, stagger=0.0)
    m = eng.run([r0], max_steps=2000)  # publishes the shared prefix
    assert m["completed"] == 1
    baseline = eng.paged.allocator.in_use  # index pins only
    assert baseline > 0
    eng._submit_request(r1)  # reserves a slot, splices, queues the prefill
    assert r1.slot >= 0
    assert eng.paged.allocator.in_use > baseline  # splice holds pages
    got = eng.cancel_slot(r1.slot)
    assert got is r1
    _assert_no_leaks(eng)
    assert eng.slots.slot_req[r1.slot] is None


# ---------------------------------------------------------------------------
# operator surface: workload preset, CLI, autoscaler discount
# ---------------------------------------------------------------------------


def test_shared_prefix_workload_preset():
    spec = shared_prefix_spec(vocab_size=100, seed=4)
    reqs = sample_requests(spec, np.linspace(0, 1, 6), with_prompts=True)
    heads = {tuple(r.prompt[: spec.shared_prefix_len]) for r in reqs}
    assert len(heads) == 1  # every prompt opens with the same system prompt
    tails = {tuple(r.prompt[spec.shared_prefix_len :]) for r in reqs}
    assert len(tails) > 1
    for r in reqs:
        assert r.input_len == len(r.prompt) >= spec.shared_prefix_len + 1
    # default spec is unchanged (shared_prefix_len=0 leaves sampling alone)
    base = sample_requests(WorkloadSpec(vocab_size=100, seed=4),
                           np.linspace(0, 1, 6), with_prompts=True)
    assert all(r.prompt is not None for r in base)


def test_serve_cli_prefix_cache(monkeypatch, capsys):
    from repro.launch import serve

    monkeypatch.setattr(
        sys, "argv",
        ["serve", "--arch", "phi4-mini-3.8b-reduced", "--scheduler", "none",
         "--rate", "40", "--duration", "0.1", "--max-batch", "2",
         "--cache-len", "128", "--kv-page-size", "16", "--prefix-cache",
         "--prefix-cache-pages", "32", "--prefill-batch", "2",
         "--n-prefill", "1", "--prefill-chunk", "8",
         "--workload", "shared-prefix"],
    )
    serve.main()
    out = capsys.readouterr().out
    assert "prefix_cache" in out and "kv_pages" in out


def test_autoscaler_prefix_discount_shrinks_prefill_pool():
    from repro.core.scaling import PerfModel
    from repro.serving.controller import AutoScaler

    cfg = get_config("dsv2-lite-reduced")
    ctrl = AutoScaler(PerfModel(cfg, slots_per_instance=3, s_ctx=64), slo=0.2,
                      n_max=8, prefill_tok_rate=100.0, window=10.0)
    ctrl.observe(0.0, 16.0, input_tokens=4000.0)
    assert ctrl.decide_prefill(1.0) == 4
    # a warm cache serving 80% of prompt tokens shrinks the pool demand
    ctrl._prefix_saved_frac = 0.8
    assert ctrl.decide_prefill(1.0) == 1
    # per-request knowledge of saved tokens discounts at observe() instead
    ctrl2 = AutoScaler(PerfModel(cfg, slots_per_instance=3, s_ctx=64), slo=0.2,
                       n_max=8, prefill_tok_rate=100.0, window=10.0)
    ctrl2.observe(0.0, 16.0, input_tokens=4000.0, saved_input_tokens=3200.0)
    assert ctrl2.decide_prefill(1.0) == 1
