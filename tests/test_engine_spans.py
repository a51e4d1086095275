"""The engine's profiler spans (``janus.*``): which are emitted, how they
nest, and the counts they carry.

A tiny paged mono engine with blocking admission serves three requests
under ``jax.profiler.start_trace`` on the CPU; the recorded ``.xplane.pb`` is
read back with ``jax.profiler.ProfileData``.  The disaggregated executor's
``janus.split.*`` stages are checked on four virtual CPU devices in a child
process.
"""

import glob
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.aebs import ReplicaLayout
from repro.models import model as model_mod
from repro.serving.engine import ServingEngine
from repro.serving.request import Request

ROOT = Path(__file__).resolve().parents[1]

DECODE_PHASES = {"janus.decode.prepare", "janus.decode.device", "janus.decode.sample",
                 "janus.decode.advance"}


def read_spans(trace_dir):
    """Every ``janus.*`` host event of the trace: (name, start, end, stats)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("janus."):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


@pytest.fixture(scope="module")
def mono_run(tmp_path_factory):
    """Three requests (prompts of 14, 20 and 8 tokens, 8-token chunks) on a
    2-slot paged mono engine; returns the spans, the engine, the requests and
    the active slot count before each decode iteration."""
    cfg = get_config("dsv2-lite-reduced")
    params = model_mod.init_params(cfg, 0)
    layout = ReplicaLayout.round_robin(cfg.num_experts, 2, 3)
    eng = ServingEngine(cfg, params, max_batch=2, cache_len=64, layout=layout,
                        scheduler="aebs", capacity_tokens=64, kv_page_size=16,
                        prefill_chunk=8, admission="blocking")
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=10 + i, arrival=0.0, input_len=n, output_len=out,
                prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32), token_times=[])
        for i, (n, out) in enumerate([(14, 4), (20, 5), (8, 2)])
    ]
    active = []
    inner = eng._decode_iteration

    def counting():
        active.append(eng.slots.num_active)
        inner()

    eng._decode_iteration = counting
    trace_dir = tmp_path_factory.mktemp("mono_trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        eng.run(reqs, max_steps=100)
    finally:
        jax.profiler.stop_trace()
    assert len(eng.completed) == 3
    return read_spans(trace_dir), eng, reqs, active


def test_mono_span_names(mono_run):
    spans, _, _, _ = mono_run
    assert {s[0] for s in spans} == {
        "janus.admit", "janus.prefill.chunk", "janus.prefill.handoff", "janus.decode",
    } | DECODE_PHASES


def test_decode_phases_nest_inside_decode(mono_run):
    spans, _, _, active = mono_run
    decode = [s for s in spans if s[0] == "janus.decode"]
    assert len(decode) == len(active)
    for s in spans:
        if s[0] in DECODE_PHASES:
            assert inside(s, decode), s
    # one of each phase per iteration, in order
    for name in DECODE_PHASES:
        assert sum(s[0] == name for s in spans) == len(decode)
    for d in decode:
        phases = [s[0] for s in spans if s[0] in DECODE_PHASES and inside(s, [d])]
        assert phases == ["janus.decode.prepare", "janus.decode.device",
                          "janus.decode.sample", "janus.decode.advance"]


def test_decode_carries_step_and_active_slots(mono_run):
    spans, eng, reqs, active = mono_run
    decode = [s for s in spans if s[0] == "janus.decode"]
    assert [s[3]["active"] for s in decode] == active
    assert [s[3]["step"] for s in decode] == list(range(len(decode)))
    # every active slot gains one token per iteration
    assert sum(active) == sum(r.generated for r in reqs)
    advance = [s for s in spans if s[0] == "janus.decode.advance"]
    assert sum(s[3]["completed"] for s in advance) == len(eng.completed)
    prepare = [s for s in spans if s[0] == "janus.decode.prepare"]
    dirty = [s[3]["dirty"] for s in prepare]
    assert set(dirty) <= {0, 1}
    # request 10 decodes from position 14 into its second 16-row page
    assert 1 in dirty


def test_prepare_counts_token_uploads(mono_run):
    spans, eng, _, active = mono_run
    prepare = [s for s in spans if s[0] == "janus.decode.prepare"]
    uploads = [s[3]["tokens"] for s in prepare]
    assert set(uploads) <= {0, 1}
    # every iteration feeds freshly written tokens: one upload each
    assert sum(uploads) == eng.token_uploads == eng.metrics()["token_uploads"] == len(active)


def test_prefill_chunks_of_a_request_cover_its_prompt(mono_run):
    spans, _, reqs, _ = mono_run
    admits = [s for s in spans if s[0] == "janus.admit"]
    assert [(s[3]["rid"], s[3]["prompt_len"]) for s in admits] == [(r.rid, r.input_len) for r in reqs]
    chunks = [s for s in spans if s[0] == "janus.prefill.chunk"]
    for r in reqs:
        mine = [s[3] for s in chunks if s[3]["rid"] == r.rid]
        assert sum(c["n"] for c in mine) == r.input_len
        assert [c["lo"] for c in mine] == list(range(0, r.input_len, 8))
    # blocking admission: each chunk and its hand-off lie inside one admission
    handoffs = [s for s in spans if s[0] == "janus.prefill.handoff"]
    assert len(handoffs) == len(chunks)
    for s in chunks + handoffs:
        assert inside(s, admits), s
    for a in admits:
        mine = [h[3] for h in handoffs if inside(h, [a])]
        assert {h["slot"] for h in mine} == {a[3]["slot"]}
        assert sum(h["n"] for h in mine) == a[3]["prompt_len"]


SPLIT_SCRIPT = r"""
import glob, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from jax.profiler import ProfileData
from repro.configs import get_config
from repro.core.aebs import ReplicaLayout
from repro.models import model as model_mod
from repro.serving.engine import ServingEngine
from repro.serving.request import Request

assert len(jax.devices()) == 4
cfg = get_config("dsv2-lite-reduced")
params = model_mod.init_params(cfg, 0)
layout = ReplicaLayout.round_robin(cfg.num_experts, 2, 3)
eng = ServingEngine(cfg, params, max_batch=4, cache_len=32, layout=layout,
                    scheduler="aebs", capacity_tokens=64, executor="disagg",
                    n_attn=2, ping_pong=True, kv_page_size=16, prefill_chunk=8)
rng = np.random.default_rng(0)
reqs = [Request(rid=i, arrival=0.0, input_len=n, output_len=3,
                prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32), token_times=[])
        for i, n in enumerate([6, 9])]
trace_dir = sys.argv[1]
jax.profiler.start_trace(trace_dir)
eng.run(reqs, max_steps=50)
jax.profiler.stop_trace()
assert len(eng.completed) == 2
(path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
         for plane in ProfileData.from_file(path).planes for line in plane.lines
         for ev in line.events if ev.name.startswith("janus.")]
layers = [kind for _, _, kind, _ in eng.disagg._layers]
print("SPANS " + json.dumps({"spans": spans, "layers": layers, "n_micro": eng.disagg.n_micro}))
"""


@pytest.mark.subprocess
def test_disagg_split_stages_per_layer(tmp_path):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SPLIT_SCRIPT, str(tmp_path)],
                       capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=540)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    out = json.loads(r.stdout.split("SPANS ", 1)[1])
    spans, layers, n_micro = out["spans"], out["layers"], out["n_micro"]
    assert n_micro == 2
    decode = [s for s in spans if s[0] == "janus.decode"]
    split = [s for s in spans if s[0].startswith("janus.split.")]
    assert decode and split
    device = [s for s in spans if s[0] == "janus.decode.device"]
    for s in split:
        assert inside(s, device), s
    for d in device:
        mine = [s for s in split if inside(s, [d])]
        assert [s[0] for s in mine].count("janus.split.head") == 1
        for li, kind in enumerate(layers):
            for mb in range(n_micro):
                got = sorted(s[0] for s in mine if s[3].get("layer") == li and s[3].get("mb") == mb)
                want = ["janus.split.attn"] if kind == "dense" else [
                    "janus.split.attn", "janus.split.combine", "janus.split.exchange",
                    "janus.split.moe"]
                assert got == want, (li, mb, got)
