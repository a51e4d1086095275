"""The comparison that decides ``correct`` fails what it must fail.

Each test drives a whole run of a cell at a CPU size (the harness's look for
a chip skipped), with the timed path broken underneath, and sees the number
that the fault spoils go over the cell's limit and ``correct`` come out
false: a token altered where it is produced, the decode step's KV writes
dropped (the state left unchanged), half of the batch's rows left out, and
the head's logits scaled (no token changes).  The control -- the reference
in fp8 put in the program's place -- is judged by the same limits and comes
out not correct.

At the CPU size the program runs in float32 on the reference's bf16-valued
weights, so that a sound run reads every number far under its limit and
``correct`` true: bf16 arithmetic at this size flips greedy near-ties that
the published widths do not have (there the served token leads by ~15
logits), and a fault test that a sound run could fail would prove nothing.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell, check, harness
from chipbench import traffic as traffic_mod
from chipbench.rehearse import stand_in

SEED = 2_147_483_711


# outputs as long as the prompts, as in the cells, so that a decode fault has
# as much served context to spoil as it has there
LENGTHS = {
    "prompt_len": {"dist": "loguniform", "low": 8, "high": 40},
    "output_len": {"dist": "uniform", "low": 24, "high": 48},
}


@pytest.fixture(autouse=True)
def bf16_valued_weights(monkeypatch):
    """The program's float32 weights rounded to the bf16 values the
    reference makes."""
    make = harness.make_params

    def rounded(cfg, seed):
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype), make(cfg, seed))

    monkeypatch.setattr(harness, "make_params", rounded)


def run(workload, hooks=None, modes=("f32",), bench=None, seconds=2.0):
    bench = bench or cell.load_benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == workload)
    cf = harness.load_config(wl["config"])
    si = stand_in(cf, traffic_mod.load(wl["traffic"]), wl["chips"])
    si["mix"].update(LENGTHS)
    si["program"]["dtype"] = "float32"
    return cell.run_cell(workload, SEED, seconds, False, time.perf_counter(), bench=bench,
                         stand_in=si, modes=modes, hooks=hooks)


def wrap_decode(engine, after):
    inner = engine._decode_once
    state = {"step": 0}

    def broken(positions, spec=None):
        before = engine.caches
        logits, tel = inner(positions, spec)
        state["step"] += 1
        return after(engine, before, logits, state["step"]), tel

    engine._decode_once = broken


def token_altered(engine):
    def after(eng, before, logits, step):
        if step % 3:
            return logits
        rows = np.asarray(eng.slots.active_slots)
        best = jnp.argmax(logits[rows], axis=-1)
        return logits.at[rows, (best + 1) % logits.shape[-1]].add(1e4)
    wrap_decode(engine, after)


def state_unchanged(engine):
    def after(eng, before, logits, step):
        eng.caches = before  # the step's KV writes are lost
        return logits
    wrap_decode(engine, after)


def half_batch_left_out(engine):
    def after(eng, before, logits, step):
        rows = np.asarray(eng.slots.active_slots)[1::2]  # every other active row
        return logits.at[rows].set(0.0) if len(rows) else logits
    wrap_decode(engine, after)


def logits_scaled(engine):
    # a wrong final norm or head scale: every greedy token stays the same
    wrap_decode(engine, lambda eng, before, logits, step: logits * 1.25)


def test_sound_run_is_correct():
    c = run("dsv2-decode")
    assert c["correct"] is True, c["compared"]
    n = c["compared"]
    assert n["token_count_mismatches"]["value"] == 0
    assert n["widest_logit_gap"]["value"] == 0.0
    for name in ("kv_rel_err", "logits_rel_err"):
        assert 0 < n[name]["value"] <= 0.1 * n[name]["limit"], n


@pytest.mark.parametrize(
    "fault, number",
    [
        (token_altered, "widest_logit_gap"),
        (state_unchanged, "kv_rel_err"),
        (half_batch_left_out, "widest_logit_gap"),
        (logits_scaled, "logits_rel_err"),
    ],
    ids=["token_altered", "state_unchanged", "half_batch_left_out", "logits_scaled"],
)
@pytest.mark.parametrize("workload", ["dsv2-decode"])
def test_fault_is_caught(workload, fault, number):
    out = run(workload, hooks=fault)
    assert out["compared"][number]["value"] > out["compared"][number]["limit"], out["compared"]
    assert out["correct"] is False


def test_control_reads_above_the_program():
    out = run("dsv2-decode", modes=("f32", "fp8"))
    limits = check.load_limits("dsv2-decode")
    assert out["verdicts"] == {"f32": True, "fp8": False}, out["readings"]
    kv = out["readings"]["kv_rel_err"]
    assert kv["fp8"] > limits["kv_rel_err"]["limit"] > kv["f32"], out["readings"]


def exchange_left_out(engine):
    ex = engine.disagg
    inner = ex._run_exchange
    ex._run_exchange = lambda h2s, regime, tel: [jnp.zeros_like(a) for a in inner(h2s, regime, tel)]


def test_split_path_reads_its_cache_and_catches_a_lost_exchange(tmp_path, monkeypatch):
    """The harness also drives the attention/MoE split (no cell uses it yet):
    a four-device stand-in reads its KV back from the attention shard, is
    correct, and is caught when the exchange to the expert devices is lost."""
    if len(jax.devices()) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
    cf = harness.load_config("dsv2-lite-4l")
    cf["serving"].update(executor="disagg", n_instances=3, slots_per_instance=22)
    (tmp_path / "split.json").write_text(json.dumps(cf))
    monkeypatch.setattr(harness, "CONFIG_DIR", tmp_path)
    bench = cell.load_benchmark()
    bench["workloads"] = [{"name": "dsv2-decode", "config": "split", "traffic": "decode_closed", "chips": 4}]
    sound = run("dsv2-decode", bench=bench)
    assert sound["correct"] is True, sound["compared"]
    broken = run("dsv2-decode", hooks=exchange_left_out, bench=bench)
    assert broken["compared"]["kv_rel_err"]["value"] > broken["compared"]["kv_rel_err"]["limit"]
    assert broken["correct"] is False
