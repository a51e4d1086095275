"""One run of one cell: set-up, the measured window, metrics, correctness.

``run_cell`` is what ``run.py`` calls; ``rehearse.py`` and the tests call it
with a CPU stand-in (smaller sizes, no device look-up).

A cell's deployment is its configuration's ``serving`` block with
``deployments/<workload>.json``, where there is one, merged over it: so two
cells of one configuration can run it on different executors or chips.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from chipbench import check, harness
from chipbench import traffic as traffic_mod

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_DIR = BENCH_DIR / "traces"
DEPLOY_DIR = BENCH_DIR / "deployments"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_benchmark() -> Dict:
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def serving_for(workload: str, cf: Dict) -> Dict:
    """The cell's deployment: the configuration's ``serving`` block with the
    cell's own ``deployments/<workload>.json`` merged over it."""
    path = DEPLOY_DIR / f"{workload}.json"
    if not path.exists():
        return dict(cf["serving"])
    with open(path) as fh:
        return dict(cf["serving"], **json.load(fh))


def load_hardware(kind: str) -> Dict:
    with open(BENCH_DIR / "hardware.json") as fh:
        table = json.load(fh)["kinds"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in hardware.json")
    return table[kind]


@dataclasses.dataclass
class Results:
    """What a metric reader sees."""

    workload: Dict
    arch: ModuleType  # the configuration's layer equations and counts (``arch.py``)
    model: Dict  # the configuration file's ``program`` block
    serving: Dict  # the cell's deployment
    chips: int
    hw: Optional[Dict]
    setup_s: float
    window: harness.Window
    trace: Optional[object] = None  # tracing.Summary of a traced run


def read_metric(name: str, res: Results) -> Optional[float]:
    """Run the metric's own reader, ``metrics/<name>.py``."""
    mod = importlib.import_module(f"chipbench.metrics.{name}")
    return mod.read(res)


def cell_metrics(bench: Dict, workload: str, traced: bool) -> List[Dict]:
    """The metrics this cell reports: end-to-end ones untraced, per-layer
    ones traced; each only where it lists this cell, or lists none."""
    key = "per_layer" if traced else "end_to_end"
    return [m for m in bench[key] if workload in m.get("workloads", [workload])]


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, t_start: float,
    bench: Optional[Dict] = None, stand_in: Optional[Dict] = None,
    modes=("f32",), hooks=None,
) -> Dict:
    """One run.  ``stand_in`` (tests and rehearsal only) replaces the
    configuration's ``program`` block, the deployment's sizes and the mix
    with small CPU sizes and skips the device look-up.  ``modes`` adds the fp8 control's
    reading (``control.py``); ``hooks(engine)`` breaks the timed path
    (the fault tests).  The benchmark's own runs pass neither."""
    import jax

    bench = bench or load_benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == workload)
    chips = int(wl["chips"])
    cf = harness.load_config(wl["config"])
    mix = traffic_mod.load(wl["traffic"])
    equations = cf["equations"]
    model, serving = cf["program"], serving_for(workload, cf)
    if stand_in:
        model = stand_in.get("program", model)
        serving = dict(serving, **stand_in.get("serving", {}))
        mix = dict(mix, **stand_in.get("mix", {}))
    devices = jax.devices()[:chips]
    hw = None if stand_in else load_hardware(devices[0].device_kind)

    counter = harness.CompileCounter()
    cfg = harness.model_config({"program": model})
    t0 = time.perf_counter()
    params = harness.make_params(cfg, seed)
    t1 = time.perf_counter()
    engine = harness.build_engine(cfg, params, serving, seed)
    del params
    if hooks is not None:
        hooks(engine)
    t2 = time.perf_counter()
    warmed = harness.warm_up(engine, mix, seed, cfg.vocab_size)
    t3 = time.perf_counter()
    log(f"set-up: imports {t0 - t_start:.1f} s, weights {t1 - t0:.1f} s, engine {t2 - t1:.1f} s, "
        f"warm-up {t3 - t2:.1f} s ({warmed} requests, {len(counter.times)} compilations so far)")

    trace_dir = None
    win_ann = None
    on_open = None
    if trace:
        trace_dir = TRACE_DIR / workload
        shutil.rmtree(trace_dir, ignore_errors=True)

        def on_open():
            nonlocal win_ann
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1  # the harness's annotations, not JAX's internals
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            win_ann = jax.profiler.TraceAnnotation("chipbench.window")
            win_ann.__enter__()

    gen = traffic_mod.Generator(mix, cfg.vocab_size, seed)
    w = harness.serve_window(
        engine, mix, gen, seconds, counter=counter, annotate=trace, on_open=on_open,
    )
    if trace:
        win_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    setup_s = w.t_open - t_start
    log(f"traffic brought to its steady state in {w.t_open - t3:.1f} s; setup_s {setup_s:.1f}")
    counter.close()
    late = np.asarray(w.lateness) * 1e3
    log(f"window {seconds} s: {len(w.spans)} engine calls, {len(w.compiles)} compilations inside {w.compiles}; "
        f"generator lateness ms p50 {np.median(late) if len(late) else 0:.3f} "
        f"p99 {np.percentile(late, 99) if len(late) else 0:.3f} max {late.max() if len(late) else 0:.3f}")

    waits = sorted(
        ((s.stamps[0] if s.stamps and s.stamps[0] <= w.t_close else w.t_close) - s.sent, len(s.item.prompt))
        for s in w.served if w.t_open <= s.sent <= w.t_close
    )
    log(f"{len(waits)} requests sent in the window; the longest first-token waits (ms, prompt tokens): "
        + ", ".join(f"{1e3 * t:.1f} ({n})" for t, n in waits[-6:]))

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    res = Results(wl, equations, model, serving, chips, hw, setup_s, w)
    out: Dict = {}
    if trace:
        from chipbench import tracing

        t0 = time.perf_counter()
        res.trace = tracing.reduce_trace(tracing.find_xplane(str(trace_dir)))
        log(f"trace reduced in {time.perf_counter() - t0:.1f} s: busy {res.trace.busy_s:.4f} s "
            f"of {res.trace.window_s:.4f} s; per chip "
            + ", ".join(f"{c.name} {c.busy_s:.4f}" for c in res.trace.chips))
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        v = read_metric(m["name"], res)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # correctness: the KV rows and last logits of the sampled in-flight
    # requests come to the host, then the program's state is freed and the
    # reference runs
    finished = [s for s in w.served if s.done]
    due = [s for s in w.served if w.t_open <= s.sent <= w.t_close]
    in_flight = {id(engine.slots.slot_req[i]) for i in engine.slots.active_slots}
    active = [s for s in w.served if id(s.req) in in_flight and w.last_step.decoded(s.req)]
    chosen_f = check.sample(finished, seed, salt=13)
    chosen_a = check.sample(active, seed, salt=17, k=check.SAMPLE_ACTIVE)
    kv = [harness.read_kv(engine, s.req.slot) for s in chosen_a]
    logits = [w.last_step.row(s.req) for s in chosen_a]
    w.last_step.logits = None
    harness.free_engine(engine)
    del engine
    gc.collect()
    t0 = time.perf_counter()
    verdict = check.compare(
        equations, model, seed, finished, chosen_f, chosen_a, kv, logits, check.load_limits(workload), modes=modes,
    )
    log(f"reference compared {len(chosen_f)} finished and {len(chosen_a)} in-flight requests "
        f"in {time.perf_counter() - t0:.1f} s; readings per mode: {verdict['readings']}; "
        f"verdicts per mode: {verdict['verdicts']}; logits_rel_err per in-flight request "
        f"{verdict['logits_rel_err_each']} at routing margins {verdict['router_margin']}")

    out["correct"] = verdict["correct"]
    out["attempted"] = len(due)
    out["failed"] = sum(1 for s in due if s.req.truncated or s.req.rejected)
    out["metrics"] = metrics
    out["device"] = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(peak),
    }
    if trace:
        out["device"]["busy_s"] = res.trace.busy_s
        out["device"]["window_s"] = res.trace.window_s
        out["breakdown"] = res.trace.breakdown()
    if len(modes) > 1:
        out["readings"] = verdict["readings"]
        out["verdicts"] = verdict["verdicts"]
    out["compared"] = verdict["compared"]
    log(f"correct: {verdict['correct']}")
    for name, c in verdict["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    return out
