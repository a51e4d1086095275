"""Two-pool decode shootout: mono vs disagg vs disagg+ping-pong.

Measures one continuous-batching decode step of ``dsv2-lite-reduced`` across
(batch, n_a, n_e) sweeps in three execution modes and writes
``BENCH_disagg_pipeline.json`` at the repo root:

* ``mono``            — the jitted monolithic ``decode_step`` (one device);
* ``disagg``          — :class:`repro.serving.disagg.DisaggExecutor`,
  sequential per-layer exchange (attention pool → MoE pool → back);
* ``disagg_pingpong`` — the same executor with m=2 micro-batch ping-pong
  (attention of micro-batch i overlapped with MoE of micro-batch i+1).

Forced-host CPU "devices" share one execution queue, so the wall clock
cannot express cross-pool overlap; the analytic prediction from
``benchmarks.sec6_pipelining.pipeline_times`` is printed next to every
measured row.  The executor's stages are ``janus.split.*`` profiler spans:
a trace of the step shows them beside the device work.

Run:  PYTHONPATH=src python -m benchmarks.disagg_pipeline_bench
"""

from __future__ import annotations

import json
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    # eight virtual host devices stand in for the pools: pin the CPU backend
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, paper_perf_model, timeit
from benchmarks.sec6_pipelining import pipeline_times
from repro.configs import get_config
from repro.core.aebs import ReplicaLayout
from repro.models import model as model_mod
from repro.launch.steps import build_disagg_executor

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(REPO_ROOT, "BENCH_disagg_pipeline.json")

ARCH = "dsv2-lite-reduced"
CACHE_LEN = 64
# (batch, n_attn, n_moe)
SWEEPS = [(32, 2, 2), (256, 2, 2), (256, 2, 4), (512, 2, 4)]


def _setup(cfg, B):
    params = model_mod.init_params(cfg, 0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab_size)
    positions = jnp.full((B,), CACHE_LEN // 2, jnp.int32)
    caches = model_mod.init_decode_caches(cfg, B, CACHE_LEN)
    return params, tokens, positions, caches


def _bench_mono(cfg, params, tokens, positions, caches, layout, cap, repeat):
    from repro.core.aebs import aebs_assign

    moe_ctx = dict(
        dispatch="grouped",
        layout_tables=layout.device_tables(),
        slot_to_expert=jnp.asarray(layout.slot_to_expert.reshape(-1)),
        num_instances=layout.num_instances,
        scheduler=aebs_assign,
        capacity=cap,
    )
    step = jax.jit(
        lambda p, t, c, i: model_mod.decode_step(p, t, c, i, cfg, extra={"moe_ctx": moe_ctx})
    )
    call = lambda: jax.block_until_ready(step(params, tokens, caches, positions)[0])
    return timeit(call, repeat=repeat, warmup=2)


def run_sweeps(repeat: int = 5) -> Dict:
    cfg = get_config(ARCH)
    pm, _ = paper_perf_model()
    results = []
    for B, n_a, n_e in SWEEPS:
        params, tokens, positions, caches = _setup(cfg, B)
        layout = ReplicaLayout.round_robin(cfg.num_experts, n_e, 2)
        cap = 4 * B  # ample: keeps the three modes token-identical
        mono_us = _bench_mono(cfg, params, tokens, positions, caches, layout, cap, repeat)

        def make(pp):
            ex = build_disagg_executor(
                cfg, params, n_a, n_e, max_batch=B, cache_len=CACHE_LEN,
                layout=layout, capacity=cap, ping_pong=pp,
            )
            ex.load_caches(caches)
            return ex

        ex_seq = make(False)
        seq_us = timeit(
            lambda: jax.block_until_ready(ex_seq.decode_step(tokens, positions)[0]),
            repeat=repeat, warmup=2,
        )
        _, tel = ex_seq.decode_step(tokens, positions)

        ex_pp = make(True)
        pp_us = timeit(
            lambda: jax.block_until_ready(ex_pp.decode_step(tokens, positions)[0]),
            repeat=repeat, warmup=2,
        )

        t_seq_pred, pipes_pred = pipeline_times(pm, B, n_a, n_e, ms=(2,))
        entry = {
            "arch": ARCH, "batch": B, "n_attn": n_a, "n_moe": n_e,
            "mono_step_ms": round(mono_us / 1e3, 3),
            "disagg_step_ms": round(seq_us / 1e3, 3),
            "disagg_pingpong_wall_ms": round(pp_us / 1e3, 3),
            "regime": tel["regime"],
            "transfer_bytes_per_step": tel["bytes_total"],
            "analytic_paper_scale": {
                "t_seq_us": round(t_seq_pred * 1e6, 1),
                "t_pipe_m2_us": round(pipes_pred[2] * 1e6, 1),
            },
        }
        results.append(entry)
    return {
        "bench": "disagg_pipeline",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "notes": "wall times on shared-core forced-host devices serialise "
                 "cross-pool work; ample capacity so all three modes emit "
                 "identical tokens",
        "sweeps": results,
    }


def run() -> List[Row]:
    """Harness entry point (benchmarks.run)."""
    report = run_sweeps(repeat=3)
    with open(OUT_PATH, "w") as fh:
        json.dump(report, fh, indent=2)
    rows: List[Row] = []
    for e in report["sweeps"]:
        rows.append(
            (
                f"disagg_pipeline/B{e['batch']}_a{e['n_attn']}e{e['n_moe']}",
                e["disagg_step_ms"] * 1e3,
                f"mono={e['mono_step_ms']}ms pp={e['disagg_pingpong_wall_ms']}ms "
                f"({e['regime']}) "
                f"analytic_seq={e['analytic_paper_scale']['t_seq_us']}us",
            )
        )
    return rows


def main() -> None:
    report = run_sweeps()
    with open(OUT_PATH, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"wrote {OUT_PATH}  (devices={report['devices']})")
    for e in report["sweeps"]:
        print(
            f"B={e['batch']} {e['n_attn']}A{e['n_moe']}E [{e['regime']}]: "
            f"mono={e['mono_step_ms']}ms disagg={e['disagg_step_ms']}ms "
            f"pp_wall={e['disagg_pingpong_wall_ms']}ms | §6 analytic "
            f"seq={e['analytic_paper_scale']['t_seq_us']}us "
            f"pipe={e['analytic_paper_scale']['t_pipe_m2_us']}us"
        )


if __name__ == "__main__":
    main()
