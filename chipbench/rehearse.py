#!/usr/bin/env python3
"""Rehearse a cell on the CPU: the same loop, reference and check as
``run.py``, at a reduced size, with no device look-up.  Prints no metrics.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload dsv2-decode

A cell on four chips needs four devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def reduced_program(m: dict) -> dict:
    """The configuration's ``program`` block at CPU-test sizes (the same
    shrink as ``ModelConfig.reduced``: 2 layers, d 256, 4 heads of 64, a
    512-token vocabulary, 4 experts of 128, top-2, 1 shared)."""
    r = dict(m, num_layers=2, d_model=256, num_heads=4, head_dim=64, vocab_size=512)
    r["num_kv_heads"] = 2 if m["num_kv_heads"] < m["num_heads"] else 4
    if m.get("num_experts"):
        r.update(num_experts=4, top_k=2, d_ff_expert=128, num_shared_experts=1)
    else:
        r["d_ff"] = 512
    return r


def stand_in(cf: dict, mix: dict, chips: int) -> dict:
    moe = bool(cf["program"].get("num_experts"))
    serving = dict(
        max_batch=4, cache_len=256, kv_page_size=16, kv_num_pages=None, prefill_chunk=16,
        capacity_tokens=4 if moe else None,
    )
    if moe:
        n = 3 if chips == 4 else 2
        serving.update(n_instances=n, slots_per_instance=-(-4 // n) + 1)
    small = dict(
        prompt_len={"dist": "loguniform", "low": 8, "high": 60},
        output_len={"dist": "uniform", "low": 4, "high": 12},
    )
    if "first_output_len" in mix:
        small["first_output_len"] = {"dist": "uniform", "low": 2, "high": 12}
    return {"program": reduced_program(cf["program"]), "serving": serving, "mix": small}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    t_start = time.perf_counter()

    from chipbench import cell, harness
    from chipbench import traffic as traffic_mod

    bench = cell.load_benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cf = harness.load_config(wl["config"])
    out = cell.run_cell(
        args.workload, args.seed, args.seconds, False, t_start, bench=bench,
        stand_in=stand_in(cf, traffic_mod.load(wl["traffic"]), wl["chips"]),
    )
    # at this size greedy near-ties can flip, so ``correct`` may read false
    # on the logit gap alone; the loop, the reference and the check ran
    print(f"rehearsal of {args.workload}: correct={out['correct']} attempted={out['attempted']} "
          f"failed={out['failed']} compared={out['compared']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
