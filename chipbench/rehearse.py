#!/usr/bin/env python3
"""Rehearse a cell on the CPU: the same loop, deployment, reference and
check as ``run.py``, at a reduced size, with no device look-up.  Prints no
metrics.

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


def stand_in(cf: dict, mix: dict, chips: int) -> dict:
    """CPU sizes for a cell (``cf`` as ``harness.load_config`` gives it): the
    arch module's shrink of the ``program`` block, small deployment sizes
    (``run_cell`` merges them over the cell's own deployment, so its
    executor and scheduler stay) and short prompts."""
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
    return {"program": cf["equations"].reduced_program(cf["program"]), "serving": serving, "mix": small}


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
