#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 chipbench/control.py --workload dsv2-decode --seeds 11,12,13 --control-seeds 11 --seconds 8

For each seed: one run of the cell's timed path with a short window at the
cell's own load, then, on the same sampled requests, every number the check
compares for the program (the lower readings) and for the fp8 control put in
the program's place (the upper readings), with each one's verdict against
the cell's limits: the control's has to be ``false``.  One JSON line per
seed on standard output.  The benchmark's own runs never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", default="", help="comma-separated: the seeds that also read the control")
    args = ap.parse_args()

    import jax

    from chipbench import cell
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    with_control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        modes = ("f32", "fp8") if seed in with_control else ("f32",)
        out = cell.run_cell(args.workload, seed, args.seconds, False, time.perf_counter(), modes=modes)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": out["correct"],
                          "readings": out.get("readings"), "verdicts": out.get("verdicts"),
                          "compared": out["compared"], "metrics": out["metrics"],
                          "memory_peak_bytes": out["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
