#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 chipbench/run.py --workload dsv2-decode --seed 7 --seconds 30 --trace 0

Reads ``BENCHMARK.json`` at the root of the checkout; the cell names its
configuration (``chipbench/configs/``), its traffic mix
(``chipbench/traffic/``) and its chips.  Prints progress and the compared
numbers on standard error and, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``--trace 1`` adds ``breakdown``), then ``compared``.  Exits non-zero, with
no result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import cell

    bench = cell.load_benchmark()
    names = [w["name"] for w in bench["workloads"]]
    if args.workload not in names:
        print(f"chipbench: unknown workload {args.workload!r}; cells: {names}", file=sys.stderr)
        return 2
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(
            f"chipbench: cell {args.workload} needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr,
        )
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    # every program goes into the persistent cache, the eager operations the
    # engine runs per prompt-chunk length included, so that only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell.log(f"device {devs[0].device_kind} x{len(devs)}; compile cache {enable_compile_cache()}")
    out = cell.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), T_START, bench=bench)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
