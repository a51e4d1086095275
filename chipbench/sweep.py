#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest arrival rate whose
backlog does not grow over the window.

    python3 chipbench/sweep.py --workload <open-loop cell> --rates 0.2,0.35,0.5 --seconds 40

For each rate, in one process (weights made once, the engine rebuilt): the
cell's traffic at that rate, a lead-in, then the window.  Prints one JSON line
per rate: requests due in the window, finished in it, the backlog (due but
unfinished) when the window opened and when it closed, tokens/s and the TTFT
95th percentile.  The cell's rate is then written into its traffic file by
hand, as a number.
"""

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20261017)
    args = ap.parse_args()

    import jax
    import numpy as np

    from chipbench import cell, harness
    from chipbench import traffic as traffic_mod
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    bench = cell.load_benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cf = harness.load_config(wl["config"])
    base = traffic_mod.load(wl["traffic"])
    cfg = harness.model_config(cf)
    params = harness.make_params(cfg, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(base, arrivals=dict(base["arrivals"], rate_per_s=rate))
        engine = harness.build_engine(cfg, params, cf["serving"], args.seed)
        harness.warm_up(engine, mix, args.seed, cfg.vocab_size)
        gen = traffic_mod.Generator(mix, cfg.vocab_size, args.seed)
        w = harness.serve_window(engine, mix, gen, args.seconds)

        def backlog(t):
            return sum(1 for s in w.served if s.sent <= t and not (s.done and s.stamps[-1] <= t))

        due = [s for s in w.served if w.t_open <= s.sent <= w.t_close]
        fin = [s for s in w.served if s.done and w.t_open <= s.stamps[-1] <= w.t_close]
        toks = sum(1 for s in w.served for t in s.stamps if w.t_open <= t <= w.t_close)
        ttft = [
            (s.stamps[0] if s.stamps and s.stamps[0] <= w.t_close else w.t_close) - s.sent
            for s in due
        ]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "finished": len(fin),
            "backlog_open": backlog(w.t_open), "backlog_close": backlog(w.t_close),
            "tok_s": toks / args.seconds,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3 if ttft else None,
        }), flush=True)
        harness.free_engine(engine)
        del engine
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
