import os

# 512 virtual host devices stand in for the production mesh, so the dry-run
# must run on the CPU backend even on a host with a TPU (where the fan-out's
# children would otherwise fight over the chip).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_DRYRUN_EXTRA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, prove memory feasibility, and extract roofline terms.

MUST be invoked as its own process (device count is locked at first jax
init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-moe-a2.7b \
        --shape decode_32k [--multi-pod] [--out results/dryrun]

    PYTHONPATH=src python -m repro.launch.dryrun --all   # fan out everything
"""

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.configs import PAPER_MODELS, REGISTRY, SHAPES, get_config, shape_supported  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import BUILDERS  # noqa: E402
from repro.roofline import analysis  # noqa: E402


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    cfg = get_config(arch)
    if os.environ.get("DRYRUN_KV_QUANT"):
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_quant=True)
        arch = arch + "+int8kv"
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        step, abs_args = BUILDERS[shape.kind](cfg, mesh, shape)
        lowered = step.lower(*abs_args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.models.transformer import period_pattern

    _, n_periods = period_pattern(cfg)
    if shape.kind == "decode":
        n_periods = 1  # serve_step unrolls the layer loop (§Perf P1)
    terms = analysis.analyze(
        arch,
        shape_name,
        mesh_name,
        chips,
        cost,
        hlo,
        mem,
        analysis.model_flops_estimate(cfg, shape),
        loop_scale=float(n_periods),
    )
    rec = terms.to_dict()
    rec.update(
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory_analysis=str(mem),
        hlo_collective_count=terms.collective_breakdown.get("count", 0),
    )
    os.makedirs(out_dir, exist_ok=True)
    fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    print(
        f"[dryrun] {arch} × {shape_name} × {mesh_name}: "
        f"compile={t_compile:.0f}s peak_mem/dev={terms.peak_memory_per_device/2**30:.2f}GiB "
        f"t_comp={terms.t_compute*1e3:.2f}ms t_mem={terms.t_memory*1e3:.2f}ms "
        f"t_coll={terms.t_collective*1e3:.2f}ms dominant={terms.dominant}"
    )
    print(mem)
    print({k: v for k, v in cost.items() if k in ("flops", "bytes accessed")})
    return rec


PAPER_ARCHS = tuple(PAPER_MODELS)


def all_combos(include_paper: bool = False):
    archs = [a for a in REGISTRY if a not in PAPER_ARCHS]
    if include_paper:
        archs += list(PAPER_ARCHS)
    for arch in archs:
        for shape_name in SHAPES:
            if arch in PAPER_ARCHS and shape_name == "train_4k":
                continue  # the paper's models are serving-only in its eval
            ok, _ = shape_supported(get_config(arch), SHAPES[shape_name])
            if ok:
                yield arch, shape_name


def fan_out(out_dir: str, multi_pod_also: bool, jobs: int, include_paper: bool = False) -> int:
    """Run every combo as a subprocess (device count is per-process)."""
    tasks = []
    for arch, shape_name in all_combos(include_paper):
        for mp in ([False, True] if multi_pod_also else [False]):
            mesh_name = "2x16x16" if mp else "16x16"
            fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
            if os.path.exists(fname):
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape_name, "--out", out_dir,
            ] + (["--multi-pod"] if mp else [])
            tasks.append(cmd)
    print(f"[dryrun] {len(tasks)} combos to run, {jobs} parallel")
    running, failed = [], []
    while tasks or running:
        while tasks and len(running) < jobs:
            cmd = tasks.pop(0)
            running.append((cmd, subprocess.Popen(cmd, env=dict(os.environ, JAX_PLATFORMS="cpu"))))
        time.sleep(2)
        still = []
        for cmd, p in running:
            if p.poll() is None:
                still.append((cmd, p))
            elif p.returncode != 0:
                failed.append(cmd)
                print("[dryrun] FAILED:", " ".join(cmd[3:]))
        running = still
    print(f"[dryrun] done, {len(failed)} failures")
    return len(failed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--paper-models", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    if args.all:
        sys.exit(fan_out(args.out, multi_pod_also=True, jobs=args.jobs,
                         include_paper=args.paper_models))
    run_one(args.arch, args.shape, args.multi_pod, args.out)


if __name__ == "__main__":
    main()
