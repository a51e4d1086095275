"""Training launcher.

CPU-scale run (real execution):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
        --reduced --steps 200 --batch 8 --seq 128

Production-mesh launch (TPU; on CPU use --dry-run to lower+compile only):
    PYTHONPATH=src python -m repro.launch.train --arch yi-34b --shape train_4k --dry-run
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    if args.dry_run:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"  # the virtual mesh is host devices
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import run_one

        run_one(args.arch, args.shape, args.multi_pod, "results/dryrun")
        return

    from repro.configs import get_config
    from repro.training.train_loop import train

    name = args.arch + ("-reduced" if args.reduced else "")
    cfg = get_config(name)
    res = train(
        cfg,
        steps=args.steps,
        batch_size=args.batch,
        seq_len=args.seq,
        seed=args.seed,
        ckpt_dir=args.ckpt_dir,
    )
    print(
        f"done: loss {res['first_loss']:.4f} → {res['final_loss']:.4f} "
        f"in {res['wall_s']:.1f}s"
    )


if __name__ == "__main__":
    main()
