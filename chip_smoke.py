#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, in one process.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the attention/MoE split over four chips

One chip: the Pallas kernels at dsv2-lite widths against their references,
then ``ServingEngine`` serving dsv2-lite-4l (dsv2-lite at its published
widths, cut to 4 layers) through the code ``repro.launch.serve`` builds with —
monolithic, then disaggregated with one attention device and two MoE
instances aliased onto the chip.  ``--chips 4`` runs only the cross-chip
path and what it is compared with: monolithic on chip 0, then one attention
chip and three MoE chips on the same requests.

Each phase prints its lines; wall times are smoke timings of a single cold
run, not a benchmark.  Any failed check exits non-zero.  The last line of
standard output is a JSON object naming the device, printed only when every
phase passed.  Weights and inputs are random, made from ``SEED``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "dsv2-lite-4l"
SEED = 0
PAGE_SIZE = 16
CACHE_LEN = 2048
MAX_BATCH = 16
OUTPUT_TOKENS = 32
# 8 prompts of 128–512 tokens, multiples of the 64-token prefill chunk so
# every chunk call shares one compiled shape
PROMPT_LENS = (128, 192, 256, 320, 384, 448, 512, 256)
# expert-FFN kernel check: 17 slots (one instance's share of 64 experts plus
# one replica) at capacity 64
FFN_SLOTS, FFN_CAP = 17, 64
# paged-attention kernel check: (query heads, KV heads) of dsv2-lite and of
# the phi4-mini GQA layout, both with head_dim 128
ATTN_HEADS = ((16, 16), (24, 8))
AEBS_INSTANCES, AEBS_TOKENS = 4, 256

# Kernel vs reference, as max|got − ref| / max|ref|.  Both sides take bf16
# inputs, accumulate in f32 and round the result to bf16 (the FFN also rounds
# its hidden activation to bf16, on both sides), so they differ by bf16
# rounding (2^-8 relative) and f32 summation order; a wrong page, head or
# expert block gives an error of order 1.
KERNEL_TOL = 2e-2
# Disaggregated vs monolithic first-step logits, as ‖Δ‖/‖mono‖ over the
# active rows.  The executors run the same bf16 math as differently shaped
# programs (per-layer stages and slot-stacked expert weights vs one fused
# step over logical experts), so each layer's activations may differ by bf16
# rounding, compounded over 4 layers; a wrong expert, a lost token or a
# misplaced KV row moves logits by order 1.
LOGIT_TOL = 5e-2


class SmokeFailure(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str):
    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    yield
    print(f"[{name}] ok — wall {time.perf_counter() - t0:.1f} s (smoke timing, not a benchmark)", flush=True)


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def print_memory(tag: str) -> None:
    import jax

    for d in jax.devices():
        st = d.memory_stats() or {}
        print(
            f"[{tag}] memory device {d.id}: bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')}",
            flush=True,
        )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform {platform!r} "
            f"({len(devs)} device(s))"
        )
    mode = os.environ.get("REPRO_PAGED_DECODE", "auto")
    check(mode == "auto", f"REPRO_PAGED_DECODE={mode!r} would steer paged decode off the kernel")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, found {len(devs)}")
    print(f"[devices] platform={platform} kind={devs[0].device_kind} count={len(devs)}", flush=True)
    return devs


def kernels(cfg) -> None:
    """The three Pallas kernels, compiled, at dsv2-lite widths vs their refs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.amax import make_routing_trace
    from repro.core.placement import build_layout
    from repro.kernels.aebs.ops import aebs_schedule
    from repro.kernels.aebs.ref import aebs_ref
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.kernels.expert_ffn.ops import expert_ffn_grouped
    from repro.kernels.expert_ffn.ref import expert_ffn_grouped_ref

    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(SEED), 12)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    x = jax.random.normal(ks[0], (FFN_SLOTS, FFN_CAP, d), bf16)
    wg = (jax.random.normal(ks[1], (E, d, f), jnp.float32) * d**-0.5).astype(bf16)
    wu = (jax.random.normal(ks[2], (E, d, f), jnp.float32) * d**-0.5).astype(bf16)
    wd = (jax.random.normal(ks[3], (E, f, d), jnp.float32) * f**-0.5).astype(bf16)
    s2e = jnp.asarray(np.where(np.arange(FFN_SLOTS) == 5, -1, (np.arange(FFN_SLOTS) * 7) % E), jnp.int32)
    active = jax.random.bernoulli(ks[4], 0.8, (FFN_SLOTS,)).astype(jnp.int32)
    got = expert_ffn_grouped(x, wg, wu, wd, s2e, active)
    with jax.default_matmul_precision("highest"):
        want = expert_ffn_grouped_ref(x, wg, wu, wd, s2e, active)
    err = rel_err(got, want)
    print(f"[kernels] expert_ffn_grouped S={FFN_SLOTS} CAP={FFN_CAP} d={d} E={E} f={f}: rel err {err:.3e} (tol {KERNEL_TOL})", flush=True)
    check(err <= KERNEL_TOL, f"expert_ffn_grouped rel err {err} > {KERNEL_TOL}")
    del x, wg, wu, wd, got, want

    hd = cfg.resolved_head_dim
    nblk = CACHE_LEN // PAGE_SIZE
    num_pages = MAX_BATCH * nblk + 1
    rng = np.random.default_rng(SEED)
    for nh, nkv in ATTN_HEADS:
        q = jax.random.normal(ks[5], (MAX_BATCH, nh, hd), bf16)
        kp = jax.random.normal(ks[6], (num_pages, PAGE_SIZE, nkv, hd), bf16)
        vp = jax.random.normal(ks[7], (num_pages, PAGE_SIZE, nkv, hd), bf16)
        bt = jnp.asarray(1 + rng.permutation(num_pages - 1).reshape(MAX_BATCH, nblk), jnp.int32)
        lens = jnp.asarray(rng.integers(1, CACHE_LEN + 1, MAX_BATCH), jnp.int32)
        got = paged_decode_attention(q, kp, vp, bt, lens)
        with jax.default_matmul_precision("highest"):
            want = paged_decode_attention_ref(q, kp, vp, bt, lens)
        err = rel_err(got, want)
        print(f"[kernels] paged_decode_attention heads {nh}/{nkv} hd={hd} page={PAGE_SIZE} B={MAX_BATCH} S={CACHE_LEN}: rel err {err:.3e} (tol {KERNEL_TOL})", flush=True)
        check(err <= KERNEL_TOL, f"paged_decode_attention {nh}/{nkv} rel err {err} > {KERNEL_TOL}")
        del q, kp, vp, got, want

    trace = make_routing_trace(2048, E, cfg.top_k, skew=0.8, seed=SEED)
    layout = build_layout(trace, E, AEBS_INSTANCES, E // AEBS_INSTANCES + 1)
    tables = layout.device_tables()
    eids = jnp.asarray(trace[:AEBS_TOKENS])
    got = aebs_schedule(eids, tables, AEBS_INSTANCES)
    want = aebs_ref(eids, tables["expert_hosts"], tables["replica_counts"], tables["slot_of"])
    same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(got, want))
    print(f"[kernels] aebs E={E} top-{cfg.top_k} instances={AEBS_INSTANCES} T={AEBS_TOKENS}: identical to ref: {same}", flush=True)
    check(same, "aebs kernel disagrees with its reference (integer outputs must match exactly)")


def build_engine(executor: str, n_instances: int, params):
    """The engine ``repro.launch.serve`` would build from these flags."""
    from repro.launch import serve

    argv = [
        "--arch", ARCH, "--seed", str(SEED),
        "--kv-page-size", str(PAGE_SIZE), "--cache-len", str(CACHE_LEN),
        "--max-batch", str(MAX_BATCH), "--n-instances", str(n_instances),
        "--executor", executor, "--n-attn", "1",
    ]
    return serve.build_engine(serve.parse_args(argv), params)


def make_requests(cfg):
    import numpy as np

    from repro.serving.request import Request

    rng = np.random.default_rng(SEED)
    return [
        Request(
            rid=i, arrival=0.0, input_len=n, output_len=OUTPUT_TOKENS,
            prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            token_times=[],
        )
        for i, n in enumerate(PROMPT_LENS)
    ]


class LogitProbe:
    """Wraps an engine's decode call: keeps the first step's logits of the
    active slots and checks every step's active logits are finite."""

    def __init__(self, eng):
        import jax.numpy as jnp
        import numpy as np

        self.first = None
        self.steps = 0
        self.nonfinite_steps = 0
        inner = eng._decode_once

        def probe(positions, spec=None):
            logits, tel = inner(positions, spec)
            rows = np.asarray(eng.slots.active_slots, np.int32)
            live = logits[rows]
            if self.first is None:
                self.first = (rows, np.asarray(live, np.float32))
            self.steps += 1
            self.nonfinite_steps += int(not bool(jnp.isfinite(live).all()))
            return logits, tel

        eng._decode_once = probe


def serve_once(tag: str, eng, cfg, check_kernel: bool):
    if check_kernel:
        # the monolithic decode step is one jitted program: its compiled text
        # shows whether paged decode attention took the Pallas kernel
        text = (
            eng._decode_jit.lower(
                eng.params, eng.tokens, eng.caches, eng.slots.positions_device()
            ).compile().as_text()
        )
        found = "tpu_custom_call" in text
        print(f"[{tag}] decode step compiled text contains tpu_custom_call: {found}", flush=True)
        check(found, "the jitted decode step has no Pallas kernel (tpu_custom_call)")
    probe = LogitProbe(eng)
    reqs = make_requests(cfg)
    t0 = time.perf_counter()
    m = eng.run(reqs)
    wall = time.perf_counter() - t0
    print(
        f"[{tag}] executor={eng.executor_name} completed={m['completed']}/{len(reqs)} "
        f"tokens={m['tokens']} decode_steps={probe.steps} "
        f"serve wall {wall:.1f} s (smoke timing, not a benchmark)",
        flush=True,
    )
    for key in ("kv_pages", "regime_counts", "transfer_bytes_total", "amax_mean"):
        if key in m:
            print(f"[{tag}] {key}: {m[key]}", flush=True)
    check(m["completed"] == len(reqs), f"{tag}: {m['completed']} of {len(reqs)} requests finished")
    for r in reqs:
        check(
            r.tokens_out is not None and len(r.tokens_out) == OUTPUT_TOKENS + 1 and not r.truncated,
            f"{tag}: request {r.rid} emitted {len(r.tokens_out or [])} tokens, wanted {OUTPUT_TOKENS + 1}",
        )
    check(probe.first is not None, f"{tag}: no decode step ran")
    check(probe.nonfinite_steps == 0, f"{tag}: {probe.nonfinite_steps} decode steps had non-finite logits")
    print(f"[{tag}] logits finite on all {probe.steps} decode steps", flush=True)
    return probe.first, {r.rid: list(r.tokens_out) for r in reqs}


def compare(tag: str, mono, disagg, mono_streams, disagg_streams) -> None:
    import numpy as np

    (rows_m, lm), (rows_d, ld) = mono, disagg
    check(np.array_equal(rows_m, rows_d), f"{tag}: first steps served different slots {rows_m} vs {rows_d}")
    err = float(np.linalg.norm(ld - lm) / max(np.linalg.norm(lm), 1e-30))
    row_err = np.linalg.norm(ld - lm, axis=1) / np.maximum(np.linalg.norm(lm, axis=1), 1e-30)
    print(
        f"[{tag}] first-step logits disagg vs mono: rel err {err:.3e} (tol {LOGIT_TOL}), "
        f"worst row {float(row_err.max()):.3e}, max |Δ| {float(np.abs(ld - lm).max()):.3e}",
        flush=True,
    )
    same = sum(
        int(a == b)
        for rid in mono_streams
        for a, b in zip(mono_streams[rid], disagg_streams[rid])
    )
    total = sum(len(v) for v in mono_streams.values())
    print(f"[{tag}] identical greedy tokens: {same}/{total} = {same / total:.3f} (reported, not a gate)", flush=True)
    check(err <= LOGIT_TOL, f"{tag}: disagg logits differ from mono by {err} > {LOGIT_TOL}")


def split_placement(eng, devs) -> None:
    """The four-chip split: attention on chip 0, expert slots on chips 1–3,
    and the per-layer exchange landing on the MoE chips."""
    import jax

    ex = eng.disagg
    attn = [d.id for d in ex.pools.attn_devices]
    moe = [d.id for d in ex.pools.moe_devices]
    print(f"[split] attention devices {attn}, MoE devices {moe}", flush=True)
    check(attn == [devs[0].id] and moe == [d.id for d in devs[1:4]], "pools are not 1 attention chip + 3 MoE chips")
    for g, dev in enumerate(ex.pools.moe_devices):
        homes = {d.id for leaf in jax.tree.leaves(ex._moe_params[g]) for d in leaf.devices()}
        print(f"[split] MoE instance {g}: slot weights on devices {sorted(homes)}", flush=True)
        check(homes == {dev.id}, f"MoE instance {g} weights live on {homes}, not device {dev.id}")


def exchange_probe(eng):
    """Record the devices each per-layer exchange delivered activations to."""
    landed = set()
    ex = eng.disagg
    inner = ex._run_exchange

    def probe(h2s, regime, tel):
        outs = inner(h2s, regime, tel)
        for a in outs:
            landed.update(d.id for d in a.devices())
        return outs

    ex._run_exchange = probe
    return landed


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4 = only the attention/MoE split over four chips and its monolithic reference",
    )
    args = ap.parse_args()

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as model_mod

    devs = check_devices(args.chips)
    print(f"[devices] compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    print(f"[config] {cfg.name}: d_model={cfg.d_model} heads={cfg.num_heads}x{cfg.resolved_head_dim} experts={cfg.num_experts} (top-{cfg.top_k}, {cfg.num_shared_experts} shared, f={cfg.d_ff_expert}) vocab={cfg.vocab_size} layers={cfg.num_layers}; cuts={cfg.cuts}", flush=True)
    n_instances = 3 if args.chips == 4 else 2

    if args.chips == 1:
        with phase("kernels"):
            kernels(cfg)

    with phase("params"):
        params = model_mod.init_params(cfg, SEED)
        jax.block_until_ready(params)
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"[params] {n} parameters on device {devs[0].id}", flush=True)

    tag = "mono" if args.chips == 1 else "mono-chip0"
    with phase(tag):
        eng = build_engine("mono", n_instances, params)
        mono_first, mono_streams = serve_once(tag, eng, cfg, check_kernel=True)
        print_memory(tag)
    del eng
    gc.collect()

    tag = "disagg-aliased" if args.chips == 1 else "disagg-split"
    with phase(tag):
        eng = build_engine("disagg", n_instances, params)
        if args.chips == 4:
            split_placement(eng, devs)
            landed = exchange_probe(eng)
        disagg_first, disagg_streams = serve_once(tag, eng, cfg, check_kernel=False)
        print_memory(tag)
        if args.chips == 4:
            print(f"[{tag}] exchange delivered activations to devices {sorted(landed)}", flush=True)
            check(landed == {d.id for d in devs[1:4]}, f"exchange landed on {landed}, not the MoE chips")
            for g, dev in enumerate(eng.disagg.pools.moe_devices):
                held = sum(x.nbytes for x in jax.tree.leaves(eng.disagg._moe_params[g]))
                in_use = (dev.memory_stats() or {}).get("bytes_in_use", 0)
                print(f"[{tag}] device {dev.id}: slot weights {held} bytes, bytes_in_use {in_use}", flush=True)
                check(in_use >= held, f"device {dev.id} holds less than its slot weights")
        compare(tag, mono_first, disagg_first, mono_streams, disagg_streams)

    print(json.dumps({
        "ok": True,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
    }))


if __name__ == "__main__":
    main()
