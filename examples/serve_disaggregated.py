"""Disaggregated serving on 8 (host) devices — the Janus architecture live.

Two demonstrations, both REAL multi-device execution on CPU host devices:

A. **Three-pool engine (pool mode)** — ``ServingEngine(executor="disagg")``
   serves a continuous-batching request stream with chunked prompt prefill
   on a 2-device prefill pool, attention stages on a 2-device attention
   pool, and expert stages on a 4-device MoE pool.  Admission is pipelined:
   each prompt streams chunk-by-chunk from the prefill pool into the
   attention pool's batch-sharded KV caches (slot lifecycle reserved →
   prefilling → active), so decode never stalls on a long prompt; every
   decode layer performs the explicit activation hand-off whose pattern
   (case-1 / case-2) is chosen per step by the adaptive two-phase model.
   Telemetry shows the regime, bytes moved, AEBS ``a_max``, TTFT and the
   (zero) decode-stall time.  Mid-run the autoscaling path is exercised for
   real: one ``reconfigure`` call rescales 2P2A4E → 1P3A4E — the prefill
   pool shrinks, the attention pool grows, the MoE pool (and its pinned
   expert weights) stays untouched, and the in-flight KV caches are
   preserved.

B. **SPMD deployment (full model)** — the production mapping (DESIGN.md §2):
   a (data=2, model=4) mesh where the model axis is the MoE pool; the
   scheduled expert-parallel decode step serves a token stream end-to-end.

Run:  PYTHONPATH=src python examples/serve_disaggregated.py
"""

import os

# eight virtual host devices stand in for the pools: pin the CPU backend
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.aebs import ReplicaLayout, aebs_assign
from repro.models import model as model_mod
from repro.serving.engine import ServingEngine
from repro.serving.request import WorkloadSpec, sample_requests
from repro.serving.trace import poisson_arrivals


def pool_mode_demo():
    print("=== A. three-pool engine: 2 prefill + 2 attention + 4 MoE devices ===")
    cfg = get_config("dsv2-lite-reduced")
    params = model_mod.init_params(cfg, 0)
    layout = ReplicaLayout.round_robin(cfg.num_experts, 4, 2)

    eng = ServingEngine(
        cfg, params, max_batch=6, cache_len=64, layout=layout,
        scheduler="aebs", capacity_tokens=64,
        executor="disagg", n_attn=2, n_prefill=2, prefill_chunk=8,
    )
    pools = eng.disagg.pools
    print(f"  pools: prefill={[d.id for d in pools.prefill_devices]} "
          f"attn={[d.id for d in pools.attn_devices]} "
          f"moe={[d.id for d in pools.moe_devices]} (admission={eng.admission})")
    spec = WorkloadSpec(mean_input=12, mean_output=12, vocab_size=cfg.vocab_size,
                        max_input=32, max_output=16, seed=0)
    reqs = sample_requests(spec, poisson_arrivals(100.0, 0.12, seed=0)[:12], with_prompts=True)

    t0 = time.perf_counter()
    m = eng.run(reqs[:6])
    print(f"  phase 1 (2P2A4E): served 6 requests in {time.perf_counter()-t0:.1f}s wall "
          f"({m.get('prefill_chunks', 0)} prompt chunks streamed, "
          f"decode stall {m['decode_stall_time']:.3f}s)")

    # one call, three independent pools: prefill shrinks, attention grows,
    # MoE (and its pinned expert weights) untouched
    relower = eng.reconfigure(n_attn=3, n_prefill=1)
    print(f"  reconfigure 2P2A4E → 1P3A4E: re-lowered pools {relower} "
          "(KV caches re-sharded in place, expert weights untouched)")

    t0 = time.perf_counter()
    m = eng.run(reqs[6:])
    print(f"  phase 2 (1P3A4E): served 6 more in {time.perf_counter()-t0:.1f}s wall")
    print(f"  telemetry: regimes={m['regime_counts']} "
          f"bytes/step={m['transfer_bytes_per_step']:.0f} "
          f"a_max mean={m['amax_mean']:.2f} max={m['amax_max']}")
    print(f"  completed={m['completed']} tokens={m['tokens']} "
          f"ttft_mean={m['ttft_mean']*1e3:.1f}ms "
          f"tpot_mean={m['tpot_mean']*1e3:.1f}ms truncated={m['truncated']}")


def spmd_mode_demo():
    print("=== B. SPMD deployment (full reduced model, 2×4 mesh) ===")
    cfg = get_config("qwen2-moe-a2.7b-reduced")
    # Auto axes: with Explicit ones (make_mesh's default) the EP layer's
    # data-sharded output no longer matches the decode scan's carry type
    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    params = model_mod.init_params(cfg, 0)
    layout = ReplicaLayout.round_robin(cfg.num_experts, 4, 2)
    moe_ctx = dict(
        dispatch="ep",
        ep_ctx=dict(mesh=mesh, dp_axes=("data",), model_axis="model", mode="scheduled"),
        scheduler=aebs_assign,
        layout_tables=layout.device_tables(),
        slot_to_expert=jnp.asarray(layout.slot_to_expert.reshape(-1)),
        num_instances=4,
    )
    B, S = 4, 32
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    with jax.set_mesh(mesh):
        _, caches = model_mod.prefill(params, tokens, cfg, cache_len=S + 16)
        step = jax.jit(
            lambda p, t, c, i: model_mod.decode_step(p, t, c, i, cfg, extra={"moe_ctx": moe_ctx})
        )
        t = tokens[:, -1:]
        t0 = time.perf_counter()
        toks = []
        for i in range(8):
            logits, caches = step(params, t, caches, jnp.int32(S + i))
            t = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            toks.append(int(t[0, 0]))
        jax.block_until_ready(t)
        wall = time.perf_counter() - t0
    print(f"  decoded 8 tokens/seq on {len(jax.devices())} devices in {wall*1e3:.0f} ms")
    print(f"  sample continuation (seq 0): {toks}")


if __name__ == "__main__":
    pool_mode_demo()
    spmd_mode_demo()
