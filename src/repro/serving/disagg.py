"""Two-pool disaggregated decode execution — Janus §3.1–§3.3 made runnable.

:class:`DisaggExecutor` drives one continuous-batching decode step across two
real device pools:

* the **attention pool** (``pools.attn_devices``) holds a full
  attention-stack replica per device and a contiguous *batch shard* of the
  in-flight KV caches; every layer's :func:`repro.models.transformer
  .attention_stage` runs there;
* the **MoE pool** (``pools.moe_devices``) holds only each device's expert
  replica-slot weights (plus the replicated router — EGate gates on the MoE
  side, §3.2); every layer's expert FFN runs there over *local slots only*,
  with the AEBS schedule recomputed redundantly per device
  (synchronisation-free, §3.4).

The per-layer hand-off is an explicit transfer whose pattern (case-1 direct
node-to-node vs case-2 pair + multicast) is chosen per step via
:func:`repro.core.comm.adaptive_two_phase` and executed as the grouped
``device_put`` schedule from :func:`repro.core.disagg.plan_exchange`.
Per-step regime, per-fabric bytes and message counts are returned as
telemetry and surfaced by ``ServingEngine.metrics()``.

Numerics: the executor composes the exact op sequence of the monolithic
``decode_step`` (stage split + item-level dispatch + attention-side
combine), so sequential pool mode produces **bit-identical logits** to the
monolithic engine.  Micro-batch ping-pong (``ping_pong=True``, m=2 —
MegaScale-style overlap of attention(i) with MoE(i+1)) routes each
micro-batch independently; it is bit-identical as well whenever expert
capacity is ample (per-micro-batch packing can only *reduce* capacity
drops).

``reconfigure`` actuates a §3.5 scaling decision mid-run: only the pool
whose count changed is re-lowered, and KV caches are re-sharded in place so
in-flight requests continue undisturbed.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.aebs import ReplicaLayout, aebs_assign
from repro.core.comm import CommConfig, HardwareSpec, adaptive_two_phase, hardware_for
from repro.core.disagg import DevicePools, DisaggConfig, plan_exchange
from repro.core.disagg import reconfigure as disagg_reconfigure
from repro.models import model as model_mod
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.models.ffn import ffn
from repro.serving.kv_cache import PagedKVCache, PrefixIndex, SpilledKV

_KV_KEYS = {"k": "kv_k", "v": "kv_v", "k_scale": "kv_k_scale", "v_scale": "kv_v_scale"}


@dataclasses.dataclass
class SpilledSlotKV:
    """A preempted slot's detached KV on a disagg executor: the shard-local
    :class:`SpilledKV` record, the shard that owns the pages (ids are
    pool-local, so restores are shard-affine), and the executor-level live
    length to put back into ``_slot_len`` on restore."""

    shard: int
    rec: SpilledKV
    tokens: int


@dataclasses.dataclass
class _Shard:
    """One attention-pool batch shard (a micro-batch slice of one device)."""

    dev_index: int  # index into pools.attn_devices
    mb: int  # micro-batch id (0 in sequential mode)
    lo: int  # global batch row range [lo, hi)
    hi: int

    @property
    def rows(self) -> int:
        return self.hi - self.lo


def _shard_bounds(max_batch: int, n: int) -> List[Tuple[int, int]]:
    sizes = [max_batch // n + (1 if i < max_batch % n else 0) for i in range(n)]
    bounds, lo = [], 0
    for s in sizes:
        bounds.append((lo, lo + s))
        lo += s
    return bounds


class DisaggExecutor:
    """Placement + per-layer cross-pool exchange for one decode deployment."""

    def __init__(
        self,
        cfg,
        params,
        pools: DevicePools,
        layout: ReplicaLayout,
        *,
        max_batch: int,
        cache_len: int,
        scheduler: Callable = aebs_assign,
        capacity: Optional[int] = None,
        ping_pong: bool = False,
        hw: Optional[HardwareSpec] = None,  # default: looked up by device kind
        devices: Optional[Sequence[jax.Device]] = None,
        kv_page_size: Optional[int] = None,
        kv_num_pages: Optional[int] = None,
        prefix_cache: bool = False,
        prefix_cache_pages: Optional[int] = None,
        prefix_chunk: int = 64,
    ):
        if not cfg.has_moe:
            raise ValueError("disagg executor requires an MoE architecture")
        period, n_periods = transformer.period_pattern(cfg)
        if cfg.encoder_layers or cfg.frontend or any(
            k not in ("dense", "moe") for k in period
        ):
            raise ValueError(
                f"disagg executor supports attention+FFN stacks only, got {period}"
            )
        if not moe_mod.scheduler_is_single_replica(scheduler):
            raise ValueError(
                "disagg executor requires a single-active-replica scheduler "
                "(AEBS/random) so replica slots carry exact expert semantics"
            )
        if len(pools.attn_devices) < 1:
            raise ValueError("attention pool must have ≥ 1 device")
        self.cfg = cfg
        self.params = params
        self.pools = pools
        self.scheduler = scheduler
        self.capacity = capacity
        self.ping_pong = ping_pong
        self.hw = hw if hw is not None else hardware_for(pools.attn_devices[0])
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.kv_page_size = kv_page_size
        self.kv_num_pages = kv_num_pages
        # per-shard page managers (local-row block tables); None = contiguous
        self._pagers: Optional[List[PagedKVCache]] = None
        # prefix cache: one radix index per attention shard (a slot can only
        # share pages with slots on its own shard — page ids are pool-local)
        self.prefix_cache = bool(prefix_cache) and kv_page_size is not None
        self.prefix_cache_pages = prefix_cache_pages
        self.prefix_chunk = max(1, int(prefix_chunk))
        self._indexes: Optional[List[PrefixIndex]] = None
        self._prefix_carry = {
            "hits": 0, "misses": 0, "saved_tokens": 0,
            "lookup_tokens": 0, "evicted_pages": 0,
        }
        # per-slot live KV length — executor-level so it survives re-sharding
        # (reconfigure / drop_attn_device rebuild block tables from it)
        self._slot_len = np.zeros(max_batch, np.int64)
        # fault-injection hook (repro.serving.faults): called before each
        # cross-pool exchange with (site, layer, micro_batch); may raise
        # PoolFault.  None (the default) keeps the fault-free path untouched.
        self.fault_hook = None
        combo_all = (
            list(pools.attn_devices)
            + list(pools.prefill_devices)
            + list(pools.moe_devices)
        )
        # degenerate single-host test pools alias physical devices; device
        # exclusion and exceeds-available validation are meaningless there
        self._aliased = len({id(d) for d in combo_all}) < len(combo_all)
        if devices is not None:
            self._all_devices = list(devices)
        else:
            # reconfigure must re-split the same universe the pools came
            # from: detect the standard three-way split of the global device
            # list; anything else is a custom pool set — stay inside it.
            universe = jax.devices()
            combo = (
                list(pools.attn_devices)
                + list(pools.prefill_devices)
                + list(pools.moe_devices)
            )
            n_a, n_e = len(pools.attn_devices), len(pools.moe_devices)
            n_p = len(pools.prefill_devices)
            std = (
                universe[:n_a]
                + universe[len(universe) - n_e - n_p : len(universe) - n_e]
                + universe[len(universe) - n_e :]
            )
            self._all_devices = None if combo == std else combo
        self.disagg_cfg = DisaggConfig(
            len(pools.attn_devices), len(pools.moe_devices), layout,
            n_prefill=len(pools.prefill_devices),
        )
        self.relower_log: List[Dict[str, bool]] = []

        # layer enumeration: (period_index, pos, kind, kv cache layer index)
        full_pos = [p for p, k in enumerate(period) if k in ("dense", "moe")]
        rank = {p: r for r, p in enumerate(full_pos)}
        self._layers = [
            (per, pos, kind, per * len(full_pos) + rank[pos])
            for per in range(n_periods)
            for pos, kind in enumerate(period)
        ]

        self._build_moe_side(layout)
        self._build_attn_side(len(pools.attn_devices), caches=None)
        self._build_attn_jits()
        self._build_moe_jits()

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def _layer_param(self, per: int, pos: int):
        return jax.tree.map(lambda a: a[per], self.params["blocks"][f"pos{pos}"])

    def _build_attn_side(self, n_attn: int, caches) -> None:
        """(Re-)place attention params and KV cache shards on ``n_attn``
        devices.  ``caches`` is the stacked engine-format cache dict to
        re-shard (zeros when None)."""
        cfg = self.cfg
        fresh = caches is None
        if fresh:
            # shapes only: fresh shards are zeroed on their own device, so the
            # whole batch's cache never stages on one device or on the host
            caches = jax.eval_shape(
                lambda: model_mod.init_decode_caches(cfg, self.max_batch, self.cache_len)
            )

        pools = self.pools
        bounds = _shard_bounds(self.max_batch, n_attn)
        if self.ping_pong and any(hi - lo < 2 for lo, hi in bounds):
            raise ValueError(
                f"ping_pong (m=2) needs ≥2 batch rows per attention device "
                f"(max_batch={self.max_batch}, n_attn={n_attn})"
            )
        self.shards: List[_Shard] = []
        for i, (lo, hi) in enumerate(bounds):
            if self.ping_pong:
                mid = lo + (hi - lo) // 2
                self.shards.append(_Shard(i, 0, lo, mid))
                self.shards.append(_Shard(i, 1, mid, hi))
            else:
                self.shards.append(_Shard(i, 0, lo, hi))
        self.n_micro = 1 + int(any(s.mb == 1 for s in self.shards))

        # attention-side parameters, replicated per pool device
        attn_layers = []
        shared_layers = []
        for per, pos, kind, _ in self._layers:
            lp = self._layer_param(per, pos)
            alp = {k: lp[k] for k in ("ln1", "attn", "ln2")}
            if kind == "dense":
                alp["ffn"] = lp["ffn"]
            attn_layers.append(alp)
            shared_layers.append(
                lp["moe"].get("shared") if kind == "moe" else None
            )
        tree = {
            "embed": self.params["embed"],
            "final_norm": self.params["final_norm"],
            "layers": attn_layers,
            "shared": shared_layers,
        }
        self._attn_params = [
            jax.device_put(tree, dev) for dev in pools.attn_devices
        ]

        # KV cache shards: per shard, per kv-layer, the engine cache rows.
        # Paged mode replaces each shard's [rows, S, ...] slabs with per-shard
        # page pools [P, ps, ...] + a local-row block table, re-paginated from
        # the dense input using the executor-level ``_slot_len`` — page ids
        # change across re-shards, the position→value mapping never does.
        self._kv: List[List[Dict[str, jax.Array]]] = []
        n_kv_layers = len({c for *_x, c in self._layers})
        if self.kv_page_size is not None:
            ps = self.kv_page_size
            np_caches = {
                name: caches[name] if fresh else np.asarray(caches[name])
                for name in _KV_KEYS.values()
                if name in caches
            }
            self._pagers = []
            for s in self.shards:
                dev = pools.attn_devices[s.dev_index]
                if self.kv_num_pages is None:
                    shard_pages = None  # full backing for the shard's rows
                else:
                    # split the operator's pool budget proportionally to rows
                    # (each shard keeps its own null page)
                    shard_pages = 1 + max(
                        1, round((self.kv_num_pages - 1) * s.rows / self.max_batch)
                    )
                pager = PagedKVCache(s.rows, self.cache_len, ps, shard_pages)
                for r in range(s.rows):
                    ln = int(self._slot_len[s.lo + r])
                    if ln > 0:
                        pager.ensure(r, ln - 1)
                bt = pager.table_device(dev)
                per_layer = []
                for l in range(n_kv_layers):
                    layer = {}
                    for short, name in _KV_KEYS.items():
                        if name not in np_caches:
                            continue
                        if fresh:
                            spec = np_caches[name]  # [L, B, S, ...]
                            layer[short] = jnp.zeros(
                                (pager.num_pages, ps, *spec.shape[3:]), spec.dtype,
                                device=dev,
                            )
                            continue
                        src = np_caches[name][l]  # [B, S, ...]
                        pool = np.zeros(
                            (pager.num_pages, ps, *src.shape[2:]), src.dtype
                        )
                        for r in range(s.rows):
                            ln = int(self._slot_len[s.lo + r])
                            if ln > 0:
                                pages, offs = pager.rows_of(r, 0, ln)
                                pool[pages, offs] = src[s.lo + r, :ln]
                        layer[short] = jax.device_put(jnp.asarray(pool), dev)
                    layer["bt"] = bt
                    per_layer.append(layer)
                self._pagers.append(pager)
                self._kv.append(per_layer)
        else:
            for s in self.shards:
                dev = pools.attn_devices[s.dev_index]
                per_layer = []
                for l in range(n_kv_layers):
                    per_layer.append(
                        {
                            short: (
                                jnp.zeros(
                                    (s.rows, *caches[name].shape[2:]),
                                    caches[name].dtype, device=dev,
                                )
                                if fresh
                                else jax.device_put(caches[name][l, s.lo : s.hi], dev)
                            )
                            for short, name in _KV_KEYS.items()
                            if name in caches
                        }
                    )
                self._kv.append(per_layer)

        # prefix indexes are shard-local: a re-shard re-assigns page ids, so
        # sharing dissolves and the indexes restart empty (correct — exported
        # rows were gathered through the shared pages before the rebuild).
        # Cumulative hit/miss telemetry carries over.
        old_indexes = getattr(self, "_indexes", None)
        if old_indexes:
            carry = self._prefix_carry
            for ix in old_indexes:
                carry["hits"] += ix.hits
                carry["misses"] += ix.misses
                carry["saved_tokens"] += ix.saved_tokens
                carry["lookup_tokens"] += ix.lookup_tokens
                carry["evicted_pages"] += ix.evicted_pages
        self._indexes = None
        if self.prefix_cache and self._pagers is not None:
            self._indexes = []
            for s, pager in zip(self.shards, self._pagers):
                budget = None
                if self.prefix_cache_pages is not None:
                    # split the operator's pin budget proportionally to rows
                    budget = max(
                        1, round(self.prefix_cache_pages * s.rows / self.max_batch)
                    )
                self._indexes.append(
                    PrefixIndex(self.prefix_chunk, pager, max_pages=budget)
                )

        # exchange schedule (regime chosen per step; both plans precomputed)
        self._plans = {r: plan_exchange(self.pools, r) for r in ("case1", "case2")}

    def _build_moe_side(self, layout: ReplicaLayout) -> None:
        cfg = self.cfg
        if layout.num_instances != len(self.pools.moe_devices):
            raise ValueError(
                f"layout has {layout.num_instances} instances but pool has "
                f"{len(self.pools.moe_devices)} MoE devices"
            )
        self.layout = layout
        self.n_moe = layout.num_instances
        self.C = layout.capacity
        self.S_total = layout.total_slots
        self.cap = self.capacity or moe_mod.default_capacity(
            self.max_batch, cfg.top_k, self.S_total, cfg.capacity_factor
        )
        tables = layout.device_tables()
        stx = np.asarray(layout.slot_to_expert)
        self._moe_params = []
        for g, dev in enumerate(self.pools.moe_devices):
            local = jnp.asarray(np.maximum(stx[g], 0))
            layers = []
            for per, pos, kind, _ in self._layers:
                if kind != "moe":
                    layers.append(None)
                    continue
                mp = self._layer_param(per, pos)["moe"]
                # each layer's slot copy moves to the instance's device as
                # soon as it is gathered: only one layer's gather is ever
                # transient on the device holding the logical weights
                layers.append(
                    jax.device_put(
                        {
                            "router": mp["router"],
                            "w": {
                                k: jnp.take(mp[k], local, axis=0)
                                for k in ("w_gate", "w_up", "w_down")
                            },
                        },
                        dev,
                    )
                )
            self._moe_params.append(
                {
                    "layers": layers,
                    "tables": jax.device_put(tables, dev),
                    "lo": jax.device_put(jnp.int32(g * self.C), dev),
                }
            )

    def _build_attn_jits(self) -> None:
        """Attention-pool stage functions.  Closures depend only on ``cfg``;
        a pool resize changes shard shapes, which jax re-traces under the
        same jit (new entries in the executable cache) — the MoE-pool
        executables are untouched."""
        cfg = self.cfg

        def embed_fn(emb, tokens):
            x = emb[tokens]
            return x * jnp.asarray(cfg.d_model**0.5, x.dtype)

        def attn_fn(lp, x, kv, positions):
            return transformer.attention_stage(lp, x, kv, positions, cfg)

        def dense_fn(lp, x, h2):
            return transformer.moe_stage(lp, x, h2, cfg)

        def head_fn(p, x):
            return transformer.lm_head(
                {"final_norm": p["final_norm"], "embed": p["embed"]}, x[:, 0, :], cfg
            )

        def attn_verify_fn(lp, x, kv, positions, widths):
            return transformer.attention_stage_verify(
                lp, x, kv, positions, cfg, widths=widths
            )

        def head_verify_fn(p, x):  # [rows, c, d] -> [rows, c, vocab]
            # per-position lm_head calls: each column runs with the exact
            # one-token decode shapes, keeping verify logits bitwise equal
            # to sequential decode (a [rows*c, d] matmul is not)
            pp = {"final_norm": p["final_norm"], "embed": p["embed"]}
            cols = [
                transformer.lm_head(pp, x[:, j, :], cfg) for j in range(x.shape[1])
            ]
            return jnp.stack(cols, axis=1)

        self._embed_jit = jax.jit(embed_fn)
        self._attn_jit = jax.jit(attn_fn)
        self._dense_jit = jax.jit(dense_fn)
        self._head_jit = jax.jit(head_fn)
        self._attn_verify_jit = jax.jit(attn_verify_fn)
        self._head_verify_jit = jax.jit(head_verify_fn)

    def _build_moe_jits(self) -> None:
        """MoE-pool stage functions + the attention-side combine.  Closures
        bake in the layout constants (n_e, C, cap), so these — and only
        these — are re-lowered when the MoE pool or layout changes."""
        cfg = self.cfg
        scheduler = self.scheduler
        n_moe, C, cap = self.n_moe, self.C, self.cap

        def moe_fn(mp, tables, lo, h):
            h2d = h.reshape(-1, h.shape[-1])
            gates, eids, _ = moe_mod.route(mp["router"], h2d, cfg.top_k)
            slot_ids, load, _ = scheduler(eids, tables, n_moe)
            local = (slot_ids >= lo) & (slot_ids < lo + C)
            buckets = jnp.where(local, slot_ids - lo, -1)
            y_items, keep = moe_mod.grouped_dispatch_items(
                h2d, buckets, C, cap, mp["w"], backend="einsum"
            )
            return y_items, keep, local.reshape(-1), gates, load

        def combine_fn(x, h2, shared_p, parts, gates):
            b = x.shape[0]
            d = x.shape[-1]
            dt = h2.dtype
            I = b * cfg.top_k
            y_items = jnp.zeros((I, d), dt)
            keep = jnp.zeros((I,), bool)
            for yg, kg, lg in parts:
                y_items = jnp.where(lg[:, None], yg, y_items)
                keep = jnp.where(lg, kg, keep)
            gflat = (gates.reshape(-1) * keep).astype(dt)
            y2d = (y_items * gflat[:, None]).reshape(b, cfg.top_k, -1).sum(axis=1)
            if shared_p is not None:
                y2d = y2d + ffn(shared_p, h2.reshape(b, d), "swiglu")
            return x + y2d.reshape(b, 1, d)

        def moe_verify_fn(mp, tables, lo, h):
            # per-position unroll of moe_fn: each candidate column routes and
            # dispatches exactly like one sequential decode step (same token
            # count, same baked capacity, same drop order), so expert outputs
            # are bitwise what the equivalent one-token steps would produce.
            # The ``c`` columns still arrived in ONE exchange — only compute
            # is per-position, the transfer amortisation is untouched.
            # Outputs are re-packed token-major (row, c) to match the h
            # flattening the combine-side slicing assumes.
            rows, c, d = h.shape
            k = cfg.top_k
            outs = [moe_fn(mp, tables, lo, h[:, j : j + 1]) for j in range(c)]
            y_items = jnp.stack(
                [o[0].reshape(rows, k, d) for o in outs], axis=1
            ).reshape(rows * c * k, d)
            keep = jnp.stack(
                [o[1].reshape(rows, k) for o in outs], axis=1
            ).reshape(rows * c * k)
            local = jnp.stack(
                [o[2].reshape(rows, k) for o in outs], axis=1
            ).reshape(rows * c * k)
            gates = jnp.stack([o[3] for o in outs], axis=1).reshape(rows * c, k)
            load = jnp.stack([o[4] for o in outs])
            return y_items, keep, local, gates, load

        def combine_verify_fn(x, h2, shared_p, parts, gates):
            # per-position combine_fn calls on the token-major packed parts —
            # same one-token shapes as the sequential decode combine
            b, s, d = x.shape
            k = cfg.top_k
            cols = []
            for j in range(s):
                parts_j = [
                    (
                        yg.reshape(b, s, k, d)[:, j].reshape(b * k, d),
                        kg.reshape(b, s, k)[:, j].reshape(b * k),
                        lg.reshape(b, s, k)[:, j].reshape(b * k),
                    )
                    for yg, kg, lg in parts
                ]
                gates_j = gates.reshape(b, s, k)[:, j]
                cols.append(
                    combine_fn(
                        x[:, j : j + 1], h2[:, j : j + 1], shared_p, parts_j, gates_j
                    )
                )
            return jnp.concatenate(cols, axis=1)

        self._moe_jit = jax.jit(moe_fn)
        self._combine_jit = jax.jit(combine_fn)
        self._moe_verify_jit = jax.jit(moe_verify_fn)
        self._combine_verify_jit = jax.jit(combine_verify_fn)

    # ------------------------------------------------------------------
    # cache interop (engine format: stacked [L, b, S, ...])
    # ------------------------------------------------------------------
    def scatter_prefill(self, one_caches: Dict[str, jax.Array], slot: int) -> None:
        """Write a single-request prefill cache (batch dim 1) into ``slot`` —
        the whole-prompt special case of the streamed chunk hand-off."""
        length = next(
            one_caches[name].shape[2]
            for short, name in _KV_KEYS.items()
            if short in self._kv[0][0]
        )
        self.scatter_prefill_chunk(one_caches, slot, 0, length)

    def scatter_prefill_chunk(
        self, one_caches: Dict[str, jax.Array], slot: int, start: int, length: int
    ) -> None:
        """Stream one prefill chunk's KV slab into ``slot``: only the rows
        holding prompt positions ``[start, start+length)`` cross the wire
        (prefill pool → owning attention shard), never the whole prompt
        cache.  Row mapping via :func:`repro.serving.kv_cache.chunk_rows`
        (shared with the mono engine's scatter)."""
        from repro.serving.kv_cache import chunk_rows

        shard = next(s for s in self.shards if s.lo <= slot < s.hi)
        si = self.shards.index(shard)
        dev = self.pools.attn_devices[shard.dev_index]
        local = slot - shard.lo
        self._slot_len[slot] = max(self._slot_len[slot], start + length)
        if self._pagers is not None:
            pager = self._pagers[si]
            pager.ensure(local, start + length - 1)
            pages, offs = pager.rows_of(local, start, length)
            positions = start + np.arange(length)
            for l, layer_kv in enumerate(self._kv[si]):
                for short, name in _KV_KEYS.items():
                    if short in layer_kv:
                        rows = jax.device_put(one_caches[name][l, 0, positions], dev)
                        layer_kv[short] = (
                            layer_kv[short].at[pages, offs].set(rows.astype(layer_kv[short].dtype))
                        )
            return
        for l, layer_kv in enumerate(self._kv[si]):
            for short, name in _KV_KEYS.items():
                if short in layer_kv:
                    idx = chunk_rows(one_caches[name].shape[2], start, length)
                    rows = jax.device_put(one_caches[name][l, 0, idx], dev)
                    layer_kv[short] = (
                        layer_kv[short].at[local, idx].set(rows.astype(layer_kv[short].dtype))
                    )

    def load_caches(
        self, caches: Dict[str, jax.Array], lengths: Optional[np.ndarray] = None
    ) -> None:
        """Adopt an engine-format stacked cache dict (re-shards onto the pool).
        ``lengths`` (per-slot live rows) drives paged re-pagination; defaults
        to treating every slot as fully live."""
        if lengths is not None:
            self._slot_len = np.asarray(lengths, np.int64).copy()
        elif self.kv_page_size is not None:
            self._slot_len = np.full(self.max_batch, self.cache_len, np.int64)
        self._build_attn_side(len(self.pools.attn_devices), caches=caches)

    def export_caches(self) -> Dict[str, jax.Array]:
        """Reassemble the engine-format stacked cache dict (global row order).
        Paged shards gather their pages back into dense rows (unbacked rows
        come back as zeros), so the export format is layout-independent."""
        order = sorted(range(len(self.shards)), key=lambda i: self.shards[i].lo)
        out: Dict[str, jax.Array] = {}
        n_layers = len(self._kv[0])
        host = jax.devices()[0]
        for short, name in _KV_KEYS.items():
            if short not in self._kv[0][0]:
                continue
            per_layer = []
            for l in range(n_layers):
                rows = []
                for i in order:
                    arr = jax.device_put(self._kv[i][l][short], host)
                    if self._pagers is not None:
                        pager = self._pagers[i]
                        pool = np.asarray(arr)  # [P, ps, ...]
                        dense = np.zeros(
                            (pager.max_batch, pager.cache_len, *pool.shape[2:]),
                            pool.dtype,
                        )
                        for r in range(pager.max_batch):
                            nb = pager.slot_blocks(r)
                            if nb:
                                pages = pager.tables[r, :nb]
                                dense[r, : nb * pager.page_size] = pool[pages].reshape(
                                    nb * pager.page_size, *pool.shape[2:]
                                )
                        arr = jnp.asarray(dense)
                    rows.append(arr)
                per_layer.append(jnp.concatenate(rows, axis=0))
            out[name] = jnp.stack(per_layer)
        return out

    # ------------------------------------------------------------------
    # paged slot lifecycle
    # ------------------------------------------------------------------
    def _shard_of(self, slot: int) -> int:
        return next(si for si, s in enumerate(self.shards) if s.lo <= slot < s.hi)

    def ensure_slot_pages(self, slot: int, pos: int) -> None:
        """Back ``slot``'s write position with a page (alloc on append)."""
        self._slot_len[slot] = max(self._slot_len[slot], pos + 1)
        if self._pagers is None:
            return
        si = self._shard_of(slot)
        self._pagers[si].ensure(slot - self.shards[si].lo, pos)

    def truncate_slot(self, slot: int, tokens: int) -> None:
        """Clamp ``slot``'s live length down to ``tokens`` rows (speculative
        verify backed and wrote candidate rows past the accepted prefix —
        pure bookkeeping, the decode mask never reads past the position)."""
        self._slot_len[slot] = min(int(self._slot_len[slot]), int(tokens))
        if self._pagers is None:
            return
        si = self._shard_of(slot)
        self._pagers[si].truncate(slot - self.shards[si].lo, tokens)

    def release_slot(self, slot: int) -> None:
        """Free a released slot's pages and forget its live length."""
        self._slot_len[slot] = 0
        if self._pagers is None:
            return
        si = self._shard_of(slot)
        self._pagers[si].release(slot - self.shards[si].lo)

    def shard_of(self, slot: int) -> int:
        """Which attention shard owns ``slot`` (spill/restore must re-attach
        on the same shard — page ids are pool-local)."""
        return self._shard_of(slot)

    def spill_slot(self, slot: int) -> Tuple["SpilledSlotKV", int]:
        """Detach ``slot``'s KV pages for preemption (no copy): the shard
        pager's block-table row moves into a :class:`SpilledSlotKV` record
        and the executor forgets the slot's live length.  Returns the record
        and the shard index a restore must target.

        The record is only valid while this shard's page pool lives: any
        attention re-shard (device loss, reconfigure, degrade-to-mono)
        rebuilds the pools from slot-owned pages and dissolves detached
        ones — the engine then falls back to restore-by-replay."""
        if self._pagers is None:
            raise RuntimeError("spill requires paged KV (kv_page_size)")
        si = self._shard_of(slot)
        rec = self._pagers[si].spill(slot - self.shards[si].lo)
        payload = SpilledSlotKV(shard=si, rec=rec, tokens=int(self._slot_len[slot]))
        self._slot_len[slot] = 0
        return payload, si

    def restore_slot(self, slot: int, payload: "SpilledSlotKV") -> None:
        """Re-attach a spilled record to fresh ``slot`` on its home shard."""
        si = self._shard_of(slot)
        if si != payload.shard:
            raise RuntimeError(
                f"slot {slot} lives on shard {si}, spilled KV belongs to "
                f"shard {payload.shard}"
            )
        self._pagers[si].restore(slot - self.shards[si].lo, payload.rec)
        self._slot_len[slot] = payload.tokens

    def drop_spilled(self, payload: "SpilledSlotKV") -> None:
        """Abandon a spilled record: return its page references to the pool."""
        self._pagers[payload.shard].drop_spilled(payload.rec)

    # ------------------------------------------------------------------
    # prefix cache (shard-local radix reuse)
    # ------------------------------------------------------------------
    def splice_prefix(self, slot: int, tokens: np.ndarray, limit: int):
        """Serve the longest cached prefix of ``tokens`` from ``slot``'s own
        shard: splice the shared pages into the local block table (per-layer
        copy-on-write for a trailing partial page) and gather the matched KV
        rows into worker-seed format (``kv name → [L, match, ...]``).
        Returns ``(match, seed_caches)`` — ``(0, None)`` on a miss."""
        if self._indexes is None:
            return 0, None
        si = self._shard_of(slot)
        local = slot - self.shards[si].lo
        match, pages = self._indexes[si].lookup(tokens, limit)
        if not match:
            return 0, None
        pager = self._pagers[si]
        cow = pager.splice(local, pages, match)
        if cow is not None:
            src, dst, rows = cow
            for layer_kv in self._kv[si]:
                for short in _KV_KEYS:
                    if short in layer_kv:
                        layer_kv[short] = layer_kv[short].at[dst, :rows].set(
                            layer_kv[short][src, :rows]
                        )
        # the spliced rows are live KV: a re-shard/fault rebuild must carry
        # them (export gathers through the shared pages, re-pagination gives
        # the slot exclusive copies — streams stay bit-identical)
        self._slot_len[slot] = max(int(self._slot_len[slot]), match)
        pgs, offs = pager.rows_of(local, 0, match)
        seed: Dict[str, np.ndarray] = {}
        for short, name in _KV_KEYS.items():
            if short not in self._kv[si][0]:
                continue
            seed[name] = np.stack(
                [np.asarray(layer_kv[short])[pgs, offs] for layer_kv in self._kv[si]]
            )
        return match, seed

    def publish_prefix(self, slot: int, tokens: np.ndarray, upto: int) -> None:
        """Index the chunk-aligned prefix KV ``slot`` just prefilled (pins
        the backing pages on its shard's index)."""
        if self._indexes is None:
            return
        si = self._shard_of(slot)
        self._indexes[si].publish(tokens, upto, slot - self.shards[si].lo)

    def prefix_stats(self) -> Optional[Dict[str, float]]:
        """Aggregated prefix-cache telemetry across the attention shards
        (plus counters carried over from pre-re-shard indexes)."""
        if self._indexes is None:
            return None
        c = dict(self._prefix_carry)
        shared = nodes = 0
        for ix in self._indexes:
            c["hits"] += ix.hits
            c["misses"] += ix.misses
            c["saved_tokens"] += ix.saved_tokens
            c["lookup_tokens"] += ix.lookup_tokens
            c["evicted_pages"] += ix.evicted_pages
            shared += ix.held_pages
            nodes += len(ix._nodes)
        total = c["hits"] + c["misses"]
        return {
            "hits": c["hits"],
            "misses": c["misses"],
            "hit_rate": c["hits"] / total if total else 0.0,
            "saved_tokens": c["saved_tokens"],
            "saved_frac": (
                c["saved_tokens"] / c["lookup_tokens"] if c["lookup_tokens"] else 0.0
            ),
            "shared_pages": shared,
            "evicted_pages": c["evicted_pages"],
            "nodes": nodes,
        }

    def _sync_tables(self) -> None:
        """Push dirty block tables into every layer's kv dict before decode."""
        if self._pagers is None:
            return
        for si, pager in enumerate(self._pagers):
            if pager.dirty:
                dev = self.pools.attn_devices[self.shards[si].dev_index]
                bt = pager.table_device(dev)
                for layer_kv in self._kv[si]:
                    layer_kv["bt"] = bt

    def slot_lengths(self) -> np.ndarray:
        """Per-slot live KV lengths (rows written), global row order."""
        return self._slot_len.copy()

    def page_stats(self) -> Optional[Dict[str, float]]:
        """Aggregated page telemetry across the attention shards."""
        if self._pagers is None:
            return None
        num_pages = sum(p.num_pages for p in self._pagers)
        in_use = sum(p.allocator.in_use for p in self._pagers)
        peak = sum(p.allocator.peak_in_use for p in self._pagers)
        free = sum(p.allocator.num_free for p in self._pagers)
        used_rows = sum(int(p.hiwater.sum()) for p in self._pagers)
        alloc_rows = in_use * self.kv_page_size
        allocatable = sum(p.num_pages - 1 for p in self._pagers)
        return {
            "page_size": self.kv_page_size,
            "num_pages": num_pages,
            "pages_in_use": in_use,
            "pages_peak": peak,
            "pages_free": free,
            "occupancy": in_use / max(1, allocatable),
            "fragmentation": 1.0 - used_rows / alloc_rows if alloc_rows else 0.0,
        }

    # ------------------------------------------------------------------
    # reconfigure (§3.5): re-lower only the affected pool
    # ------------------------------------------------------------------
    def reconfigure(
        self,
        n_attn: Optional[int] = None,
        n_moe: Optional[int] = None,
        layout: Optional[ReplicaLayout] = None,
        n_prefill: Optional[int] = None,
    ) -> Dict[str, bool]:
        cur_a = len(self.pools.attn_devices)
        cur_e = len(self.pools.moe_devices)
        cur_p = len(self.pools.prefill_devices)
        n_attn = cur_a if n_attn is None else int(n_attn)
        n_moe = cur_e if n_moe is None else int(n_moe)
        n_prefill = cur_p if n_prefill is None else int(n_prefill)
        # validate before any state mutates: a bad size must surface as a
        # clear ValueError naming the pool, not an opaque downstream JAX error
        if n_attn < 1:
            raise ValueError(
                f"attention pool size must be ≥ 1, got n_attn={n_attn} "
                "(the engine cannot decode without an attention pool)"
            )
        if n_moe < 1:
            raise ValueError(
                f"MoE pool size must be ≥ 1, got n_moe={n_moe} "
                "(expert layers need at least one MoE device)"
            )
        if n_prefill < 0:
            raise ValueError(f"prefill pool size must be ≥ 0, got n_prefill={n_prefill}")
        avail = len(self._all_devices if self._all_devices is not None else jax.devices())
        if not self._aliased and n_attn + n_moe + n_prefill > avail:
            raise ValueError(
                f"pool sizes {n_attn} (attn) + {n_moe} (moe) + {n_prefill} "
                f"(prefill) = {n_attn + n_moe + n_prefill} exceed the {avail} "
                "available devices"
            )
        relower = {
            "attn": n_attn != cur_a,
            "moe": n_moe != cur_e or layout is not None,
            # a MoE resize re-anchors the (tail-anchored) prefill pool too
            "prefill": n_prefill != cur_p or (n_prefill > 0 and n_moe != cur_e),
        }
        if not (relower["attn"] or relower["moe"] or relower["prefill"]):
            self.relower_log.append(relower)
            return relower

        caches = self.export_caches() if relower["attn"] else None
        devs = self._all_devices
        allow_reuse = len(devs or jax.devices()) < n_attn + n_moe + n_prefill
        self.pools = DevicePools.split(
            n_attn, n_moe, devs, node_size=self.pools.node_size,
            allow_reuse=allow_reuse, n_prefill=n_prefill,
        )
        new_layout = layout or (
            self.layout
            if n_moe == cur_e
            else ReplicaLayout.round_robin(self.cfg.num_experts, n_moe, self.C)
        )
        if relower["moe"]:
            self._build_moe_side(new_layout)
            self._build_moe_jits()  # layout constants changed → re-lower MoE stages
        if relower["attn"]:
            # in-flight KV caches are preserved: re-shard the exported rows;
            # attention jits re-trace for the new shard shapes on first use
            self._build_attn_side(n_attn, caches=caches)
        else:
            # MoE-only change still needs fresh exchange plans (pool changed)
            self._plans = {r: plan_exchange(self.pools, r) for r in ("case1", "case2")}
        self.disagg_cfg = disagg_reconfigure(
            self.disagg_cfg, n_attn, n_moe, new_layout, n_prefill=n_prefill
        )
        self.relower_log.append(relower)
        return relower

    # ------------------------------------------------------------------
    # fault recovery: device loss
    # ------------------------------------------------------------------
    def exclude_device(self, pool: str, index: int) -> None:
        """Remove a dead device from the executor's universe so the next
        ``reconfigure`` re-splits onto survivors only.  With aliased
        (device-reusing) single-host test pools the exclusion is skipped —
        the loss is logical and recovery proceeds on the shared device."""
        devs = {
            "attn": self.pools.attn_devices,
            "moe": self.pools.moe_devices,
            "prefill": self.pools.prefill_devices,
        }[pool][index]
        universe = list(
            self._all_devices if self._all_devices is not None else jax.devices()
        )
        hits = [i for i, d in enumerate(universe) if d is devs]
        if self._aliased or len(hits) != 1:
            return
        universe.pop(hits[0])
        self._all_devices = universe

    def drop_attn_device(self, dead: int) -> List[int]:
        """Attention device ``dead`` died: destroy its batch-shard KV rows
        (a real failure loses that memory — recovery must *rebuild*, not
        read), shrink the pool to the survivors, and return the lost global
        batch rows so the engine can re-prefill their requests.  Needs ≥ 2
        attention devices — with one, there is nothing to shrink to and the
        engine degrades to the mono path instead."""
        n_attn = len(self.pools.attn_devices)
        if not 0 <= dead < n_attn:
            raise ValueError(f"no attention device {dead} (pool has {n_attn})")
        if n_attn < 2:
            raise ValueError("cannot drop the last attention device — degrade instead")
        lost: List[int] = []
        for si, s in enumerate(self.shards):
            if s.dev_index != dead:
                continue
            lost.extend(range(s.lo, s.hi))
            self._kv[si] = [
                {k: jnp.zeros_like(v) for k, v in layer.items()}
                for layer in self._kv[si]
            ]
            if self._pagers is not None:
                for r in range(s.rows):
                    self._pagers[si].release(r)
        if lost:
            # the dead shard's pages (and their block tables) died with it —
            # survivors re-paginate from zero length and replay rebuilds them
            self._slot_len[np.asarray(lost)] = 0
        self.exclude_device("attn", dead)
        self.reconfigure(n_attn=n_attn - 1)
        return sorted(lost)

    # ------------------------------------------------------------------
    # the exchange: realised two-phase transfer
    # ------------------------------------------------------------------
    def _dev_of(self, addr: Tuple[str, int]) -> jax.Device:
        pool, idx = addr
        return (self.pools.attn_devices if pool == "attn" else self.pools.moe_devices)[idx]

    def _run_exchange(self, h2s: Dict[int, jax.Array], regime: str, tel: Dict) -> List[jax.Array]:
        """Land the concatenation of all shards' ``h2`` on every MoE device
        following the per-regime ``device_put`` schedule.  ``h2s`` maps
        attention-device index → this micro-batch's activation slice."""
        chunks, steps = self._plans[regime]
        have: Dict[Tuple[int, Tuple[str, int]], jax.Array] = {}
        node_payload: Dict[Tuple[int, ...], jax.Array] = {}
        for cid, ch in enumerate(chunks):
            leader = ("attn", ch.members[0])
            if ch.members not in node_payload:
                parts = [jax.device_put(h2s[i], self._dev_of(leader)) for i in ch.members]
                node_payload[ch.members] = (
                    parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
                )
            payload = node_payload[ch.members]
            if ch.n_subs > 1:  # case-2 pair split: ≈ total/pairs rows per chunk
                payload = jnp.array_split(payload, ch.n_subs, axis=0)[ch.sub]
            have[(cid, leader)] = payload
        for st in steps:
            if st.phase == 1:
                tel["bytes_fast"] += h2s[st.src[1]].nbytes
                tel["msgs_fast"] += 1
                continue
            arr = have[(st.chunk, st.src)]
            have[(st.chunk, st.dst)] = jax.device_put(arr, self._dev_of(st.dst))
            tel[f"bytes_{st.fabric}"] += arr.nbytes
            tel[f"msgs_{st.fabric}"] += 1
        outs = []
        for g in range(len(self.pools.moe_devices)):
            got = [have[(cid, ("moe", g))] for cid in range(len(chunks))]
            outs.append(got[0] if len(got) == 1 else jnp.concatenate(got, axis=0))
        return outs

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def decode_step(self, tokens, positions) -> Tuple[jax.Array, Dict]:
        """One batched decode step.  Returns (logits [b, vocab], telemetry).
        ``tokens`` [b, 1] may be a host array: each shard's rows then go
        straight to that shard's attention device, not through device 0."""
        return self._decode_impl(tokens, positions, None)

    def decode_step_verify(self, tokens, positions, widths) -> Tuple[jax.Array, Dict]:
        """One batched speculative-verify step: ``tokens`` is [b, c] (last
        accepted token + drafts), ``widths`` the per-slot valid row counts.
        Returns (logits [b, c, vocab], telemetry).  Each per-layer exchange
        ships c rows per slot instead of one, so the transfer-bytes telemetry
        directly shows the amortisation speculation buys."""
        return self._decode_impl(tokens, positions, widths)

    def _decode_impl(self, tokens, positions, widths) -> Tuple[jax.Array, Dict]:
        """The step's stages are profiler spans as the host dispatches them:
        ``janus.split.{attn,exchange,moe,combine}`` per layer (``layer``) and
        micro-batch (``mb``), then ``janus.split.head``.  Dispatch is
        asynchronous, so a span ends when its work is queued, not done; the
        device trace shows when it ran."""
        verify = widths is not None
        self._sync_tables()
        cfg = self.cfg
        pools = self.pools
        dtype_bytes = jnp.dtype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32).itemsize
        c = CommConfig(
            n_attn=len(pools.attn_devices),
            n_moe=self.n_moe,
            bytes_per_token=cfg.d_model * dtype_bytes,
            batch=self.max_batch,
            hw=dataclasses.replace(self.hw, devices_per_node=max(1, pools.node_size)),
        )
        _, regime = adaptive_two_phase(c)
        tel: Dict = {
            "regime": regime,
            "bytes_slow": 0,
            "bytes_fast": 0,
            "msgs_slow": 0,
            "msgs_fast": 0,
        }

        # shard inputs + embed (attention pool)
        xs: List[jax.Array] = []
        poss: List[jax.Array] = []
        wids: List[Optional[jax.Array]] = []
        for si, s in enumerate(self.shards):
            dev = pools.attn_devices[s.dev_index]
            tok = jax.device_put(tokens[s.lo : s.hi], dev)
            pos = jax.device_put(jnp.asarray(positions)[s.lo : s.hi], dev)
            poss.append(pos)
            wids.append(
                jax.device_put(jnp.asarray(widths)[s.lo : s.hi], dev)
                if verify
                else None
            )
            xs.append(self._embed_jit(self._attn_params[s.dev_index]["embed"], tok))

        mbs = [
            [si for si, s in enumerate(self.shards) if s.mb == m]
            for m in range(self.n_micro)
        ]
        # per-micro-batch item offsets (token order = shard order within the mb)
        offs = []
        for group in mbs:
            o, acc = {}, 0
            for si in group:
                o[si] = acc
                acc += self.shards[si].rows
            offs.append((o, acc))

        amax_parts: List[jax.Array] = []
        for li, (per, pos_idx, kind, cidx) in enumerate(self._layers):
            h2s_all: List[Optional[jax.Array]] = [None] * len(self.shards)

            def attn_mb(m, group, li=li, cidx=cidx):
                with TraceAnnotation("janus.split.attn", layer=li, mb=m):
                    for si in group:
                        s = self.shards[si]
                        lp = self._attn_params[s.dev_index]["layers"][li]
                        if verify:
                            x, h2, new_kv = self._attn_verify_jit(
                                lp, xs[si], self._kv[si][cidx], poss[si], wids[si]
                            )
                        else:
                            x, h2, new_kv = self._attn_jit(
                                lp, xs[si], self._kv[si][cidx], poss[si]
                            )
                        xs[si], h2s_all[si] = x, h2
                        self._kv[si][cidx] = new_kv

            if kind == "dense":
                for m, group in enumerate(mbs):
                    attn_mb(m, group)
                    for si in group:
                        lp = self._attn_params[self.shards[si].dev_index]["layers"][li]
                        xs[si] = self._dense_jit(lp, xs[si], h2s_all[si])
                continue

            # MoE layer: per micro-batch attention → exchange → expert → combine,
            # dispatched in ping-pong order: micro-batch m's expert stage is in
            # flight (MoE pool) while m+1's attention runs (attention pool), and
            # m's combine (attention pool) overlaps m+1's expert stage (§6 /
            # MegaScale micro-batch pipelining).
            pending: List[Tuple[int, List[int], List]] = []
            moe_jit = self._moe_verify_jit if verify else self._moe_jit
            for m, group in enumerate(mbs):
                attn_mb(m, group)
                with TraceAnnotation("janus.split.exchange", layer=li, mb=m):
                    h2s = {self.shards[si].dev_index: h2s_all[si] for si in group}
                    if self.fault_hook is not None:
                        self.fault_hook("exchange", li, m)
                    h_on_moe = self._run_exchange(h2s, regime, tel)
                with TraceAnnotation("janus.split.moe", layer=li, mb=m):
                    res = [
                        moe_jit(
                            self._moe_params[g]["layers"][li],
                            self._moe_params[g]["tables"],
                            self._moe_params[g]["lo"],
                            h_on_moe[g],
                        )
                        for g in range(self.n_moe)
                    ]
                if pending:
                    self._combine_mb(
                        *pending.pop(0), xs, h2s_all, offs, li, tel, amax_parts, verify
                    )
                pending.append((m, group, res))
            while pending:
                self._combine_mb(
                    *pending.pop(0), xs, h2s_all, offs, li, tel, amax_parts, verify
                )

        with TraceAnnotation("janus.split.head"):
            head_jit = self._head_verify_jit if verify else self._head_jit
            logit_shards = {}
            for si, s in enumerate(self.shards):
                p = self._attn_params[s.dev_index]
                logit_shards[s.lo] = head_jit(
                    {"final_norm": p["final_norm"], "embed": p["embed"]}, xs[si]
                )
            logits = jnp.concatenate(
                [
                    jax.device_put(logit_shards[lo], pools.attn_devices[0])
                    for lo in sorted(logit_shards)
                ],
                axis=0,
            )
        tel["a_max"] = int(np.max([np.asarray(a) for a in amax_parts])) if amax_parts else 0
        tel["bytes_total"] = tel["bytes_slow"] + tel["bytes_fast"]
        return logits, tel

    def _combine_mb(
        self, m, group, res, xs, h2s_all, offs, li, tel, amax_parts, verify=False
    ) -> None:
        """Ship expert partials back to the owning attention shards and run
        the gate-combine there (mono-identical op order).  In verify mode a
        shard's rows carry ``c`` candidate tokens each, so item/gate slices
        scale by the per-row token width."""
        with TraceAnnotation("janus.split.combine", layer=li, mb=m):
            k = self.cfg.top_k
            # per-row token width: 1 in decode, c (candidate rows) in verify
            w = xs[group[0]].shape[1] if verify else 1
            off, _total = offs[m]
            amax_parts.append(jnp.max(res[0][4]))  # load from instance 0 (redundant copies agree)
            for si in group:
                s = self.shards[si]
                dev = self.pools.attn_devices[s.dev_index]
                r0, r1 = off[si] * w, (off[si] + s.rows) * w
                parts = []
                for y_items, keep, local, _gates, _load in res:
                    part = (
                        jax.device_put(y_items[r0 * k : r1 * k], dev),
                        jax.device_put(keep[r0 * k : r1 * k], dev),
                        jax.device_put(local[r0 * k : r1 * k], dev),
                    )
                    tel["bytes_slow"] += sum(a.nbytes for a in part)
                    tel["msgs_slow"] += 1
                    parts.append(part)
                gates = jax.device_put(res[0][3][r0:r1], dev)
                tel["bytes_slow"] += gates.nbytes
                tel["msgs_slow"] += 1
                shared = self._attn_params[s.dev_index]["shared"][li]
                combine = self._combine_verify_jit if verify else self._combine_jit
                xs[si] = combine(xs[si], h2s_all[si], shared, parts, gates)
