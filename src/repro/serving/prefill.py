"""Prefill-pool worker: chunked prompt prefill with streamed KV hand-off.

The third Janus sub-cluster.  :class:`PrefillWorker` owns the prefill
devices (``DevicePools.prefill_devices``) — each holds a full model replica —
and drives an admission pipeline that overlaps prompt processing with the
decode loop instead of stalling it:

* the engine *reserves* a batch slot for an arrived request and submits it
  here; the request queues until a prefill device is free;
* the prompt is processed in fixed-size token chunks
  (:func:`repro.models.transformer.prefill_chunk` — bit-equivalent to
  whole-prompt prefill under ample expert capacity); architectures without
  chunked-prefill support (recurrent / encoder-decoder stacks) fall back to
  one whole-prompt call on the same pool;
* after every chunk, the chunk's KV slab is streamed into the decode-side
  batched caches through the engine-provided ``sink`` (mono: a sliced
  ``scatter_prefill_chunk_caches``; disagg: ``DisaggExecutor
  .scatter_prefill_chunk`` onto the owning attention shard) — the decode
  pool sees the cache fill up incrementally, and the hand-off never moves
  the whole prompt cache in one bulk transfer;
* when the last chunk lands, the request's first token (greedy over the
  final chunk's last-position logits) is returned to the engine, which flips
  the slot ``prefilling → active``.

Timing model: chunks are timed per call (wall clock, or ``prefill_time_fn``
when the engine runs a modeled clock) and accumulated on a *per-device pool
timeline* (``busy_until``) that runs concurrently with the engine's decode
clock — on disjoint hardware the two pools really do overlap; on shared host
devices the schedule (chunk order, per-device serialisation, completion
stamps) is the real one even though the arithmetic shares cores.  The engine
activates a finished request once its clock passes the completion stamp.

Each chunk call, from its dispatch to its logits on the device, is a
``janus.prefill.chunk`` profiler span carrying the request's ``rid`` (a
fused call: its ``rows``), the chunk's first position ``lo`` and its token
count ``n``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import model as model_mod
from repro.serving.request import Request


@dataclasses.dataclass
class PrefillEvent:
    """A finished prefill: returned by :meth:`PrefillWorker.poll`."""

    req: Request
    slot: int
    first_token: int
    finish_t: float  # completion stamp on the prefill-pool timeline


@dataclasses.dataclass
class _InFlight:
    req: Request
    slot: int
    dev_index: int
    prompt: np.ndarray
    caches: Optional[Dict[str, jax.Array]] = None  # per-request decode-format caches
    done: int = 0  # prompt tokens already prefilled (or served from cache)
    ready_t: float = 0.0  # pool-timeline moment the next chunk may start
    prefix: int = 0  # leading tokens served by the prefix cache (skipped here)
    seed: Optional[Dict] = None  # prefix KV rows [L, prefix, ...] to pre-load


class PrefillWorker:
    """Chunked prefill over a dedicated device pool + admission queue."""

    def __init__(
        self,
        cfg,
        params,
        devices: Optional[Sequence[jax.Device]] = None,
        *,
        cache_len: int,
        chunk: int = 64,
        extra: Optional[Dict] = None,
        prefill_time_fn: Optional[Callable[[int], float]] = None,
        max_chunks_per_poll: int = 1,
        batch: int = 1,
    ):
        self.cfg = cfg
        self.cache_len = cache_len
        self.chunk = max(1, int(chunk))
        if getattr(cfg, "sliding_window", None):
            # windowed layers attend [cache ⊕ chunk]: a chunk larger than the
            # rolling window would overwrite keys its own queries still need
            self.chunk = min(self.chunk, min(cache_len, cfg.sliding_window))
        self.extra = extra
        self.prefill_time_fn = prefill_time_fn
        self.max_chunks_per_poll = max(1, int(max_chunks_per_poll))
        self.chunked = model_mod.supports_chunked_prefill(cfg)
        # batched multi-prompt prefill: pack up to ``batch`` pending prompts
        # into one padded-and-masked prefill_chunk call per device, so short
        # prompts stop serialising behind long ones.  Row-independent by
        # construction (per-row starts/lengths mask), so streams stay
        # bit-identical to the one-at-a-time path.
        self.batch = max(1, int(batch))
        self.batched = self.batch > 1 and model_mod.supports_batched_prefill(cfg)
        self.chunks_done = 0
        # fault-injection hook (repro.serving.faults): called before each
        # chunk's compute with (slot, dev_index, chunk_ordinal); may raise
        # PoolFault.  None (the default) keeps the fault-free path untouched.
        self.fault_hook = None
        self.set_devices(devices, params)

        def _call_extra(n_tokens: int):
            """Drop-free MoE capacity by default: a ``None`` capacity becomes
            the call's own token count (an expert can receive at most that
            many tokens), so chunked and whole-prompt prefill both see zero
            drops — the regime where they are bit-equivalent.  ``n_tokens``
            is a static trace-time shape, so this costs no retraces."""
            extra = self.extra
            mc = (extra or {}).get("moe_ctx")
            if mc is not None and mc.get("capacity") is None:
                extra = dict(extra)
                extra["moe_ctx"] = dict(mc, capacity=n_tokens)
            return extra

        def _chunk_fn(p, toks, caches, start):
            return model_mod.prefill_chunk(
                p, toks, caches, start, cfg, extra=_call_extra(toks.shape[1])
            )

        def _full_fn(p, toks):
            return model_mod.prefill(
                p, toks, cfg, self.cache_len, extra=_call_extra(toks.shape[1])
            )

        def _batched_fn(p, toks, caches, starts, lengths):
            return model_mod.prefill_chunk_batched(
                p, toks, caches, starts, lengths, cfg,
                extra=_call_extra(toks.shape[0] * toks.shape[1]),
            )

        self._chunk_jit = jax.jit(_chunk_fn)
        self._full_jit = jax.jit(_full_fn)
        self._batch_jit = jax.jit(_batched_fn)

        self._queue: List[_InFlight] = []
        self._current: List[List[_InFlight]] = [[] for _ in self.devices]

    # ------------------------------------------------------------------
    # pool membership (reconfigure support)
    # ------------------------------------------------------------------
    def set_devices(self, devices: Optional[Sequence[jax.Device]], params) -> None:
        """(Re-)place the full-model replica on every pool device.  With an
        empty pool the worker degrades to the default device (prefill is then
        co-located with decode — the pre-disaggregation layout).  In-flight
        per-request caches migrate with their device index, so a mid-prefill
        pool resize never loses chunk progress."""
        devs = list(devices or [])
        if not devs:
            devs = [jax.devices()[0]]
        self.devices = devs
        self._params = [jax.device_put(params, d) for d in devs]
        # pool timeline survives a resize: a surviving device keeps the time
        # it already claimed (new devices start idle, which is exact)
        old_busy = getattr(self, "busy_until", [])
        self.busy_until = [
            old_busy[i] if i < len(old_busy) else 0.0 for i in range(len(devs))
        ]
        cur = getattr(self, "_current", None)
        if cur:  # migrate in-flight work into the resized pool
            carry = [e for group in cur for e in group]
            self._current = [[] for _ in devs]
            for e in carry:
                e.dev_index = min(e.dev_index, len(devs) - 1)
                if e.caches is not None:
                    e.caches = jax.device_put(e.caches, devs[e.dev_index])
                if len(self._current[e.dev_index]) < self.batch:
                    self._current[e.dev_index].append(e)
                else:
                    self._queue.insert(0, e)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def submit(
        self,
        req: Request,
        slot: int,
        now: float,
        start: int = 0,
        seed_caches: Optional[Dict] = None,
    ) -> None:
        """Queue a reserved request for prefill (FIFO).  A prefix-cache hit
        passes ``start`` (chunk-aligned tokens already served by shared
        pages) and ``seed_caches`` (those positions' KV rows, ``[L, start,
        ...]`` per key) — the worker seeds its per-request cache with them
        and skips straight to the first cold chunk."""
        prompt = req.prompt
        if prompt is None:
            rng = np.random.default_rng(req.rid)
            prompt = rng.integers(0, self.cfg.vocab_size, size=req.input_len, dtype=np.int32)
        start = int(start)
        self._queue.append(
            _InFlight(
                req, slot, -1, np.asarray(prompt, np.int32),
                done=start, ready_t=now, prefix=start, seed=seed_caches,
            )
        )

    @property
    def num_pending(self) -> int:
        return len(self._queue) + sum(len(g) for g in self._current)

    # ------------------------------------------------------------------
    # fault recovery
    # ------------------------------------------------------------------
    def fail_device(self, dev_index: int) -> List[Request]:
        """A prefill device died: drop its in-flight entry's partial caches
        (they lived on the dead device — a real failure destroys them) and
        return the displaced requests for the engine to requeue from chunk 0.
        The device itself stays in ``self.devices`` until the engine resizes
        the pool (``set_devices`` / ``engine.reconfigure``)."""
        displaced: List[Request] = []
        if 0 <= dev_index < len(self._current):
            for entry in self._current[dev_index]:
                entry.caches = None
                entry.seed = None
                entry.done = entry.prefix = 0
                displaced.append(entry.req)
            self._current[dev_index] = []
        return displaced

    def cancel_slot(self, slot: int) -> Optional[Request]:
        """Withdraw a queued or in-flight request by slot (its streamed-out
        chunks were lost downstream); returns the request, or None if the
        worker no longer holds it."""
        for i, entry in enumerate(self._queue):
            if entry.slot == slot:
                self._queue.pop(i)
                return entry.req
        for di, group in enumerate(self._current):
            for entry in group:
                if entry.slot == slot:
                    group.remove(entry)
                    return entry.req
        return None

    def run_sync(self, prompt: np.ndarray, slot: int, sink) -> int:
        """Deterministic replay: prefill ``prompt`` synchronously on device 0
        and stream every chunk through ``sink``, bypassing the queue, the
        pool timeline *and* the fault hook (recovery work is not re-faulted).
        Chunk boundaries match the queued path (fixed size from 0), so the
        replayed KV slabs are bit-identical to what the original admission
        streamed.  Returns the first generated token (greedy)."""
        dev = self.devices[0]
        params = self._params[0]
        prompt = np.asarray(prompt, np.int32)
        n = len(prompt)
        if not self.chunked:
            toks = jax.device_put(jnp.asarray(prompt)[None, :], dev)
            logits, caches = self._full_jit(params, toks)
            sink(slot, 0, -1, caches)
            return int(np.argmax(np.asarray(logits[0])))
        caches = jax.device_put(
            model_mod.init_decode_caches(self.cfg, 1, self.cache_len), dev
        )
        logits = None
        for lo in range(0, n, self.chunk):
            hi = min(lo + self.chunk, n)
            toks = jax.device_put(jnp.asarray(prompt[lo:hi])[None, :], dev)
            logits, caches = self._chunk_jit(params, toks, caches, jnp.int32(lo))
            sink(slot, lo, hi - lo, caches)
        return int(np.argmax(np.asarray(logits[0])))

    # ------------------------------------------------------------------
    # the pipeline: one poll = at most ``max_chunks_per_poll`` chunks/device
    # ------------------------------------------------------------------
    def poll(self, sink: Callable[[int, int, int, Dict], None]) -> List[PrefillEvent]:
        """Advance prefill work and stream finished chunks through ``sink``.

        ``sink(slot, start, length, one_caches)`` lands the chunk's KV rows
        in the decode-side caches (``start``/``length`` index the position
        axis; ``length == -1`` marks a whole-prompt fallback cache).  Returns
        the requests whose prefill finished this poll, stamped with their
        pool-timeline completion times.
        """
        events: List[PrefillEvent] = []
        # groups of size 1 take the exact legacy one-at-a-time path; larger
        # groups (prefill_batch > 1 on a batchable architecture) fuse one
        # padded chunk call per device
        limit = self.batch if self.batched else 1
        for di in range(len(self.devices)):
            group = self._current[di]
            while len(group) < limit and self._queue:
                entry = self._queue.pop(0)
                if entry.caches is not None and entry.dev_index != di:
                    # a resize-displaced entry resumes on a different device:
                    # its partial caches must follow (params live per device)
                    entry.caches = jax.device_put(entry.caches, self.devices[di])
                entry.dev_index = di
                group.append(entry)
            if not group:
                continue
            for _ in range(self.max_chunks_per_poll):
                events.extend(self._advance_group(di, sink))
                if not self._current[di]:
                    break
        return events

    def _advance_group(self, di: int, sink) -> List[PrefillEvent]:
        group = self._current[di]
        if len(group) == 1:
            ev = self._advance(group[0], sink)
            if ev is not None:
                self._current[di] = []
                return [ev]
            return []
        return self._advance_batched(di, sink)

    def _init_caches(self, entry: _InFlight, dev) -> Dict[str, jax.Array]:
        """Fresh per-request caches; a prefix-cache hit pre-loads the shared
        rows so cold chunks attend the full ``[0, start + c)`` span."""
        caches = jax.device_put(
            model_mod.init_decode_caches(self.cfg, 1, self.cache_len), dev
        )
        if entry.seed:
            m = entry.prefix
            for k, rows in entry.seed.items():
                if k in caches:
                    caches[k] = caches[k].at[:, 0, :m].set(
                        jax.device_put(jnp.asarray(rows), dev).astype(caches[k].dtype)
                    )
            entry.seed = None
        return caches

    def _advance(self, entry: _InFlight, sink) -> Optional[PrefillEvent]:
        if self.fault_hook is not None:
            # before any compute or state mutation: a raise here leaves the
            # entry untouched, so a retry of the same poll is trivially safe
            self.fault_hook(entry.slot, entry.dev_index, self.chunks_done)
        dev = self.devices[entry.dev_index]
        params = self._params[entry.dev_index]
        n = len(entry.prompt)
        if not self.chunked:
            # whole-prompt fallback (recurrent / enc-dec stacks): one call on
            # the pool device, one bulk hand-off
            toks = jax.device_put(jnp.asarray(entry.prompt)[None, :], dev)
            t0 = time.perf_counter()
            with TraceAnnotation("janus.prefill.chunk", rid=entry.req.rid, lo=0, n=n):
                logits, caches = self._full_jit(params, toks)
                logits.block_until_ready()
            dt = self.prefill_time_fn(n) if self.prefill_time_fn else time.perf_counter() - t0
            sink(entry.slot, 0, -1, caches)
            return self._finish(entry, logits, dt)

        lo = entry.done
        hi = min(lo + self.chunk, n)
        if entry.caches is None:
            entry.caches = self._init_caches(entry, dev)
        toks = jax.device_put(jnp.asarray(entry.prompt[lo:hi])[None, :], dev)
        t0 = time.perf_counter()
        with TraceAnnotation("janus.prefill.chunk", rid=entry.req.rid, lo=lo, n=hi - lo):
            logits, entry.caches = self._chunk_jit(params, toks, entry.caches, jnp.int32(lo))
            logits.block_until_ready()
        dt = (
            self.prefill_time_fn(hi - lo)
            if self.prefill_time_fn
            else time.perf_counter() - t0
        )
        sink(entry.slot, lo, hi - lo, entry.caches)
        entry.done = hi
        self.chunks_done += 1
        if hi < n:
            # pool-timeline accounting: the chunk starts as soon as both the
            # device and the request's previous chunk are done — the engine's
            # decode clock runs concurrently and is never charged
            start_t = max(self.busy_until[entry.dev_index], entry.ready_t)
            self.busy_until[entry.dev_index] = entry.ready_t = start_t + dt
            return None
        return self._finish(entry, logits, dt)

    def _finish(self, entry: _InFlight, logits, dt: float) -> PrefillEvent:
        start_t = max(self.busy_until[entry.dev_index], entry.ready_t)
        finish_t = start_t + dt
        self.busy_until[entry.dev_index] = finish_t
        first = int(np.argmax(np.asarray(logits[0])))
        entry.caches = None  # working copy dropped; KV already streamed out
        return PrefillEvent(entry.req, entry.slot, first, finish_t)

    def _advance_batched(self, di: int, sink) -> List[PrefillEvent]:
        """One fused chunk call for every request on device ``di``: each
        row's tokens are padded to the widest member chunk and masked by its
        own (start, length), so rows are computed exactly as the serial path
        would — one kernel launch instead of ``len(group)``.  The device
        timeline is charged once for the fused call (the batching win)."""
        group = self._current[di]
        if self.fault_hook is not None:
            for entry in group:
                self.fault_hook(entry.slot, entry.dev_index, self.chunks_done)
        dev = self.devices[di]
        params = self._params[di]
        for entry in group:
            if entry.caches is None:
                entry.caches = self._init_caches(entry, dev)
        B = len(group)
        los = [e.done for e in group]
        his = [min(e.done + self.chunk, len(e.prompt)) for e in group]
        lens = [hi - lo for lo, hi in zip(los, his)]
        cmax = max(lens)
        toks = np.zeros((B, cmax), np.int32)
        for i, e in enumerate(group):
            toks[i, : lens[i]] = e.prompt[los[i] : his[i]]
        keys = list(group[0].caches.keys())
        stacked = {
            k: jnp.concatenate([e.caches[k] for e in group], axis=1) for k in keys
        }
        toks_d = jax.device_put(jnp.asarray(toks), dev)
        starts = jax.device_put(jnp.asarray(los, jnp.int32), dev)
        lengths = jax.device_put(jnp.asarray(lens, jnp.int32), dev)
        total = sum(lens)
        t0 = time.perf_counter()
        with TraceAnnotation("janus.prefill.chunk", rows=B, lo=min(los), n=total):
            logits, stacked = self._batch_jit(params, toks_d, stacked, starts, lengths)
            logits.block_until_ready()
        dt = (
            self.prefill_time_fn(total)
            if self.prefill_time_fn
            else time.perf_counter() - t0
        )
        start_t = max([self.busy_until[di]] + [e.ready_t for e in group])
        finish_t = start_t + dt
        self.busy_until[di] = finish_t
        events: List[PrefillEvent] = []
        logits_np: Optional[np.ndarray] = None
        remaining: List[_InFlight] = []
        for i, e in enumerate(group):
            e.caches = {k: stacked[k][:, i : i + 1] for k in keys}
            sink(e.slot, los[i], lens[i], e.caches)
            e.done = his[i]
            e.ready_t = finish_t
            self.chunks_done += 1
            if e.done >= len(e.prompt):
                if logits_np is None:
                    logits_np = np.asarray(logits)
                first = int(np.argmax(logits_np[i]))
                e.caches = None
                events.append(PrefillEvent(e.req, e.slot, first, finish_t))
            else:
                remaining.append(e)
        self._current[di] = remaining
        return events
