"""Continuous-batching decode engine with the Janus scheduled-MoE path.

The engine serves a stream of requests against one model deployment:

  * admission: waiting requests are placed into free batch slots through one
    of two admission paths:

      - ``admission="blocking"`` (legacy *schedule*): each request's whole
        prompt is prefilled before the next decode iteration, charging the
        decode clock — one long prompt stalls every in-flight request.  The
        compute itself goes through the same worker/chunking as the
        pipelined path, so the two admission modes are bit-identical in
        what they serve and differ only in when;
      - ``admission="pipelined"`` (default when a prefill pool exists): the
        request *reserves* a slot and is handed to the
        :class:`repro.serving.prefill.PrefillWorker`, which chunks the
        prompt on the dedicated prefill pool and streams each finished
        chunk's KV slab straight into the decode-side batched caches; the
        slot walks ``reserved → prefilling → active`` and the decode loop
        never waits on a prompt;

  * decode: one batched decode per iteration with *per-slot* positions
    (continuous batching — slots join/leave independently), through one of
    two executors sharing identical semantics and telemetry:

      - ``executor="mono"``: the jitted monolithic ``decode_step`` on the
        default device (single-instance baseline);
      - ``executor="disagg"``: the two-decode-pool
        :class:`repro.serving.disagg.DisaggExecutor` — attention stages on
        ``n_attn`` pool devices, expert stages on the MoE pool, with the
        adaptive two-phase exchange realised per layer and per-step
        regime / transfer-byte / ``a_max`` telemetry recorded;

  * MoE architectures route through the scheduled slot path: routing →
    AEBS (or a baseline scheduler) → replica-slot dispatch, with per-layer
    ``a_max`` telemetry surfaced to the controller.  Dispatch defaults to
    the sort-based grouped path (``repro.models.moe.grouped_dispatch_ffn``)
    — no per-step ``[S_total, d, f]`` weight materialisation;
  * timing: wall-clock by default, or pluggable ``step_time_fn`` /
    ``prefill_time_fn`` driven by the analytic performance model (used in
    tests and the simulator); the prefill pool keeps its own concurrent
    timeline, so pipelined admission never charges prompt work to the
    decode clock;
  * scaling: :meth:`ServingEngine.reconfigure` actuates a controller
    decision mid-run (§3.5) — prefill, attention and MoE pool counts move
    independently, in-flight KV caches are preserved;
  * spans: each decode iteration, admission, prefill chunk and KV hand-off
    is a ``jax.profiler.TraceAnnotation`` named ``janus.*`` (counts as its
    keyword arguments), on the device trace's clock when a profiler runs
    and ~1 µs each when none does.  A decode iteration is ``janus.decode``
    with the children ``.prepare`` (pages, uploads), ``.device`` (the step
    until its logits are ready), ``.sample`` (token readback) and
    ``.advance`` (per-slot bookkeeping); no span is opened per slot.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.aebs import ReplicaLayout, aebs_assign
from repro.core import baselines
from repro.core.disagg import DevicePools
from repro.core.placement import layout_for_survivors
from repro.kernels.aebs.ops import aebs_schedule
from repro.models import model as model_mod
from repro.serving.faults import (
    DEVICE_LOSS,
    FaultPlan,
    FaultRuntime,
    PoolFault,
    RetryPolicy,
    Watchdog,
)
from repro.serving.kv_cache import (
    ACTIVE,
    PAGED_KEYS,
    PREFILLING,
    PagedKVCache,
    PrefixIndex,
    SlotManager,
    make_paged_caches,
    paginate_caches,
    scatter_prefill_caches,
    scatter_prefill_chunk_caches,
    scatter_prefill_chunk_paged,
    zero_slots,
)
from repro.serving.prefill import PrefillEvent, PrefillWorker
from repro.serving.request import Request

SCHEDULERS = {
    "aebs": aebs_assign,
    "aebs_kernel": aebs_schedule,  # Pallas TPU kernel, same Algorithm-1 contract
    "random": baselines.random_assign,
    "token_hash": baselines.token_hash_assign,
    "none": None,
}

# request-level admission schedulers (``sched=``, distinct from the MoE
# replica-slot ``scheduler=``): fifo = strict arrival order, priority =
# higher Request.priority first with preemption via KV spill/restore
ADMISSION_SCHEDS = ("fifo", "priority")


@dataclasses.dataclass
class _SpillRecord:
    """A preempted request waiting off-batch: its detached KV payload (a
    mono :class:`SpilledKV` or disagg ``SpilledSlotKV``) and, on disagg, the
    shard the pages must re-attach to.  ``payload=None`` means the pages
    were dissolved by an attention re-shard while spilled — the restore
    falls back to deterministic replay."""

    req: Request
    payload: Optional[Any]
    shard: Optional[int] = None
    spilled_at: float = 0.0  # engine clock at preemption (TPOT wait split)


class ServingEngine:
    def __init__(
        self,
        cfg,
        params,
        *,
        max_batch: int = 8,
        cache_len: int = 512,
        layout: Optional[ReplicaLayout] = None,
        scheduler: str = "aebs",
        capacity_tokens: Optional[int] = None,
        prefill_capacity_tokens: Optional[int] = None,  # default: capacity_tokens
        dispatch: str = "grouped",  # grouped = slot-indirect hot path (no weight copy)
        step_time_fn: Optional[Callable[[int], float]] = None,
        prefill_time_fn: Optional[Callable[[int], float]] = None,
        extra_builder: Optional[Callable[[int], Dict]] = None,
        executor: str = "mono",  # mono | disagg
        n_attn: int = 1,
        n_prefill: int = 0,
        admission: Optional[str] = None,  # blocking | pipelined (default: pipelined iff n_prefill)
        prefill_chunk: int = 64,
        pools: Optional[DevicePools] = None,
        node_size: int = 1,
        ping_pong: bool = False,
        fault_plan: Optional[FaultPlan] = None,
        retry_policy: Optional[RetryPolicy] = None,
        watchdog: Optional[Watchdog] = None,
        max_prefill_queue: Optional[int] = None,  # admission backpressure bound
        kv_page_size: Optional[int] = None,  # page the "" KV caches (None = contiguous)
        kv_num_pages: Optional[int] = None,  # pool size (default: full backing + null)
        prefix_cache: bool = False,  # page-granular radix prefix reuse (needs paging)
        prefix_cache_pages: Optional[int] = None,  # index pin budget (None = unbounded)
        prefill_batch: int = 1,  # prompts fused per prefill-device chunk call
        sched: str = "fifo",  # request admission: fifo | priority (preemptive)
        draft_config=None,  # small config drafting tokens → speculative decode
        draft_params=None,  # default: shared weights (self-draft) or fresh init
        spec_k: int = 0,  # drafts per verify step (0 + draft_config → 2)
    ):
        self.cfg = cfg
        self.params = params
        self.slots = SlotManager(max_batch, cache_len)
        self.cache_len = cache_len
        self.max_batch = max_batch
        self.layout = layout
        self.scheduler_name = scheduler
        self.step_time_fn = step_time_fn
        self.prefill_time_fn = prefill_time_fn
        self.extra_builder = extra_builder
        self.executor_name = executor
        # the next decode step's input tokens: written on the host, uploaded
        # once per iteration by _upload_tokens (the only source of truth)
        self.tokens = np.zeros((max_batch, 1), np.int32)
        self._tokens_in = None  # the uploaded copy the decode step reads
        self._tokens_dirty = True
        self.token_uploads = 0
        self.clock = 0.0
        self.amax_log: List[int] = []
        self.regime_log: List[str] = []
        self.transfer_bytes_log: List[int] = []
        self.completed: List[Request] = []
        self.rejected: List[Request] = []
        self.decode_stall_time = 0.0  # prefill time charged while decodes were in flight
        self.steps_done = 0  # global decode-step ordinal (fault schedules key off it)
        if max_prefill_queue is not None and max_prefill_queue < 1:
            raise ValueError(
                f"max_prefill_queue must be ≥ 1, got {max_prefill_queue} "
                "(a zero bound would close admission permanently)"
            )
        self.max_prefill_queue = max_prefill_queue
        if sched not in ADMISSION_SCHEDS:
            raise ValueError(
                f"unknown admission scheduler {sched!r}; choose from "
                f"{ADMISSION_SCHEDS}"
            )
        self.sched = sched
        self._spilled: List[_SpillRecord] = []  # preempted, awaiting restore
        self.preempt_count = 0
        self.restore_count = 0
        self.spill_replay_count = 0  # restores that had to replay (pages lost)
        self.kv_page_size = kv_page_size
        self.kv_num_pages = kv_num_pages
        self.paged: Optional[PagedKVCache] = None  # mono-executor page manager
        self.prefix_cache = bool(prefix_cache)
        self.prefix_cache_pages = prefix_cache_pages
        self.prefix: Optional[PrefixIndex] = None  # mono-executor radix index
        if self.prefix_cache:
            if kv_page_size is None:
                raise ValueError(
                    "prefix_cache requires paged KV storage (set kv_page_size) "
                    "— a hit is served by block-table page sharing"
                )
            if not model_mod.supports_batched_prefill(cfg):
                raise ValueError(
                    "prefix_cache requires an architecture whose decode caches "
                    "are all full-attention (dense/moe periods) — rolling-"
                    "window / recurrent state cannot be seeded positionally"
                )
        # effective worker chunk (mirrors PrefillWorker's sliding-window
        # clamp) — the prefix index's chunk grid must match it exactly
        eff_chunk = max(1, int(prefill_chunk))
        if getattr(cfg, "sliding_window", None):
            eff_chunk = min(eff_chunk, min(cache_len, cfg.sliding_window))
        self.faults: Optional[FaultRuntime] = None
        self.degraded_reason: Optional[str] = None
        # subscribers notified on permanent device loss: fn(fault, clock).
        # The AutoScaler attaches here so lost capacity feeds its next decision.
        self.fault_listeners: List[Callable[[PoolFault, float], None]] = []

        moe_ctx = None
        if cfg.has_moe and layout is not None and scheduler != "none":
            moe_ctx = dict(
                dispatch=dispatch,
                layout_tables=layout.device_tables(),
                slot_to_expert=jnp.asarray(layout.slot_to_expert.reshape(-1)),
                num_instances=layout.num_instances,
                scheduler=SCHEDULERS[scheduler],
                capacity=capacity_tokens,
            )
        self._moe_ctx = moe_ctx

        self.disagg: Optional["DisaggExecutor"] = None
        if executor == "disagg":
            from repro.serving.disagg import DisaggExecutor

            if layout is None or scheduler == "none":
                raise ValueError("executor='disagg' needs a replica layout and scheduler")
            if pools is None:
                pools = DevicePools.split(
                    n_attn, layout.num_instances, node_size=node_size,
                    n_prefill=n_prefill,
                    allow_reuse=len(jax.devices()) < n_attn + layout.num_instances + n_prefill,
                )
            self.disagg = DisaggExecutor(
                cfg, params, pools, layout,
                max_batch=max_batch, cache_len=cache_len,
                scheduler=SCHEDULERS[scheduler], capacity=capacity_tokens,
                ping_pong=ping_pong,
                kv_page_size=kv_page_size, kv_num_pages=kv_num_pages,
                prefix_cache=self.prefix_cache,
                prefix_cache_pages=prefix_cache_pages,
                prefix_chunk=eff_chunk,
            )
            self.caches = None  # cache residency moves to the executor's pool
        elif executor == "mono":
            if pools is None and n_prefill:
                pools = DevicePools.split(
                    0, 0, n_prefill=n_prefill,
                    allow_reuse=len(jax.devices()) < n_prefill,
                )
            self.caches = model_mod.init_decode_caches(cfg, max_batch, cache_len)
            if kv_page_size is not None:
                self.paged, self.caches = make_paged_caches(
                    self.caches, max_batch, cache_len, kv_page_size, kv_num_pages
                )
        else:
            raise ValueError(f"unknown executor: {executor}")

        def _decode(params, tokens, caches, positions):
            extra = {"moe_ctx": moe_ctx} if moe_ctx else None
            return model_mod.decode_step(params, tokens, caches, positions, cfg, extra=extra)

        self._decode_jit = jax.jit(_decode)

        # --- speculative decode: draft model + batched greedy verify -------
        # The draft proposes ``spec_k`` tokens per iteration; one
        # ``decode_step_verify`` call scores all of them (plus the last
        # accepted token) in a single pass and the longest greedy-matching
        # prefix is accepted.  Verification is against the target's own
        # argmax, so the emitted stream is bit-identical to non-speculative
        # greedy decode no matter what the draft proposes — the draft only
        # moves the acceptance rate, never the tokens.
        if spec_k < 0:
            raise ValueError(f"spec_k must be ≥ 0, got {spec_k}")
        if spec_k and draft_config is None:
            raise ValueError("spec_k > 0 requires a draft_config")
        if draft_config is not None and spec_k == 0:
            spec_k = 2
        self.spec_k = int(spec_k)
        self.draft_config = draft_config if self.spec_k else None
        self.spec_steps = 0  # verify iterations taken
        self.spec_slot_steps = 0  # per-slot verify participations
        self.spec_draft_tokens = 0  # draft proposals scored
        self.spec_draft_accepted = 0  # draft proposals accepted
        self.spec_emitted_tokens = 0  # tokens emitted by verify steps
        self._draft_params = None
        self._draft_caches = None
        # slot → (rid, n): draft cache rows [0, n) mirror request rid's true
        # token stream; anything less at decode position forces a rebuild
        self._draft_stream: Dict[int, tuple] = {}
        if self.spec_k:
            dcfg = draft_config
            if not model_mod.supports_speculative_decode(cfg):
                raise ValueError(
                    "speculative decode requires full-context dense/moe decode "
                    "layers (rolling-window / recurrent state has no batched "
                    "multi-position verify)"
                )
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({dcfg.vocab_size}) must match target vocab "
                    f"({cfg.vocab_size}) — draft tokens are verified verbatim"
                )
            if draft_params is not None:
                self._draft_params = draft_params
            elif dcfg is cfg or dcfg.name == cfg.name:
                self._draft_params = params  # self-draft: share target weights
            else:
                self._draft_params = model_mod.init_params(dcfg, seed=0)
            self._draft_caches = model_mod.init_decode_caches(
                dcfg, max_batch, cache_len
            )

            def _draft_decode(dparams, tokens, caches, positions):
                return model_mod.decode_step(dparams, tokens, caches, positions, dcfg)

            def _draft_prefill(dparams, tokens):
                return model_mod.prefill(dparams, tokens, dcfg, cache_len)

            def _verify(params, tokens, caches, positions, widths):
                # same extra as the base decode closure: the verify unrolls
                # per-position decode steps, so each routes exactly b tokens
                # under the unchanged capacity budget — identical drop
                # patterns to sequential decode by construction
                extra = {"moe_ctx": moe_ctx} if moe_ctx else None
                return model_mod.decode_step_verify(
                    params, tokens, caches, positions, cfg, extra=extra, widths=widths
                )

            self._draft_decode_jit = jax.jit(_draft_decode)
            self._draft_prefill_jit = jax.jit(_draft_prefill)
            self._verify_jit = jax.jit(_verify)

        # prefill path: logical-expert routing (no scheduling — prompts don't
        # route through replica slots) on the sort-based grouped dispatch.
        # Capacity is drop-free by default: the worker fills a None capacity
        # with each call's own token count (an expert can receive at most
        # that many tokens), so blocking, pipelined and chunked prefill all
        # see zero drops and stay bit-identical regardless of the decode
        # budget.  ``prefill_capacity_tokens`` overrides this with a fixed
        # cap for operators who deliberately want prompt-side drops.
        prefill_moe_ctx = (
            {"capacity": prefill_capacity_tokens, "dispatch": "grouped"}
            if cfg.has_moe
            else None
        )

        # admission pipeline (tentpole): all prompt work goes through the
        # PrefillWorker — "pipelined" overlaps it with decode via the slot
        # state machine, "blocking" drains it synchronously per request and
        # charges the decode clock (the legacy schedule).  Sharing one worker
        # keeps the two admission modes' numerics identical by construction
        # (same chunking, same jitted programs), so token streams are
        # bit-equal across admission modes, not just across executors.
        if admission is None:
            admission = "pipelined" if n_prefill else "blocking"
        if admission not in ("blocking", "pipelined"):
            raise ValueError(f"unknown admission mode: {admission}")
        self.admission = admission
        self._ready: List[PrefillEvent] = []
        prefill_devices = list(pools.prefill_devices) if pools is not None else []
        worker_extra = self.extra_builder(1) if self.extra_builder else None
        if prefill_moe_ctx is not None:
            worker_extra = dict(worker_extra or {})
            worker_extra["moe_ctx"] = prefill_moe_ctx
        # under a modeled decode clock with no prefill model, prefill is free
        # (legacy semantics) — never mix wall-clock stamps into a modeled
        # timeline, or activation times become meaningless hybrids
        worker_time_fn = prefill_time_fn
        if step_time_fn is not None and prefill_time_fn is None:
            worker_time_fn = lambda n_tok: 0.0
        self.prefill_worker = PrefillWorker(
            cfg, params, prefill_devices,
            cache_len=cache_len, chunk=prefill_chunk,
            extra=worker_extra, prefill_time_fn=worker_time_fn,
            batch=prefill_batch,
        )
        if self.prefix_cache and self.paged is not None:
            self.prefix = PrefixIndex(
                self.prefill_worker.chunk, self.paged,
                max_pages=prefix_cache_pages,
            )

        if fault_plan is not None:
            self.arm_faults(fault_plan, policy=retry_policy, watchdog=watchdog)

    # ------------------------------------------------------------------
    # fault injection / health monitoring
    # ------------------------------------------------------------------
    def arm_faults(
        self,
        plan: FaultPlan,
        policy: Optional[RetryPolicy] = None,
        watchdog: Optional[Watchdog] = None,
    ) -> FaultRuntime:
        """Arm a fault plan: build the runtime and install its hooks on the
        executor exchange path and the prefill worker's chunk loop.  With no
        plan armed neither hook exists and the hot path is untouched."""
        self.faults = FaultRuntime(plan, policy=policy, watchdog=watchdog)
        if self.disagg is not None:
            self.disagg.fault_hook = self.faults.exchange_hook
        self.prefill_worker.fault_hook = self.faults.prefill_hook
        return self.faults

    def _pool_sizes(self) -> Dict[str, int]:
        sizes = {"attn": 0, "moe": 0}
        if self.disagg is not None:
            sizes["attn"] = len(self.disagg.pools.attn_devices)
            sizes["moe"] = len(self.disagg.pools.moe_devices)
        sizes["prefill"] = len(self.prefill_worker.devices)
        return sizes

    def _charge(self, dt: float) -> None:
        """Advance the clock for fault handling (backoff, recovery) and book
        the stall so operators can see what faults cost."""
        if dt <= 0:
            return
        self.clock += dt
        if self.faults is not None:
            self.faults.stats.fault_stall_s += dt

    def _fault_preflight(self) -> None:
        """Heartbeat: fire any step-scheduled faults, then poll pool health
        and recover from every detected device loss before decoding."""
        self.faults.advance_to_step(self.steps_done)
        while True:
            fault = self.faults.poll_health(self._pool_sizes())
            if fault is None:
                return
            self._recover(fault)

    def _recover(self, fault: PoolFault) -> None:
        """Dispatch recovery for a permanent fault and book its latency."""
        t0 = time.perf_counter()
        if fault.pool == "moe":
            self._recover_moe_loss(fault)
        elif fault.pool == "attn":
            self._recover_attn_loss(fault)
        elif fault.pool == "prefill":
            self._recover_prefill_loss(fault)
        else:  # unknown pool: last resort
            self._degrade_to_mono(f"unrecoverable fault: {fault}")
        self.faults.mark_handled(fault)
        wall = time.perf_counter() - t0
        stats = self.faults.stats
        stats.recoveries += 1
        stats.recovery_latency_s.append(wall)
        # modeled clocks charge the policy constant (deterministic tests);
        # wall clocks charge what recovery actually took
        self._charge(
            self.faults.policy.recovery_charge_s if self.step_time_fn else wall
        )
        if fault.kind == DEVICE_LOSS:
            for listener in self.fault_listeners:
                listener(fault, self.clock)

    def _recover_moe_loss(self, fault: PoolFault) -> None:
        """Permanent MoE-device loss: re-plan expert placement onto the
        survivors and re-lower only the MoE pool.  Every expert keeps a seat,
        so expert semantics — hence token streams — are unchanged."""
        ex = self.disagg
        if ex is None:
            return  # already degraded to mono: there is no MoE pool to lose
        n_moe = len(ex.pools.moe_devices)
        if n_moe <= 1:
            self._degrade_to_mono("lost the last MoE device")
            return
        ex.exclude_device("moe", fault.index)
        new_layout = layout_for_survivors(self.cfg.num_experts, n_moe - 1)
        self.reconfigure(n_moe=n_moe - 1, layout=new_layout)

    def _recover_attn_loss(self, fault: PoolFault) -> None:
        """Permanent attention-device loss: the dead shard's KV rows are
        gone.  Re-shard the batch over the survivors, then rebuild each lost
        slot by deterministic replay (re-prefill + re-decode of its own
        history) — bit-exact because every row is rewritten by the same
        jitted program that originally produced it."""
        ex = self.disagg
        if ex is None:
            return
        if len(ex.pools.attn_devices) <= 1:
            # no surviving shard to host the batch: degrade, then rebuild
            # everything (the whole batch lived on the dead device)
            self._degrade_to_mono(
                "lost the last attention device",
                lost_rows=list(range(self.max_batch)),
            )
            return
        lost_rows = ex.drop_attn_device(fault.index)
        # the re-shard rebuilt every shard's page pool from slot-owned pages
        # — KV detached into spill records dissolved with the old pools
        self._invalidate_spills()
        self._rebuild_lost_slots(lost_rows)

    def _recover_prefill_loss(self, fault: PoolFault) -> None:
        """Prefill-worker/device failure: drop its in-flight prefill, shrink
        the pool, and requeue the displaced request from chunk 0 — chunked
        prefill is deterministic, so the restart serves identical tokens."""
        worker = self.prefill_worker
        displaced = worker.fail_device(fault.index)
        if self.disagg is not None and len(self.disagg.pools.prefill_devices) > 0:
            self.disagg.exclude_device("prefill", fault.index)
            self.reconfigure(
                n_prefill=len(self.disagg.pools.prefill_devices) - 1
            )  # syncs worker.set_devices (falls back to the default device at 0)
        else:
            survivors = [d for i, d in enumerate(worker.devices) if i != fault.index]
            worker.set_devices(survivors, self.params)
        for req in displaced:
            slot = req.slot
            self.slots.fail(slot)
            self.slots.requeue(slot)
            self.slots.start_prefill(slot)
            # drop the slot's pages — including any prefix-cache pins — and
            # re-splice fresh: the restart must not leak reservations
            self._release_pages(slot)
            start, seed = self._prefix_splice(req, slot)
            worker.submit(
                req, slot, now=max(self.clock, req.arrival),
                start=start, seed_caches=seed,
            )
            self.faults.stats.requeued += 1

    def _rebuild_lost_slots(self, lost_rows: List[int]) -> None:
        """Restore every occupied slot whose KV rows a dead attention shard
        took with it: ACTIVE slots replay their full history; PREFILLING
        slots requeue (their already-streamed chunks landed on the dead
        shard); RESERVED/FREE slots had nothing to lose."""
        stats = self.faults.stats
        for slot in lost_rows:
            state = self.slots.state[slot]
            if state == ACTIVE:
                self._replay_slot(slot)
                stats.replayed_slots += 1
            elif state == PREFILLING:
                req = self.prefill_worker.cancel_slot(slot)
                if req is None:
                    # prefill already finished; its event is waiting for
                    # activation but every streamed chunk is lost — drop the
                    # event and restart the prompt
                    for ev in self._ready:
                        if ev.slot == slot:
                            req = ev.req
                    self._ready = [ev for ev in self._ready if ev.slot != slot]
                if req is None:
                    continue
                self.slots.fail(slot)
                self.slots.requeue(slot)
                self.slots.start_prefill(slot)
                self._release_pages(slot)
                start, seed = self._prefix_splice(req, slot)
                self.prefill_worker.submit(
                    req, slot, now=max(self.clock, req.arrival),
                    start=start, seed_caches=seed,
                )
                stats.requeued += 1

    def _replay_slot(self, slot: int) -> None:
        """Deterministically rebuild one slot's KV: re-prefill the prompt
        through the worker (same chunk boundaries, same jitted program →
        bit-exact), then re-decode the generated tokens one at a time with
        every other slot parked at the scratch row — each row is rebuilt by
        the machinery that originally wrote it, and every replayed token is
        checked against the recorded stream."""
        req = self.slots.slot_req[slot]
        first = self.prefill_worker.run_sync(
            self._prompt_tokens(req), slot, self._chunk_sink
        )
        if req.tokens_out and first != req.tokens_out[0]:
            raise RuntimeError(
                f"recovery replay diverged at the first token of slot {slot}: "
                f"{first} != {req.tokens_out[0]}"
            )
        for t in range(req.generated):
            toks = np.zeros((self.max_batch, 1), np.int32)
            toks[slot, 0] = req.tokens_out[t]
            pos = np.full((self.max_batch,), self.cache_len - 1, np.int32)
            pos[slot] = req.input_len + t
            self._ensure_slot_page(slot, req.input_len + t)
            if self.disagg is not None:
                logits, _ = self.disagg.decode_step(toks, jnp.asarray(pos))
            else:
                logits, self.caches = self._decode_jit(
                    self.params, jnp.asarray(toks), self.caches, jnp.asarray(pos)
                )
            nxt = int(np.argmax(np.asarray(logits[slot])))
            if nxt != req.tokens_out[t + 1]:
                raise RuntimeError(
                    f"recovery replay diverged at generated token {t} of slot "
                    f"{slot}: {nxt} != {req.tokens_out[t + 1]}"
                )

    def _degrade_to_mono(
        self, reason: str, lost_rows: Optional[List[int]] = None
    ) -> None:
        """Last resort: collapse the disaggregated executor onto the default
        device.  Surviving KV is exported; ``lost_rows`` (rows a dead shard
        destroyed) are zeroed and rebuilt by replay after the switch."""
        ex = self.disagg
        if self.faults is not None:
            self.faults.stats.degraded += 1
        if ex is None:
            return
        caches = ex.export_caches()
        if lost_rows:
            caches = zero_slots(caches, lost_rows)
        if self.kv_page_size is not None:
            # re-paginate the dense export: fresh page ids, same position→
            # value mapping, so replayed streams stay bit-identical
            lengths = np.array(ex.slot_lengths(), np.int64)
            if lost_rows:
                lengths[np.asarray(lost_rows)] = 0
            self.paged, caches = paginate_caches(
                caches, lengths, self.kv_page_size, self.kv_num_pages
            )
            if self.prefix_cache:
                # sharing dissolved with the shard pagers; restart a fresh
                # mono index over the re-paginated pool
                self.prefix = PrefixIndex(
                    self.prefill_worker.chunk, self.paged,
                    max_pages=self.prefix_cache_pages,
                )
        self.caches = jax.device_put(caches, jax.devices()[0])
        self.disagg = None
        self.executor_name = "mono"
        self.degraded_reason = reason
        # shard pagers died with the executor: spilled KV restores by replay
        self._invalidate_spills()
        if lost_rows:
            self._rebuild_lost_slots(lost_rows)

    def _guarded_decode(self, positions, spec=None) -> tuple:
        """One decode step with the fault envelope: transient exchange faults
        retry the (idempotent) step under exponential backoff; a spent retry
        budget or an unrecoverable fault degrades to mono; injected
        sub-deadline delays are charged to the clock."""
        if self.faults is None:
            return self._decode_once(positions, spec)
        attempt = 0
        while True:
            try:
                logits, tel = self._decode_once(positions, spec)
            except PoolFault as fault:
                if not fault.transient:
                    self._recover(fault)
                    continue
                attempt += 1
                self.faults.stats.retries += 1
                if attempt > self.faults.policy.max_retries:
                    self.faults.mark_handled(fault)
                    self._degrade_to_mono(f"retry budget exhausted: {fault}")
                    continue
                self._charge(self.faults.policy.delay(attempt))
                continue
            self._charge(self.faults.consume_delay())
            return logits, tel

    def _set_token(self, slot: int, token: int) -> None:
        """Write ``slot``'s next decode input on the host copy."""
        self.tokens[slot, 0] = token
        self._tokens_dirty = True

    def _upload_tokens(self) -> bool:
        """Send the step's input tokens to the executor once, if they changed
        since the last upload.  The upload is a fresh copy, so no device
        array aliases the host buffer the loop keeps writing.  Mono puts it
        on the default device; the disagg executor is handed the host copy
        and puts each shard's rows on that shard's attention device."""
        if not self._tokens_dirty:
            return False
        snap = self.tokens.copy()
        self._tokens_in = snap if self.disagg is not None else jax.device_put(snap)
        self._tokens_dirty = False
        self.token_uploads += 1
        return True

    def _decode_once(self, positions, spec=None) -> tuple:
        if spec is not None:
            vtokens, widths = spec
            if self.disagg is not None:
                logits, tel = self.disagg.decode_step_verify(
                    vtokens, positions, widths
                )
                logits.block_until_ready()
                return logits, tel
            logits, self.caches = self._verify_jit(
                self.params, vtokens, self.caches, positions, widths
            )
            logits.block_until_ready()
            return logits, None
        if self.disagg is not None:
            logits, tel = self.disagg.decode_step(self._tokens_in, positions)
            logits.block_until_ready()
            return logits, tel
        logits, self.caches = self._decode_jit(
            self.params, self._tokens_in, self.caches, positions
        )
        logits.block_until_ready()
        return logits, None

    def _worker_poll(self) -> List[PrefillEvent]:
        """Poll the prefill worker under the fault envelope: transient chunk
        faults retry (the hook fires before any compute, so the chunk is
        untouched); a spent budget escalates to device loss on that device."""
        if self.faults is None:
            return self.prefill_worker.poll(self._chunk_sink)
        attempt = 0
        while True:
            try:
                return self.prefill_worker.poll(self._chunk_sink)
            except PoolFault as fault:
                if not fault.transient:
                    self._recover(fault)
                    continue
                attempt += 1
                self.faults.stats.retries += 1
                if attempt > self.faults.policy.max_retries:
                    self.faults.mark_handled(fault)
                    self._recover(
                        PoolFault(
                            "prefill", fault.index, DEVICE_LOSS,
                            transient=False,
                            detail="chunk retry budget exhausted",
                        )
                    )
                    attempt = 0
                    continue
                self._charge(self.faults.policy.delay(attempt))

    def _reject(self, req: Request) -> None:
        """Admission control: the request waited past its deadline while the
        engine was saturated — reject it without ever holding a slot."""
        req.rejected = True
        req.finished = self.clock
        self.rejected.append(req)

    def cancel_slot(self, slot: int) -> Optional[Request]:
        """Withdraw a reserved/prefilling request before activation: pull it
        from the prefill worker (or its finished-but-unactivated event),
        release the slot's pages — dropping any prefix-cache pins — and free
        the slot.  Returns the withdrawn request, or None if the slot holds
        nothing cancellable (free or already active)."""
        req = self.prefill_worker.cancel_slot(slot)
        if req is None:
            for ev in self._ready:
                if ev.slot == slot:
                    req = ev.req
            self._ready = [ev for ev in self._ready if ev.slot != slot]
        if req is None:
            held = self.slots.slot_req[slot]
            if held is not None and self.slots.state[slot] != ACTIVE:
                req = held
        if req is None:
            return None
        self._release_pages(slot)
        self.slots.release(slot)
        return req

    def _admission_open(self) -> bool:
        """Backpressure: stop admitting when the prefill queue is saturated."""
        if self.max_prefill_queue is None:
            return True
        return self.prefill_worker.num_pending < self.max_prefill_queue

    # ------------------------------------------------------------------
    # prefix cache (page-granular radix reuse)
    # ------------------------------------------------------------------
    def _prompt_tokens(self, req: Request) -> np.ndarray:
        """The request's prompt tokens, materialising the seeded synthetic
        prompt when none was given (same rng contract as the worker)."""
        if req.prompt is not None:
            return np.asarray(req.prompt, np.int32)
        rng = np.random.default_rng(req.rid)
        return rng.integers(0, self.cfg.vocab_size, size=req.input_len, dtype=np.int32)

    def _prefix_splice(self, req: Request, slot: int):
        """Serve the longest cached prefix of ``req``'s prompt into the
        freshly reserved ``slot``: shared pages are spliced into its block
        table (copy-on-write for a trailing partial page) and the matched KV
        rows are gathered for worker seeding.  Returns ``(start,
        seed_caches)`` for :meth:`PrefillWorker.submit` — ``(0, None)`` when
        the cache is off or misses.  The match is capped at the largest chunk
        boundary strictly below the prompt length so at least one token
        always prefills (activation needs first-token logits)."""
        if not self.prefix_cache:
            return 0, None
        tokens = self._prompt_tokens(req)
        chunk = self.prefill_worker.chunk
        limit = ((len(tokens) - 1) // chunk) * chunk
        if limit <= 0:
            return 0, None
        if self.disagg is not None:
            return self.disagg.splice_prefix(slot, tokens, limit)
        match, pages = self.prefix.lookup(tokens, limit)
        if not match:
            return 0, None
        cow = self.paged.splice(slot, pages, match)
        caches = dict(self.caches)
        if cow is not None:
            src, dst, rows = cow
            for k in PAGED_KEYS:
                if k in caches:
                    caches[k] = caches[k].at[:, dst, :rows].set(
                        caches[k][:, src, :rows]
                    )
        pgs, offs = self.paged.rows_of(slot, 0, match)
        seed = {k: caches[k][:, pgs, offs] for k in PAGED_KEYS if k in caches}
        caches["block_tables"] = self.paged.table_device()
        self.caches = caches
        return match, seed

    def _prefix_publish(self, req: Request, slot: int) -> None:
        """Index the chunk-aligned span of the prompt ``slot`` just finished
        prefilling (called at activation, when every row is written)."""
        if not self.prefix_cache:
            return
        tokens = self._prompt_tokens(req)
        chunk = self.prefill_worker.chunk
        upto = (len(tokens) // chunk) * chunk
        if upto <= 0:
            return
        if self.disagg is not None:
            self.disagg.publish_prefix(slot, tokens, upto)
        else:
            self.prefix.publish(tokens, upto, slot)

    # ------------------------------------------------------------------
    # priority scheduling: preemption via KV spill/restore
    # ------------------------------------------------------------------
    def _preempt_capable(self) -> bool:
        """Preemption needs paged KV — spill is a block-table detach, and a
        contiguous cache has no tables to detach."""
        if self.paged is not None:
            return True
        return self.disagg is not None and self.disagg._pagers is not None

    def _find_slot(self, shard: Optional[int]) -> Optional[int]:
        """Lowest free slot, restricted to one disagg shard when a spilled
        record must re-attach where its pages live."""
        free = self.slots.free_slots
        if shard is None or self.disagg is None:
            return free[0] if free else None
        for s in free:
            if self.disagg.shard_of(s) == shard:
                return s
        return None

    def _pick_victim(self, priority: int, shard: Optional[int]) -> Optional[int]:
        """The active slot to preempt for a priority-``priority`` candidate:
        strictly lower priority only (equal priority never preempts — that
        would thrash), preferring the least-generated victim (least work
        parked off-batch), slot index breaking ties deterministically."""
        best = None
        for s in self.slots.active_slots:
            if shard is not None and self.disagg is not None:
                if self.disagg.shard_of(s) != shard:
                    continue
            req = self.slots.slot_req[s]
            if req.priority >= priority:
                continue
            key = (req.priority, req.generated, s)
            if best is None or key < best[0]:
                best = (key, s)
        return None if best is None else best[1]

    def preempt_slot(self, slot: int) -> Request:
        """Preempt an ACTIVE slot: detach its KV pages into a spill record
        (block-table move, zero copy — prefix-cache pins ride along via
        their refcounts) and free the slot.  The request keeps its stream
        state (``tokens_out``, ``generated``) and resumes bit-identically
        when a slot frees up or its priority wins one back."""
        if self.slots.state[slot] != ACTIVE:
            raise RuntimeError(
                f"slot {slot} is {self.slots.state[slot]}, cannot preempt"
            )
        if self.paged is not None:
            payload, shard = self.paged.spill(slot), None
        elif self.disagg is not None and self.disagg._pagers is not None:
            payload, shard = self.disagg.spill_slot(slot)
        else:
            raise RuntimeError("preemption requires paged KV (set kv_page_size)")
        req = self.slots.release(slot)
        # slot's pages moved to the record, so the usual free-on-release is
        # a no-op — but the call keeps release paths uniform (and drops
        # nothing because spill already emptied the ownership list)
        self._release_pages(slot)
        req.preemptions += 1
        self._spilled.append(
            _SpillRecord(
                req=req, payload=payload, shard=shard, spilled_at=self.clock
            )
        )
        self.preempt_count += 1
        self._draft_stream.pop(slot, None)
        return req

    def _restore_record(self, rec: _SpillRecord, slot: int) -> None:
        """Re-admit a spilled request into free ``slot``: re-attach its
        pages (or rebuild them by deterministic replay when a re-shard
        dissolved the pool they lived in) and resume decode at
        ``input_len + generated`` with the last emitted token as input."""
        req = rec.req
        self.slots.reserve(req, slot=slot)
        if rec.payload is None:
            self.slots.resume(slot)
            self._replay_slot(slot)
            self.spill_replay_count += 1
        else:
            if self.paged is not None:
                self.paged.restore(slot, rec.payload)
            else:
                self.disagg.restore_slot(slot, rec.payload)
            self.slots.resume(slot)
        self._set_token(slot, req.tokens_out[-1])
        # the park time between two of the request's tokens is scheduling
        # wait, not decode latency — record it so TPOT can split it out
        if req.wait_spans is None:
            req.wait_spans = []
        req.wait_spans.append((rec.spilled_at, self.clock))
        self.restore_count += 1

    def _drop_spill(self, rec: _SpillRecord) -> None:
        """Abandon a spill record (deadline lapsed): free its pages."""
        if rec.payload is None:
            return
        if self.paged is not None:
            self.paged.drop_spilled(rec.payload)
        elif self.disagg is not None:
            self.disagg.drop_spilled(rec.payload)

    def _invalidate_spills(self) -> None:
        """An attention re-shard (device loss, reconfigure, degrade) rebuilt
        the page pools from slot-owned pages — detached spill payloads
        dissolved with the old pools.  Downgrade every record to
        restore-by-replay (bit-exact by construction, like fault replay)."""
        for rec in self._spilled:
            rec.payload = None
            rec.shard = None

    def _schedule_admission(self, waiting: List[Request]) -> List[Request]:
        """Place arrived work into slots.  ``sched="fifo"`` is the legacy
        strict-arrival-order loop.  ``sched="priority"`` merges spilled
        (restorable) and new requests into one candidate order — priority
        first, restores before fresh admits on ties, then arrival — and,
        when no slot is free, spills the lowest-priority active slot for a
        strictly higher-priority candidate."""
        if self.sched == "fifo":
            while (
                waiting
                and waiting[0].arrival <= self.clock
                and self.slots.free_slots
                and self._admission_open()
            ):
                req = waiting.pop(0)
                if self.admission == "pipelined":
                    self._submit_request(req)
                else:
                    self._prefill_request(req)
            return waiting
        while True:
            cands: List[tuple] = []
            for rec in self._spilled:
                cands.append(
                    (-rec.req.priority, 0, rec.req.arrival, rec.req.rid, rec)
                )
            for r in waiting:
                if r.arrival <= self.clock:
                    cands.append((-r.priority, 1, r.arrival, r.rid, r))
            cands.sort(key=lambda c: c[:4])
            progressed = False
            for key in cands:
                item = key[-1]
                is_restore = isinstance(item, _SpillRecord)
                # restores bypass prefill backpressure: they need no prefill
                if not is_restore and not self._admission_open():
                    continue
                shard = item.shard if is_restore else None
                slot = self._find_slot(shard)
                if slot is None and self._preempt_capable():
                    prio = item.req.priority if is_restore else item.priority
                    victim = self._pick_victim(prio, shard)
                    if victim is not None:
                        self.preempt_slot(victim)
                        slot = self._find_slot(shard)
                if slot is None:
                    continue
                if is_restore:
                    self._spilled.remove(item)
                    self._restore_record(item, slot)
                else:
                    waiting.remove(item)
                    if self.admission == "pipelined":
                        self._submit_request(item)
                    else:
                        self._prefill_request(item)
                progressed = True
                break
            if not progressed:
                return waiting

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def _prefill_request(self, req: Request) -> None:
        """Blocking admission: drain the prefill worker synchronously for this
        one request — the legacy schedule (the decode loop stalls for the
        whole prompt), with the same chunked compute as the pipelined path."""
        with TraceAnnotation("janus.admit", rid=req.rid, prompt_len=req.input_len) as span:
            stalled = self.slots.num_active > 0
            slot = self.slots.reserve(req)
            span.set_metadata(slot=slot)
            self.slots.start_prefill(slot)
            now = max(self.clock, req.arrival)
            start, seed = self._prefix_splice(req, slot)
            self.prefill_worker.submit(req, slot, now=now, start=start, seed_caches=seed)
            events: List[PrefillEvent] = []
            while not events:
                events = self._worker_poll()
            ev = events[0]
            # legacy clock semantics: modeled prefill time when calibrated, wall
            # otherwise (zero under a modeled decode clock with no prefill model —
            # the worker's time_fn is already pinned to 0 for that combination)
            dt = ev.finish_t - now
            self.slots.activate(slot)
            self._set_token(slot, ev.first_token)
            self.clock += dt
            if stalled:
                self.decode_stall_time += dt
            req.prefill_done = self.clock
            req.token_times.append(self.clock)
            req.tokens_out = [ev.first_token]
            self._prefix_publish(req, slot)

    def _submit_request(self, req: Request) -> None:
        """Pipelined admission: reserve the slot, queue the prompt for the
        prefill pool — the decode clock is never charged."""
        with TraceAnnotation("janus.admit", rid=req.rid, prompt_len=req.input_len) as span:
            slot = self.slots.reserve(req)
            span.set_metadata(slot=slot)
            self.slots.start_prefill(slot)
            start, seed = self._prefix_splice(req, slot)
            self.prefill_worker.submit(
                req, slot, now=max(self.clock, req.arrival),
                start=start, seed_caches=seed,
            )

    def _chunk_sink(self, slot: int, start: int, length: int, one_caches: Dict) -> None:
        """Land one streamed prefill chunk (or a whole-prompt fallback cache,
        ``length == -1``) in the decode-side caches."""
        with TraceAnnotation("janus.prefill.handoff", slot=slot, lo=start, n=length):
            if self.disagg is not None:
                if length < 0:
                    self.disagg.scatter_prefill(one_caches, slot)
                else:
                    self.disagg.scatter_prefill_chunk(one_caches, slot, start, length)
            elif self.paged is not None:
                if length < 0:
                    # whole-prompt fallback: the prompt's rows are one big chunk;
                    # positionless state (ssm/enc_out) takes the contiguous path
                    start, length = 0, self.slots.slot_req[slot].input_len
                    rest = {
                        k: v for k, v in one_caches.items() if not k.startswith("kv_")
                    }
                    if rest:
                        self.caches = scatter_prefill_caches(self.caches, rest, slot)
                self.caches = scatter_prefill_chunk_paged(
                    self.caches, one_caches, slot, start, length, self.paged
                )
            elif length < 0:
                self.caches = scatter_prefill_caches(self.caches, one_caches, slot)
            else:
                self.caches = scatter_prefill_chunk_caches(
                    self.caches, one_caches, slot, start, length
                )

    # ------------------------------------------------------------------
    # paged-KV slot lifecycle
    # ------------------------------------------------------------------
    def _ensure_pages(self, widths=None) -> bool:
        """Back every active slot's next write position with a page (alloc on
        append) and refresh the device block table if anything changed.  A
        speculative verify writes ``widths[s]`` rows starting at the slot's
        position, so its whole candidate span is backed up front.  Returns
        whether the mono block table went to the device again (the disagg
        executor uploads its shards' tables inside its step)."""
        if self.paged is not None:
            for s in self.slots.active_slots:
                extent = int(widths[s]) - 1 if widths is not None else 0
                self.paged.ensure(s, int(self.slots.positions[s]) + extent)
            if self.paged.dirty:
                self.caches = dict(self.caches)
                self.caches["block_tables"] = self.paged.table_device()
                return True
        elif self.disagg is not None:
            for s in self.slots.active_slots:
                extent = int(widths[s]) - 1 if widths is not None else 0
                self.disagg.ensure_slot_pages(
                    s, int(self.slots.positions[s]) + extent
                )
        return False

    def _ensure_slot_page(self, slot: int, pos: int) -> None:
        """Replay-path variant of :meth:`_ensure_pages` for a single slot."""
        if self.paged is not None:
            self.paged.ensure(slot, pos)
            if self.paged.dirty:
                self.caches = dict(self.caches)
                self.caches["block_tables"] = self.paged.table_device()
        elif self.disagg is not None:
            self.disagg.ensure_slot_pages(slot, pos)

    def _release_pages(self, slot: int) -> None:
        """Free a released slot's pages (free-on-release)."""
        if self.paged is not None:
            self.paged.release(slot)
        elif self.disagg is not None:
            self.disagg.release_slot(slot)

    def _poll_prefill(self) -> None:
        """Advance the prefill pipeline and activate any finished requests
        whose completion stamp the decode clock has passed."""
        self._ready.extend(self._worker_poll())
        still: List[PrefillEvent] = []
        for ev in self._ready:
            if ev.finish_t <= self.clock:
                self.slots.activate(ev.slot)
                self._set_token(ev.slot, ev.first_token)
                ev.req.prefill_done = ev.finish_t
                ev.req.token_times.append(ev.finish_t)
                ev.req.tokens_out = [ev.first_token]
                self._prefix_publish(ev.req, ev.slot)
            else:
                still.append(ev)
        self._ready = still

    def _prefill_pending(self) -> int:
        return self.prefill_worker.num_pending + len(self._ready)

    # ------------------------------------------------------------------
    # speculative decode: draft → batched verify → greedy acceptance
    # ------------------------------------------------------------------
    def _draft_ensure(self, slot: int) -> None:
        """Make the draft cache mirror ``slot``'s true token stream up to its
        decode position.  Fresh activations, restores into a new slot, and
        slot reuse all land here and rebuild by whole-history draft prefill;
        a slot that advanced through speculation rounds is already covered.
        The rebuild need not be numerically identical to the incremental
        path — emitted tokens never depend on draft numerics, only the
        acceptance rate does."""
        req = self.slots.slot_req[slot]
        pos = int(self.slots.positions[slot])
        rid, have = self._draft_stream.get(slot, (None, -1))
        if rid == req.rid and have >= pos:
            return
        history = self._prompt_tokens(req)
        if req.generated:
            history = np.concatenate(
                [history, np.asarray(req.tokens_out[:-1], np.int32)]
            )
        _, one = self._draft_prefill_jit(
            self._draft_params, jnp.asarray(history[None, :])
        )
        self._draft_caches = scatter_prefill_caches(self._draft_caches, one, slot)
        self._draft_stream[slot] = (req.rid, pos)

    def _spec_widths(self) -> np.ndarray:
        """Per-slot verify width: ``spec_k + 1`` rows (last accepted token +
        drafts), clamped so a slot never scores past its remaining output
        budget or the cache rows non-speculative decode could have written
        (positions ≤ cache_len - 3 before the truncation check)."""
        c = self.spec_k + 1
        widths = np.zeros(self.max_batch, np.int32)
        for s in self.slots.active_slots:
            req = self.slots.slot_req[s]
            pos = int(self.slots.positions[s])
            w = min(c, req.output_len - req.generated, self.cache_len - 2 - pos)
            widths[s] = max(1, w)
        return widths

    def _spec_iteration(self) -> None:
        """One speculative decode iteration: k + 1 draft forwards (the extra
        one keeps the draft cache exactly one token behind the feed so a
        fully accepted round never leaves a stale row), one batched verify,
        then per-slot greedy acceptance.  Each slot gains between 1 and
        ``spec_k + 1`` tokens; rejected rows are left beyond the advanced
        position where the decode mask never reads them, and the paged
        high-water mark is truncated back to honesty."""
        if self.faults is not None:
            self._fault_preflight()
        active = list(self.slots.active_slots)
        widths = self._spec_widths()
        with TraceAnnotation("janus.decode.prepare") as span:
            span.set_metadata(
                dirty=self._ensure_pages(widths), tokens=int(self._upload_tokens())
            )
        for s in active:
            self._draft_ensure(s)
        t0 = time.perf_counter()
        c = self.spec_k + 1
        drafts = np.zeros((self.max_batch, self.spec_k), np.int32)
        feed = self._tokens_in
        for j in range(c):
            dpos = jnp.asarray(
                np.minimum(self.slots.positions + j, self.cache_len - 1)
            )
            dlogits, self._draft_caches = self._draft_decode_jit(
                self._draft_params, feed, self._draft_caches, dpos
            )
            if j < self.spec_k:
                nxt = np.asarray(jnp.argmax(dlogits, axis=-1), np.int32)
                drafts[:, j] = nxt
                feed = jnp.asarray(nxt[:, None])
        vtokens = np.zeros((self.max_batch, c), np.int32)
        vtokens[:, 0] = self.tokens[:, 0]
        vtokens[:, 1:] = drafts
        positions = self.slots.positions_device()
        with TraceAnnotation("janus.decode.device"):
            logits, tel = self._guarded_decode(
                positions, spec=(jnp.asarray(vtokens), jnp.asarray(widths))
            )
        if tel is not None:
            self.regime_log.append(tel["regime"])
            self.transfer_bytes_log.append(tel["bytes_total"])
            self.amax_log.append(tel["a_max"])
        wall = time.perf_counter() - t0
        self.clock += (
            self.step_time_fn(self.slots.num_active) if self.step_time_fn else wall
        )
        self.steps_done += 1
        self.spec_steps += 1

        with TraceAnnotation("janus.decode.sample"):
            greedy = np.asarray(jnp.argmax(logits, axis=-1), np.int32)  # [b, c]
        with TraceAnnotation("janus.decode.advance") as span:
            done = len(self.completed)
            for s in active:
                if self.slots.state[s] != ACTIVE:
                    continue  # released by a recovery path mid-iteration
                req = self.slots.slot_req[s]
                w = int(widths[s])
                a = 0
                while a < w - 1 and drafts[s, a] == greedy[s, a]:
                    a += 1
                gained = a + 1
                self.spec_slot_steps += 1
                self.spec_draft_tokens += w - 1
                self.spec_draft_accepted += a
                self.spec_emitted_tokens += gained
                for j in range(gained):
                    req.generated += 1
                    req.token_times.append(self.clock)
                    self.slots.advance(s)
                    if req.tokens_out is not None:
                        req.tokens_out.append(int(greedy[s, j]))
                self._set_token(s, greedy[s, a])
                pos = int(self.slots.positions[s])
                # verify backed w rows but only `gained` advanced: clamp the
                # high-water mark so spill records and occupancy stay honest
                if self.paged is not None:
                    self.paged.truncate(s, pos)
                elif self.disagg is not None:
                    self.disagg.truncate_slot(s, pos)
                self._draft_stream[s] = (req.rid, pos)
                if req.generated >= req.output_len or pos >= self.cache_len - 2:
                    if req.generated < req.output_len:
                        req.truncated = True  # context exhausted before target
                    req.finished = self.clock
                    self.completed.append(self.slots.release(s))
                    self._release_pages(s)
                    self._draft_stream.pop(s, None)
            span.set_metadata(completed=len(self.completed) - done)

    # ------------------------------------------------------------------
    def _decode_iteration(self) -> None:
        with TraceAnnotation(
            "janus.decode", step=self.steps_done, active=self.slots.num_active
        ):
            if self.spec_k:
                self._spec_iteration()
                return
            if self.faults is not None:
                self._fault_preflight()
            with TraceAnnotation("janus.decode.prepare") as span:
                span.set_metadata(
                    dirty=self._ensure_pages(), tokens=int(self._upload_tokens())
                )
                positions = self.slots.positions_device()
            t0 = time.perf_counter()
            with TraceAnnotation("janus.decode.device"):
                logits, tel = self._guarded_decode(positions)
            if tel is not None:
                self.regime_log.append(tel["regime"])
                self.transfer_bytes_log.append(tel["bytes_total"])
                self.amax_log.append(tel["a_max"])
            wall = time.perf_counter() - t0
            self.clock += self.step_time_fn(self.slots.num_active) if self.step_time_fn else wall
            self.steps_done += 1

            with TraceAnnotation("janus.decode.sample"):
                next_tokens = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            with TraceAnnotation("janus.decode.advance") as span:
                done = len(self.completed)
                for s in self.slots.active_slots:
                    req = self.slots.slot_req[s]
                    req.generated += 1
                    req.token_times.append(self.clock)
                    self.slots.advance(s)
                    self._set_token(s, next_tokens[s])
                    if req.tokens_out is not None:
                        req.tokens_out.append(int(next_tokens[s]))
                    if req.generated >= req.output_len or self.slots.positions[s] >= self.cache_len - 2:
                        if req.generated < req.output_len:
                            req.truncated = True  # context exhausted before target length
                        req.finished = self.clock
                        self.completed.append(self.slots.release(s))
                        self._release_pages(s)
                span.set_metadata(completed=len(self.completed) - done)

    # ------------------------------------------------------------------
    def run(self, requests: List[Request], max_steps: int = 100_000) -> Dict:
        """Serve all requests (arrivals gated by the engine clock)."""
        waiting = sorted(requests, key=lambda r: r.arrival)
        steps = 0
        while (
            waiting or self._spilled or self.slots.num_active or self._prefill_pending()
        ) and steps < max_steps:
            # admission control: reject arrived requests whose deadline lapsed
            # while the engine was saturated (they never held a slot)
            if any(r.deadline is not None for r in waiting):
                still_waiting: List[Request] = []
                for r in waiting:
                    if (
                        r.deadline is not None
                        and r.arrival <= self.clock
                        and self.clock > r.deadline
                    ):
                        self._reject(r)
                    else:
                        still_waiting.append(r)
                waiting = still_waiting
            # a reserved/prefilling request whose deadline lapsed mid-queue is
            # cancelled: its slot and pages (including prefix pins) return to
            # the pool instead of finishing a prompt nobody will wait for
            for slot in self.slots.pending_slots:
                req = self.slots.slot_req[slot]
                if (
                    req is not None
                    and req.deadline is not None
                    and self.clock > req.deadline
                ):
                    if self.cancel_slot(slot) is not None:
                        self._reject(req)
            # a spilled (preempted) request whose deadline lapsed off-batch
            # is dropped: its detached pages return to the pool
            for rec in list(self._spilled):
                if rec.req.deadline is not None and self.clock > rec.req.deadline:
                    self._spilled.remove(rec)
                    self._drop_spill(rec)
                    self._reject(rec.req)
            # admit arrived requests into slots (fifo or priority/preemptive)
            waiting = self._schedule_admission(waiting)
            self._poll_prefill()
            if self.slots.num_active == 0:
                if self._ready:  # idle: jump to the next prefill completion
                    self.clock = max(self.clock, min(ev.finish_t for ev in self._ready))
                    continue
                if self._prefill_pending():  # chunks still streaming: keep polling
                    continue
                if waiting:  # idle: jump to next arrival
                    self.clock = max(self.clock, waiting[0].arrival)
                    continue
                break
            self._decode_iteration()
            steps += 1
        return self.metrics()

    # ------------------------------------------------------------------
    def reconfigure(
        self,
        n_attn: Optional[int] = None,
        n_moe: Optional[int] = None,
        layout: Optional[ReplicaLayout] = None,
        n_prefill: Optional[int] = None,
    ) -> Dict[str, bool]:
        """Actuate a scaling decision mid-run (§3.5): only the pools whose
        counts changed are re-lowered; in-flight KV caches are preserved and
        in-progress chunked prefills migrate with the prefill pool.
        Disagg executor only — the monolithic engine re-lowers wholesale."""
        if self.disagg is None:
            raise NotImplementedError(
                "mid-run reconfigure requires executor='disagg' (the monolithic "
                "engine re-lowers wholesale — rebuild the engine instead)"
            )
        relower = self.disagg.reconfigure(
            n_attn=n_attn, n_moe=n_moe, layout=layout, n_prefill=n_prefill
        )
        if relower.get("attn"):
            # attention re-shard rebuilt the page pools: detached spill
            # payloads dissolved — downgrade them to restore-by-replay
            self._invalidate_spills()
        self.layout = self.disagg.layout
        if relower.get("prefill"):
            self.prefill_worker.set_devices(
                self.disagg.pools.prefill_devices, self.params
            )
        return relower

    # ------------------------------------------------------------------
    def metrics(self) -> Dict:
        done = self.completed
        out: Dict = {"completed": len(done), "tokens": sum(r.generated for r in done)}
        out["truncated"] = sum(1 for r in done if r.truncated)
        out["rejected"] = len(self.rejected)
        out["preemptions"] = self.preempt_count
        out["restores"] = self.restore_count
        if self.spill_replay_count:
            out["spill_replays"] = self.spill_replay_count
        # SLO attainment over every *measured* request (one that carries a
        # TTFT or TPOT target): rejected/unserved requests count as misses,
        # so shedding load can never inflate attainment
        measured = [
            r for r in done + self.rejected if r.slo_ok() is not None
        ]
        if measured:
            per_tenant: Dict[str, List[bool]] = {}
            for r in measured:
                per_tenant.setdefault(r.tenant, []).append(bool(r.slo_ok()))
            out["slo"] = {
                "measured": len(measured),
                "attained": sum(1 for r in measured if r.slo_ok()),
                "attainment": sum(1 for r in measured if r.slo_ok()) / len(measured),
                "per_tenant": {
                    t: sum(v) / len(v) for t, v in sorted(per_tenant.items())
                },
            }
        out["decode_stall_time"] = self.decode_stall_time
        out["prefill_chunks"] = self.prefill_worker.chunks_done
        out["token_uploads"] = self.token_uploads
        if self.spec_k:
            out["spec"] = {
                "k": self.spec_k,
                "steps": self.spec_steps,
                "draft_tokens": self.spec_draft_tokens,
                "accepted_draft_tokens": self.spec_draft_accepted,
                "emitted_tokens": self.spec_emitted_tokens,
                "accepted_per_step": (
                    self.spec_emitted_tokens / self.spec_slot_steps
                    if self.spec_slot_steps
                    else 0.0
                ),
                "acceptance_rate": (
                    self.spec_draft_accepted / self.spec_draft_tokens
                    if self.spec_draft_tokens
                    else 0.0
                ),
            }
        if self.paged is not None:
            out["kv_pages"] = self.paged.stats()
        elif self.disagg is not None:
            page_stats = self.disagg.page_stats()
            if page_stats is not None:
                out["kv_pages"] = page_stats
        if self.prefix is not None:
            out["prefix_cache"] = self.prefix.stats()
        elif self.disagg is not None:
            prefix_stats = self.disagg.prefix_stats()
            if prefix_stats is not None:
                out["prefix_cache"] = prefix_stats
        if self.faults is not None:
            out["faults"] = self.faults.stats.as_dict()
            if self.degraded_reason is not None:
                out["degraded_reason"] = self.degraded_reason
        # disaggregated-exchange telemetry (satellite of amax_log): which
        # two-phase regime served each step, and the bytes it moved
        if self.regime_log:
            out["regime_counts"] = {
                r: self.regime_log.count(r) for r in sorted(set(self.regime_log))
            }
            out["transfer_bytes_total"] = int(sum(self.transfer_bytes_log))
            out["transfer_bytes_per_step"] = float(
                np.mean(self.transfer_bytes_log)
            )
        if self.amax_log:
            out["amax_mean"] = float(np.mean(self.amax_log))
            out["amax_max"] = int(np.max(self.amax_log))
        if not done:
            return out
        # TTFT: prompt turnaround (arrival → first token) — the metric the
        # prefill pool exists to improve; TPOT alone can't see prefill wins
        ttfts = np.array([r.prefill_done - r.arrival for r in done if r.prefill_done >= 0])
        if len(ttfts):
            out["ttft_mean"] = float(ttfts.mean())
            out["ttft_p99"] = float(np.percentile(ttfts, 99))
        gaps = np.concatenate(
            [r.decode_gaps() for r in done if len(r.token_times) > 1]
        )
        span = max(r.finished for r in done) - min(r.arrival for r in done)
        out.update(
            throughput_tok_s=out["tokens"] / max(span, 1e-9),
            tpot_mean=float(gaps.mean()) if len(gaps) else 0.0,
            tpot_p99=float(np.percentile(gaps, 99)) if len(gaps) else 0.0,
            clock=self.clock,
        )
        return out
