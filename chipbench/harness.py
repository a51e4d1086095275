"""The benchmark's serving loop: ``ServingEngine`` driven on the wall clock.

The harness builds the engine from a configuration file, warms every shape
the cell's traffic reaches, then runs its own loop over the three calls that
``ServingEngine.run`` makes in each iteration -- ``_schedule_admission``,
``_poll_prefill`` and ``_decode_iteration`` -- handing a request to the engine
only when it is due on the wall clock.  Every time stamp is the harness's
``time.perf_counter()`` read once the result is on the host (the engine turns
each step's tokens into host integers before it returns); the engine's own
``clock`` and the time fields of ``ServingEngine.metrics()`` are never read.

Engine surface used (private methods; a public wall-clock step is a program
change for a later PR): ``_schedule_admission``, ``_poll_prefill``,
``_decode_iteration``, ``slots`` (``free_slots``, ``active_slots``,
``num_active``, ``positions``, ``slot_req``), ``completed``, ``amax_log``,
``transfer_bytes_log``, ``prefill_worker.chunks_done``, ``params``,
``caches``, ``paged``, ``disagg`` (its ``shards``, ``_pagers``, ``_kv`` and
the module's ``_KV_KEYS``); the window wraps ``_decode_once`` to keep the
newest step's logits for the check.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import gc
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import arch
from chipbench import traffic as traffic_mod

BENCH_DIR = Path(__file__).resolve().parent
CONFIG_DIR = BENCH_DIR / "configs"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

def load_config(name: str) -> Dict:
    """The configuration file, with its arch module (``arch.py``) resolved
    once under ``"equations"``."""
    with open(CONFIG_DIR / f"{name}.json") as fh:
        cf = json.load(fh)
    cf["equations"] = arch.for_config(cf)
    prog = cf["program"]
    for ours, theirs in cf["equations"].published_pairs(prog):
        if float(prog[ours]) != float(cf[theirs]):
            raise ValueError(f"{name}: program {ours}={prog[ours]} but {theirs}={cf[theirs]}")
    return cf


def model_config(cf: Dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(**cf["program"])


def make_params(cfg, seed: int):
    """The program's weights from ``seed``, made on the device by one jitted
    call of the model's initialiser (the same values as ``model.init_params``)."""
    import jax
    from repro.models import transformer

    init = jax.jit(lambda key: transformer.init_params(cfg, key))
    params = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def build_engine(cfg, params, serving: Dict, seed: int):
    """``ServingEngine`` with the deployment of the configuration file.

    Mirrors ``repro.launch.serve.build_engine`` (same layout from the same
    routing trace) and adds ``capacity_tokens``, which that launcher does not
    expose: at ``capacity_tokens = max_batch`` no decode token is dropped.
    The copy stays only until the launcher takes a ``capacity_tokens``
    option (PERF.md, Open questions); the harness then calls it."""
    from repro.serving.engine import ServingEngine

    layout = None
    if cfg.has_moe and serving["scheduler"] != "none":
        from repro.core.amax import make_routing_trace
        from repro.core.placement import build_layout

        trace = make_routing_trace(2048, cfg.num_experts, cfg.top_k, skew=0.8, seed=seed)
        layout = build_layout(
            trace, cfg.num_experts, serving["n_instances"], serving["slots_per_instance"]
        )
    return ServingEngine(
        cfg, params,
        max_batch=serving["max_batch"], cache_len=serving["cache_len"], layout=layout,
        scheduler=serving["scheduler"], capacity_tokens=serving["capacity_tokens"],
        executor=serving["executor"], n_attn=serving["n_attn"],
        prefill_chunk=serving["prefill_chunk"], kv_page_size=serving["kv_page_size"],
        kv_num_pages=serving["kv_num_pages"],
    )


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Served:
    """A request as the client saw it: when it was due (open loop) or sent
    (closed loop), and when each of its tokens reached the host."""

    item: traffic_mod.Item
    sent: float
    req: object  # the engine's Request
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class Span:
    name: str  # admit | poll | decode | idle
    t0: float
    t1: float
    active: int = 0  # decode: slots active in the call
    lengths: Optional[np.ndarray] = None  # decode: KV lengths of the active slots
    tokens: int = 0  # admit: prompt tokens prefilled


class CompileCounter:
    """Counts backend compilations (cache loads included) as they happen."""

    def __init__(self):
        import jax

        self.times: List[float] = []
        self.names: List[str] = []

        def _cb(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.times.append(time.perf_counter())
                self.names.append(str(kw.get("fun_name", "?")))

        self._cb = _cb
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def between(self, t0: float, t1: float) -> List[str]:
        """Names of the programs compiled (or loaded) between t0 and t1."""
        return [n for t, n in zip(self.times, self.names) if t0 <= t <= t1]

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._cb)


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------


class Loop:
    """Drives one engine.  ``annotate`` wraps each engine call in a profiler
    ``TraceAnnotation`` named after its span, on the device trace's clock."""

    def __init__(self, engine, mix: Dict, gen: traffic_mod.Generator, annotate: bool = False):
        self.engine = engine
        self.mix = mix
        self.gen = gen
        self.annotate = annotate
        self.queue: List[Served] = []  # due, not yet in the engine
        self.by_rid: Dict[int, Served] = {}
        self.served: List[Served] = []
        self.spans: List[Span] = []
        self.waiting_first: List[Served] = []
        self.lateness: List[float] = []
        self.schedule: List[traffic_mod.Item] = []
        self.t_origin = 0.0
        self.n_completed = 0

    @contextlib.contextmanager
    def _span(self, name: str, **info):
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"chipbench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield info
        finally:
            t1 = time.perf_counter()
            if self.annotate:
                ann.__exit__(None, None, None)
            self.spans.append(Span(name, t0, t1, **info))

    # -- sending -----------------------------------------------------------
    def send(self, item: traffic_mod.Item, t: float) -> Served:
        from repro.serving.request import Request

        # arrival 0 passes the engine's own gate at once: the harness decides
        # when a request is due, on the wall clock
        req = Request(
            rid=item.rid, arrival=0.0, input_len=len(item.prompt),
            output_len=max(1, item.out_len - 1), prompt=item.prompt,
            token_times=[],
        )
        s = Served(item=item, sent=t, req=req)
        self.by_rid[item.rid] = s
        self.served.append(s)
        self.queue.append(s)
        return s

    def start_closed(self, n_clients: int) -> None:
        self.t_origin = time.perf_counter()
        for c in range(n_clients):
            it = self.gen.make(first=True)
            it.client = c
            self.send(it, self.t_origin)

    def start_open(self, horizon_s: float) -> None:
        self.schedule = traffic_mod.open_schedule(self.gen, horizon_s)
        self.t_origin = time.perf_counter()

    def _release_due(self, now: float) -> None:
        while self.schedule and self.t_origin + self.schedule[0].due <= now:
            it = self.schedule.pop(0)
            due = self.t_origin + it.due
            self.lateness.append(now - due)  # how late the generator ran
            self.send(it, due)

    # -- one iteration -----------------------------------------------------
    def step(self) -> None:
        eng = self.engine
        self._release_due(time.perf_counter())
        while self.queue and eng.slots.free_slots:
            s = self.queue[0]
            with self._span("admit", tokens=len(s.item.prompt)):
                left = eng._schedule_admission([s.req])
            if left:
                break
            self.queue.pop(0)
            self.waiting_first.append(s)
            self._stamp_first()
        if eng._prefill_pending():  # the engine's poll has work only then
            with self._span("poll"):
                eng._poll_prefill()
            self._stamp_first()
        if eng.slots.num_active:
            rows = eng.slots.active_slots
            reqs = [eng.slots.slot_req[i] for i in rows]
            before = [len(r.tokens_out) for r in reqs]
            lengths = eng.slots.positions[rows].astype(np.int64) + 1
            with self._span("decode", active=len(rows), lengths=lengths):
                eng._decode_iteration()
            t = time.perf_counter()
            for r, b in zip(reqs, before):
                self.by_rid[r.rid].stamps.extend([t] * (len(r.tokens_out) - b))
            self._complete(t)
        elif not self.queue and not eng._prefill_pending():
            # nothing in flight: wait for the next due request
            nxt = self.t_origin + self.schedule[0].due if self.schedule else time.perf_counter() + 1e-3
            with self._span("idle"):
                while time.perf_counter() < nxt:
                    time.sleep(min(1e-3, max(0.0, nxt - time.perf_counter())))

    def _stamp_first(self) -> None:
        if not self.waiting_first:
            return
        t = time.perf_counter()
        still = []
        for s in self.waiting_first:
            if s.req.tokens_out:
                s.stamps.append(t)
            else:
                still.append(s)
        self.waiting_first = still

    def _complete(self, t: float) -> None:
        done = self.engine.completed[self.n_completed:]
        self.n_completed = len(self.engine.completed)
        for r in done:
            s = self.by_rid[r.rid]
            s.done = True
            if self.mix["loop"] == "closed":  # the client sends again at once
                it = self.gen.make()
                it.client = s.item.client
                it.due = t - self.t_origin
                bisect.insort(self.schedule, it, key=lambda x: x.due)


class LastStep:
    """Wraps the engine's ``_decode_once`` to keep the newest decode step's
    logits and the request each active row belonged to: the check compares
    those rows with the reference's logits.  It keeps references only; the
    logits come to the host after the window has closed."""

    def __init__(self, engine):
        self.logits = None
        self.reqs: Dict[int, object] = {}
        inner = engine._decode_once
        slots = engine.slots

        def recording(positions, spec=None):
            logits, tel = inner(positions, spec)
            self.logits = logits
            self.reqs = {i: slots.slot_req[i] for i in slots.active_slots}
            return logits, tel

        engine._decode_once = recording

    def decoded(self, req) -> bool:
        """Whether ``req``'s newest token came from the recorded step."""
        return any(r is req for r in self.reqs.values())

    def row(self, req) -> np.ndarray:
        slot = next(i for i, r in self.reqs.items() if r is req)
        return np.asarray(self.logits[slot], np.float32)


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------


def final_chunk_lengths(mix: Dict, chunk: int) -> List[int]:
    """Every length the last prefill chunk of the mix's prompts can have."""
    lo, hi = mix["prompt_len"]["low"], mix["prompt_len"]["high"]
    return sorted({(n - 1) % chunk + 1 for n in range(lo, hi + 1)})


def warm_up(engine, mix: Dict, gen_seed: int, vocab: int) -> int:
    """Run the cell's own shapes once through the timed path: one prompt of
    each final-chunk length (the program compiles a prefill program per
    chunk length) and a full prompt chunk, each served four tokens, in
    batches that fill the slots.  Returns the number of requests served."""
    chunk = engine.prefill_worker.chunk
    lens = [n for n in final_chunk_lengths(mix, chunk) if n != chunk] + [chunk]
    rng = np.random.default_rng([gen_seed, 11])
    warm_mix = {"loop": "warm"}
    # four tokens each: the last decode steps of a batch then run with an
    # unchanged block table, which the engine passes back as the previous
    # step's (committed) output -- a second variant of the decode program
    items = [
        traffic_mod.Item(rid=-(i + 1), prompt=rng.integers(0, vocab, size=n, dtype=np.int32), out_len=4)
        for i, n in enumerate(lens)
    ]
    loop = Loop(engine, warm_mix, gen=None)
    # the full-chunk prompt goes alone first: once a decode step has run, the
    # page pool is that step's output, as it is for every prefill in the window
    for batch in (items[-1:], items[:-1]):
        for it in batch:
            loop.send(it, time.perf_counter())
        while loop.queue or engine.slots.num_active or engine._prefill_pending():
            loop.step()
    engine.completed.clear()
    return len(items)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    seconds: float
    compiles: List[str]
    lateness: List[float]
    served: List[Served]
    spans: List[Span]
    amax: List[int]
    transfer_bytes: List[int]
    prefill_chunks: int
    last_step: LastStep


def serve_window(
    engine, mix: Dict, gen: traffic_mod.Generator, seconds: float,
    counter: Optional[CompileCounter] = None, annotate: bool = False,
    on_open: Optional[Callable[[], None]] = None,
) -> Window:
    """Bring the traffic to its steady state, then measure ``seconds``."""
    loop = Loop(engine, mix, gen, annotate=annotate)
    if mix["loop"] == "closed":
        loop.start_closed(traffic_mod.clients(mix, engine.max_batch))
        while loop.queue or engine._prefill_pending():
            loop.step()
    else:
        lead = float(mix.get("lead_in_s", 0.0))
        loop.start_open(lead + seconds)
        while time.perf_counter() < loop.t_origin + lead:
            loop.step()
    if on_open is not None:
        on_open()
    last_step = LastStep(engine)
    n_amax, n_bytes = len(engine.amax_log), len(engine.transfer_bytes_log)
    chunks0 = engine.prefill_worker.chunks_done
    lateness0 = len(loop.lateness)
    t_open = time.perf_counter()
    t_close = t_open + seconds
    while time.perf_counter() < t_close:
        loop.step()
    t_end = time.perf_counter()
    # the check reads the KV rows of requests still being served; an open
    # loop can close on an idle engine, so then, outside the window, the
    # mix's next requests are served until one has a few tokens cached
    if not engine.slots.num_active:
        for _ in range(2):
            loop.send(gen.make(), time.perf_counter())
        while not any(len(engine.slots.slot_req[i].tokens_out) >= 4 for i in engine.slots.active_slots):
            loop.step()
    return Window(
        t_open=t_open, t_close=t_close, seconds=seconds,
        compiles=counter.between(t_open, t_end) if counter else [],
        lateness=loop.lateness[lateness0:],
        served=loop.served, spans=[s for s in loop.spans if s.t1 >= t_open],
        amax=list(engine.amax_log[n_amax:]),
        transfer_bytes=list(engine.transfer_bytes_log[n_bytes:]),
        prefill_chunks=engine.prefill_worker.chunks_done - chunks0,
        last_step=last_step,
    )


def read_kv(engine, slot: int) -> Dict[str, np.ndarray]:
    """Every paged cache array the timed path wrote for ``slot``, keyed by the
    engine's name for it (``kv_k``, ``kv_v``, and any other the configuration
    pages): ``[layers, rows, ...]`` float32 on the host, rows ``0 ..
    positions[slot]-1`` (the prompt and every served token but the newest)."""
    from repro.serving.disagg import _KV_KEYS
    from repro.serving.kv_cache import PAGED_KEYS

    n = int(engine.slots.positions[slot])
    if engine.disagg is not None:
        ex = engine.disagg
        si = next(i for i, sh in enumerate(ex.shards) if sh.lo <= slot < sh.hi)
        pages = np.asarray(ex._pagers[si].slot_pages(slot - ex.shards[si].lo))
        layers = ex._kv[si]  # the split keeps each layer's caches by short name
        pools = {key: [layer[short] for layer in layers] for short, key in _KV_KEYS.items() if short in layers[0]}
    else:
        pages = np.asarray(engine.paged.slot_pages(slot))
        pools = {key: engine.caches[key] for key in PAGED_KEYS if key in engine.caches}
    out = {}
    for key, pool in pools.items():
        layers = pool if isinstance(pool, list) else [pool[l] for l in range(pool.shape[0])]
        rows = [np.asarray(layer[pages], np.float32).reshape(-1, *layer.shape[2:])[:n] for layer in layers]
        out[key] = np.stack(rows)
    return out


def free_engine(engine) -> None:
    """Drop the engine's device state (weights, caches) before the reference."""
    engine.params = None
    engine.caches = None
    engine.prefill_worker._params = []
    engine.disagg = None
    gc.collect()


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, float), q))
