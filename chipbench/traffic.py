"""One general generator for every traffic mix in ``chipbench/traffic/``.

A mix is a JSON file of parameters:

``loop``            ``"closed"`` (each client sends its next request as soon
                    as the previous one completes) or ``"open"`` (requests
                    are due on a schedule whatever the system does).
``clients``         closed loop: a number, or ``"max_batch"`` for one client
                    per batch slot of the configuration.
``arrivals``        open loop: ``{"process": "poisson", "rate_per_s": r}``.
``lead_in_s``       open loop: seconds of arrivals before the window opens.
``prompt_len``, ``output_len``, ``first_output_len`` (closed loop, each
                    client's first request): a length distribution,
                    ``{"dist": "uniform"|"loguniform", "low", "high"}`` or
                    ``{"dist": "lognormal", "median", "sigma", "low", "high"}``.
                    ``output_len`` counts every token served, the first one
                    (from prefill) included.
``tokens``          ``"uniform"``: prompt ids uniform over the vocabulary.

Every seed gets the same work: lengths and inter-arrival gaps come in
blocks of ``BLOCK`` requests, each block the distribution's quantiles at
``(j + 0.5) / BLOCK`` in an order drawn once from ``LAYOUT_SEED``, the same
for every seed.  A window holds a few tens of requests, and any reordering
moves which lengths it sends and which of them meet at one admission, so
the seed draws only what does not change the work: the prompts' token ids
(and, elsewhere, the weights).  The open loop's arrivals are a stratified
Poisson process.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
POOL = 4096  # requests a generator prepares; cycled if a run needs more
BLOCK = 16  # requests per stratified block
LAYOUT_SEED = 7  # orders the blocks; the same for every --seed


def load(name: str) -> Dict:
    with open(TRAFFIC_DIR / f"{name}.json") as fh:
        return json.load(fh)


def _quantiles(spec: Dict, u: np.ndarray) -> np.ndarray:
    lo, hi = spec["low"], spec["high"]
    dist = spec["dist"]
    if dist == "uniform":
        x = lo + u * (hi + 1 - lo)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if dist == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if dist == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def _blocks(values: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` values: ``values`` (one block) repeated, each block permuted."""
    reps = -(-n // len(values))
    return np.concatenate([values[rng.permutation(len(values))] for _ in range(reps)])[:n]


def stratified(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths of ``spec``: blocks of stratified quantiles, each block
    in an order drawn from ``rng``."""
    return _blocks(_quantiles(spec, (np.arange(BLOCK) + 0.5) / BLOCK), n, rng)


def max_len(spec: Dict) -> int:
    return int(spec["high"])


@dataclasses.dataclass
class Item:
    """One request the generator makes: its prompt and how many tokens the
    client wants served.  ``due`` is set by the loop that sends it."""

    rid: int
    prompt: np.ndarray
    out_len: int
    client: int = -1
    due: float = 0.0


class Generator:
    """Makes the requests of one mix for one seed, in order."""

    def __init__(self, mix: Dict, vocab_size: int, seed: int):
        self.mix = mix
        self.vocab = vocab_size
        self.rng = np.random.default_rng([seed, 7])  # token ids
        self.layout = np.random.default_rng(LAYOUT_SEED)  # lengths and gaps
        self.prompts = stratified(mix["prompt_len"], POOL, self.layout)
        self.outs = stratified(mix["output_len"], POOL, self.layout)
        first = mix.get("first_output_len")
        self.firsts = stratified(first, POOL, self.layout) if first else None
        self.next_rid = 0

    def make(self, first: bool = False) -> Item:
        i = self.next_rid
        n = int(self.prompts[i % POOL])
        out = int((self.firsts if first and self.firsts is not None else self.outs)[i % POOL])
        prompt = self.rng.integers(0, self.vocab, size=n, dtype=np.int32)
        self.next_rid += 1
        return Item(rid=i, prompt=prompt, out_len=out)

    def gaps(self, n: int) -> np.ndarray:
        """``n`` inter-arrival gaps of the open-loop process (seconds)."""
        arr = self.mix["arrivals"]
        rate = float(arr["rate_per_s"])
        u = (np.arange(BLOCK) + 0.5) / BLOCK
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        return _blocks(-np.log1p(-u) / rate, n, self.layout)


def open_schedule(gen: Generator, horizon_s: float) -> List[Item]:
    """Open loop: requests due at the cumulative sums of the stratified gaps,
    up to ``horizon_s``."""
    rate = float(gen.mix["arrivals"]["rate_per_s"])
    n = int(math.ceil(rate * horizon_s * 1.5)) + BLOCK
    items = []
    for due in np.cumsum(gen.gaps(n)):
        if due >= horizon_s:
            break
        it = gen.make()
        it.due = float(due)
        items.append(it)
    return items


def clients(mix: Dict, max_batch: int) -> Optional[int]:
    if mix["loop"] != "closed":
        return None
    c = mix["clients"]
    return max_batch if c == "max_batch" else int(c)
