"""Whether the timed path served the right tokens and cached the right state.

Once the window has closed, two samples are drawn from the seed, each with
its longest request: four of the requests the program finished, and
sixteen of those still being served that the window's last decode step
served, whose KV rows the engine still holds.  The float32 reference (``reference.py``),
teacher-forced on each prompt and its served tokens, then gives the numbers
compared, each against the cell's limit in ``limits/<workload>.json``:

``widest_logit_gap``     over both samples and every served position, the
                         reference's best logit minus its logit of the token
                         the program served.  0 where every served token is
                         the reference's greedy choice.
``kv_rel_err``           over the requests still being served, every layer,
                         K and V: ||cached - reference|| / ||reference||,
                         where "cached" is what the program's prefill and
                         decode steps wrote into the paged cache.
``logits_rel_err``       the median over the requests still being served
                         of ||logits - reference|| / ||reference||: the
                         logits of the window's last decode step against
                         the reference's at the same position.  It reaches
                         the last layer, the final norm and the LM head,
                         which no cached row does.  The median, because
                         a position whose top-6 routing is a near-tie
                         reads 4-17 times the rest when bf16 picks the
                         other expert: ~15% of positions (PERF.md,
                         Findings).
``token_count_mismatches``  finished requests not served exactly the number
                         of tokens their client asked for.

The control (``reference.py``'s ``fp8``) is judged as if it stood in the
program's place: its own gap, K/V and logits against the same limits.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

from chipbench import reference

LIMIT_DIR = Path(__file__).resolve().parent / "limits"
SAMPLE = 4  # finished requests compared: the longest and three drawn from the seed
SAMPLE_ACTIVE = 16  # requests in flight compared
JUDGED = ("widest_logit_gap", "kv_rel_err", "logits_rel_err")  # per mode


def load_limits(workload: str) -> Dict:
    with open(LIMIT_DIR / f"{workload}.json") as fh:
        return json.load(fh)


def sample(requests: Sequence, seed: int, salt: int, k: int = SAMPLE) -> List:
    """The longest request (prompt plus served tokens) and ``k - 1`` others
    drawn from the seed."""
    if not requests:
        return []
    order = sorted(requests, key=lambda s: (len(s.item.prompt) + len(s.req.tokens_out), s.item.rid))
    longest, rest = order[-1], order[:-1]
    rng = np.random.default_rng([seed, salt])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(
    model: Dict, seed: int, finished: Sequence, chosen_finished: Sequence,
    chosen_active: Sequence, active_kv: Sequence, active_logits: Sequence, limits: Dict,
    modes=("f32",),
) -> Dict:
    """The numbers compared, each beside its limit, and a verdict for each
    mode: ``f32`` is the program, any other the control in its place.
    ``readings`` gives every mode's value of each judged number."""
    chosen = list(chosen_finished) + list(chosen_active)
    prompts = [s.item.prompt for s in chosen]
    served = [np.asarray(s.req.tokens_out, np.int32) for s in chosen]
    pad = [None] * len(chosen_finished)
    miscount = sum(int(len(s.req.tokens_out) != s.item.out_len) for s in finished)
    ref = reference.compare_served(
        model, seed, prompts, served, program_kv=pad + list(active_kv),
        program_logits=pad + list(active_logits), modes=modes,
    )
    nan = {mode: float("nan") for mode in modes}
    each = ref["logits_rel_err"]
    readings = {
        "widest_logit_gap": {mode: float(max(float(np.max(g)) for g in gs)) for mode, gs in ref["gaps"].items()},
        "kv_rel_err": ref["kv_rel_err"] or nan,
        "logits_rel_err": {mode: float(np.median(e)) for mode, e in each.items()} if each else nan,
    }
    sampled = len(chosen_finished) >= 1 and len(chosen_active) >= 1
    # a NaN reading (nothing to compare) fails its ``<=``
    verdicts = {
        mode: bool(sampled and miscount == 0
                   and all(readings[n][mode] <= limits[n]["limit"] for n in JUDGED))
        for mode in modes
    }
    compared = {
        "requests_compared": {"value": len(chosen), "limit": ">= 2"},
        "served_tokens_compared": {"value": int(sum(len(t) for t in served)), "limit": "> 0"},
        "token_count_mismatches": {"value": miscount, "limit": 0},
        **{n: {"value": readings[n]["f32"], "limit": limits[n]["limit"]} for n in JUDGED},
    }
    return {"correct": verdicts["f32"], "compared": compared, "readings": readings, "verdicts": verdicts,
            "logits_rel_err_each": each, "router_margin": ref["router_margin"]}
