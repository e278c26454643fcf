"""Closed loop over ONE fixed cycle of request shapes: the queue is kept
non-empty, and the cycle's order is dealt once in stratified blocks from
``pair_key``. Every run starts at the head of the cycle: ``--seed``
decides weights and token ids (in the builder) and nothing of the
sizes or their order. (Dealt from the seed, six seeds spread
`out_tok_s` by 2.0% where one seed repeated to 0.2%: my chip runs,
PR 25. Until PR 28 the seed picked where in the cycle a run started;
once a window held 1.3 cycles instead of 1.1 that too was the work:
six seeds ranged 2.7% where one seed repeated to 0.01-0.4%, and the
driver's check read 2.5%: PERF.md section 6, PR 28.)"""

from __future__ import annotations

import random

from benchmark.generators._common import (
    deal_stratified,
    fixed_permutation,
    log_spaced,
)


def shapes(t: dict) -> list[dict]:
    """The cycle, in its fixed order."""
    n = int(t["shapes"])
    n_full = int(round(n * float(t["full_share"])))
    prompts = [int(t["prompt_max"])] * n_full + log_spaced(
        t["prompt_min"], t["prompt_max"], n - n_full)
    answers = log_spaced(t["new_min"], t["new_max"], n)
    perm = fixed_permutation(n, int(t["pair_key"]))
    base = [{"prompt_len": prompts[i], "new_tokens": answers[perm[i]],
             "_key": (prompts[i], answers[perm[i]])} for i in range(n)]
    return deal_stratified(base, int(t["block"]),
                           random.Random(int(t["pair_key"])))


def plan(traffic: dict, seed: int, seconds: float) -> dict:
    t = traffic
    ring = shapes(t)
    need = int(seconds * float(t["max_requests_per_s"])) + len(ring)
    need = -(-need // len(ring)) * len(ring)        # whole cycles
    items = [{"phase": "closed", "due": None,
              "prompt_len": ring[k % len(ring)]["prompt_len"],
              "new_tokens": ring[k % len(ring)]["new_tokens"]}
             for k in range(need)]
    lead_s = float(t["lead_in_share"]) * seconds
    return {"mode": "closed", "items": items,
            "outstanding_per_slot": float(t["outstanding_per_slot"]),
            "window": (lead_s, seconds)}
