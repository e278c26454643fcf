"""Open-loop arrivals at a fixed rate over ONE fixed cycle of requests.

The traffic file fixes everything that shapes the queue: the count, the
multiset of (prompt, answer) lengths, the multiset of gaps (the
mid-quantiles of an exponential at the rate) and the order of the
cycle, dealt once in stratified blocks from ``pair_key``. ``--seed``
decides where in the cycle the counted interval begins (and, in the
builder, weights and token ids), nothing else: the lead-in is the
stretch of the cycle before that point, the counted interval is the
whole cycle once, the drain goes round again. Every seed therefore
offers the same arrivals with the same neighbours, in another order.
(PR 25's first sets dealt the order itself from the seed: one seed
repeated to 1%, six seeds spread `ttft_p90_s` by 35%.)"""

from __future__ import annotations

import random

from benchmark.generators._common import (
    deal_stratified,
    exp_quantile_gaps,
    fixed_permutation,
    log_spaced,
)


def cycle(t: dict, n: int) -> list[dict]:
    """The fixed cycle of n requests: shape and the gap before it."""
    prompts = log_spaced(t["prompt_min"], t["prompt_max"], n)
    answers = log_spaced(t["new_min"], t["new_max"], n)
    perm = fixed_permutation(n, int(t["pair_key"]))
    shapes = [{"prompt_len": prompts[i], "new_tokens": answers[perm[i]],
               "_key": prompts[i]} for i in range(n)]
    fixed = random.Random(int(t["pair_key"]))
    shapes = deal_stratified(shapes, int(t["block"]), fixed)
    gaps = deal_stratified(exp_quantile_gaps(float(t["rate_per_s"]), n),
                           int(t["block"]), fixed)
    return [{"prompt_len": s["prompt_len"], "new_tokens": s["new_tokens"],
             "gap": g} for s, g in zip(shapes, gaps)]


def plan(traffic: dict, seed: int, seconds: float) -> dict:
    t = traffic
    rate = float(t["rate_per_s"])
    lead_s = float(t["lead_in_share"]) * seconds
    n_lead = max(1, round(rate * lead_s))
    n = max(1, round(rate * (seconds - lead_s)))
    n_drain = max(1, round(rate * float(t["drain_limit_s"])))
    ring = cycle(t, n)
    offset = random.Random(seed).randrange(n)
    items, due, window = [], 0.0, [0.0, 0.0]
    for k in range(-n_lead, n + n_drain):
        c = ring[(offset + k) % n]
        phase = "lead_in" if k < 0 else "counted" if k < n else "drain"
        if k == 0:
            window[0] = due + 1e-9
        if k == n:
            window[1] = due + 1e-9
        due += c["gap"]
        items.append({"phase": phase, "due": due,
                      "prompt_len": c["prompt_len"],
                      "new_tokens": c["new_tokens"]})
    return {"mode": "open", "items": items, "window": tuple(window),
            "drain_limit_s": float(t["drain_limit_s"])}
