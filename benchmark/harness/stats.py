"""Metric arithmetic on plain records. No JAX, no program imports.

Every end-to-end number the benchmark prints is computed here from
timestamps, so that none moves in steps of one completion.
"""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """The one interpolation rule of this benchmark: linear between
    order statistics at rank ``p * (n - 1)`` (numpy's default,
    ``statistics.quantiles(method="inclusive")``). On 100 values the
    90th percentile lies between the 90th and 91st smallest."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = p * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def counted(requests, window):
    """Requests DUE inside the counted interval [t0, t1). A request
    that finishes after t1 still counts: the run waits for it."""
    t0, t1 = window
    return [r for r in requests if r["phase"] == "counted"
            and t0 <= r["due"] < t1]


def ttft_values(requests, window) -> list[float]:
    """first token minus DUE time per counted request. A failed or
    refused request takes the largest value seen (or the drain limit
    when none succeeded), so it always sits in the tail."""
    rows = counted(requests, window)
    good = [r["first_token_at"] - r["due"] for r in rows
            if r.get("ok") and r.get("first_token_at")]
    worst = max(good) if good else float("inf")
    return good + [worst] * (len(rows) - len(good))


def tpot_values_ms(requests, window) -> list[float]:
    """(finish - first token) / (new tokens - 1) per counted request,
    in milliseconds; requests of one token have no gap and are left
    out, failed ones take the largest value."""
    rows = counted(requests, window)
    good, bad = [], 0
    for r in rows:
        if not (r.get("ok") and r.get("first_token_at")):
            bad += 1
        elif r["new_tokens"] > 1:
            good.append(1e3 * (r["finished_at"] - r["first_token_at"])
                        / (r["new_tokens"] - 1))
    worst = max(good) if good else float("inf")
    return good + [worst] * bad


def e2e_values(requests, window) -> list[float]:
    """finish minus DUE time per counted request: the whole wait of a
    reader, first token and pace together. A failed request takes the
    largest value."""
    rows = counted(requests, window)
    good = [r["finished_at"] - r["due"] for r in rows
            if r.get("ok") and r.get("finished_at")]
    worst = max(good) if good else float("inf")
    return good + [worst] * (len(rows) - len(good))


def out_tok_s(steps, window) -> float | None:
    """Tokens handed to requests by the steps whose host fetch ended
    inside the counted interval, the first such step left out, over the
    time from that first fetch to the last. Continuous in every
    timestamp. The count is the program's own, ``new_tokens`` of each
    step record, so a dispatch of any kind is counted for what it
    delivered; a record without it is an error, not a zero."""
    t0, t1 = window
    inside = sorted((s for s in steps if t0 <= s["t_end"] < t1),
                    key=lambda s: s["t_end"])
    if len(inside) < 2:
        return None
    span = inside[-1]["t_end"] - inside[0]["t_end"]
    if span <= 0:
        return None
    return sum(int(s["new_tokens"]) for s in inside[1:]) / span


def spread(values) -> float:
    """Interquartile distance as a share of the median, by
    ``statistics.quantiles(values, n=4)`` — the driver's rule."""
    import statistics

    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("inf")
