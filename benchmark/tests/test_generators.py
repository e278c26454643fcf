"""The traffic generators are pure functions of (file, seed, seconds):
the count, the multiset of lengths and the multiset of gaps are the
same for every seed, two calls with one seed agree, and the order
differs between seeds in the open loop and not in the closed one."""
import collections
import json
import pathlib

import pytest

from benchmark.generators import (
    closed_shapes,
    open_stratified,
)

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
SEEDS = (1, 2, 2_900_000_011)


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def counted(plan):
    return [i for i in plan["items"] if i["phase"] == "counted"]


def gaps(items):
    dues = [0.0] + [i["due"] for i in items]
    return sorted(round(b - a, 9) for a, b in zip(dues, dues[1:]))


def test_open_same_count_lengths_and_gaps_for_every_seed():
    t = load("qa-steady")
    plans = [open_stratified.plan(t, s, 51.0) for s in SEEDS]
    shape = lambda i: (i["prompt_len"], i["new_tokens"])  # noqa: E731

    def counted_gaps(p):
        dues = [i["due"] for i in p["items"] if i["phase"] != "drain"]
        n = len(counted(p))
        return sorted(round(b - a, 9)
                      for a, b in zip(dues[-n - 1:], dues[-n:]))

    ref = plans[0]
    for p in plans[1:]:
        for phase in ("lead_in", "counted", "drain"):
            a = [i for i in ref["items"] if i["phase"] == phase]
            b = [i for i in p["items"] if i["phase"] == phase]
            assert len(a) == len(b) > 0
        assert collections.Counter(map(shape, counted(ref))) == \
            collections.Counter(map(shape, counted(p)))
        assert counted_gaps(p) == pytest.approx(counted_gaps(ref), abs=1e-6)
        # the counted interval is the whole cycle: one length for all
        assert p["window"][1] - p["window"][0] == pytest.approx(
            ref["window"][1] - ref["window"][0], abs=1e-6)


def test_open_every_request_keeps_its_neighbours():
    """Another seed is another rotation of the one cycle."""
    t = load("qa-steady")
    a = [(i["prompt_len"], i["new_tokens"])
         for i in counted(open_stratified.plan(t, 1, 51.0))]
    b = [(i["prompt_len"], i["new_tokens"])
         for i in counted(open_stratified.plan(t, 2, 51.0))]
    assert a != b
    k = b.index(a[0])
    assert b[k:] + b[:k] == a


def test_open_count_is_rate_times_interval_and_order_differs():
    t = load("qa-steady")
    p1, p2 = (open_stratified.plan(t, s, 51.0) for s in SEEDS[:2])
    n = len(counted(p1))
    assert n == round(t["rate_per_s"] * 51.0 * (1 - t["lead_in_share"]))
    assert [i["prompt_len"] for i in counted(p1)] != \
        [i["prompt_len"] for i in counted(p2)]
    dues = [i["due"] for i in p1["items"]]
    assert dues == sorted(dues)
    w0, w1 = p1["window"]
    assert all(w0 <= i["due"] < w1 for i in counted(p1))


def test_open_two_calls_with_one_seed_agree():
    t = load("qa-steady")
    assert open_stratified.plan(t, 7, 51.0) == \
        open_stratified.plan(t, 7, 51.0)


def test_open_long_prompts_do_not_all_get_long_answers():
    t = load("qa-steady")
    items = counted(open_stratified.plan(t, 3, 51.0))
    top = sorted(items, key=lambda i: -i["prompt_len"])[:len(items) // 4]
    mean_all = sum(i["new_tokens"] for i in items) / len(items)
    mean_top = sum(i["new_tokens"] for i in top) / len(top)
    assert 0.6 * mean_all < mean_top < 1.4 * mean_all


def test_closed_every_cycle_is_the_same_multiset():
    t = load("summarize-backlog")
    n = t["shapes"]
    ring = closed_shapes.shapes(t)
    base = collections.Counter((s["prompt_len"], s["new_tokens"])
                               for s in ring)
    assert sum(1 for s in ring if s["prompt_len"] == t["prompt_max"]) \
        >= n // 2
    orders = []
    for seed in SEEDS:
        items = closed_shapes.plan(t, seed, 51.0)["items"]
        assert len(items) % n == 0 and len(items) >= 2 * n
        for c in range(0, len(items), n):
            cyc = collections.Counter(
                (i["prompt_len"], i["new_tokens"])
                for i in items[c:c + n])
            assert cyc == base
        orders.append([(i["prompt_len"], i["new_tokens"])
                       for i in items[:n]])
    # every seed offers the cycle from its head: the seed is not the work
    assert orders[0] == orders[1] == orders[2] == [
        (s["prompt_len"], s["new_tokens"]) for s in ring]
    assert closed_shapes.plan(t, 5, 51.0) == closed_shapes.plan(t, 5, 51.0)
