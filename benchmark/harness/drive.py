"""The one general load loop: open (arrivals on a schedule, whatever
the system does) or closed (keep a fixed amount of work outstanding).
Load comes from this one thread; the system reports completions through
its own records."""

from __future__ import annotations

import time


def drive(system, plan: dict, log) -> dict:
    """Offers the plan's load. Returns the counted interval on the
    monotonic clock and how late each arrival was sent."""
    items = plan["items"]
    start = time.monotonic()
    w0, w1 = plan["window"]
    window = (start + w0, start + w1)
    if plan["mode"] == "open":
        return _open(system, plan, items, start, window, log)
    return _closed(system, plan, items, start, window)


def _open(system, plan, items, start, window, log) -> dict:
    late = []
    counted_ids = [i for i, it in enumerate(items)
                   if it["phase"] == "counted"]
    drain_deadline = window[1] + plan["drain_limit_s"]
    for i, item in enumerate(items):
        due = start + item["due"]
        if item["phase"] == "drain" and (
                system.all_done(counted_ids)
                or time.monotonic() > drain_deadline):
            break
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        sent = time.monotonic()
        system.submit(i, item, due)
        if item["phase"] == "counted":
            late.append(sent - due)
    # every counted request finishes, or fails at the drain limit
    while (not system.all_done(counted_ids)
           and time.monotonic() < drain_deadline):
        time.sleep(0.02)
    undone = [i for i in counted_ids if not system.is_done(i)]
    if undone:
        log(f"drain limit reached with {len(undone)} counted requests "
            f"unfinished: they count as failed")
    return {"window": window, "late_s": late, "end": time.monotonic()}


def _closed(system, plan, items, start, window) -> dict:
    nxt = 0
    while time.monotonic() < window[1]:
        while nxt < len(items) and system.wants_more(plan):
            system.submit(nxt, items[nxt], None)
            nxt += 1
        system.wait_progress(0.01)
    return {"window": window, "late_s": [], "end": time.monotonic(),
            "submitted": nxt}
