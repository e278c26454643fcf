"""Selections shared by readers: what lies in the counted interval and
what lies in the traced slice of it."""

from __future__ import annotations


def in_window(rows, window, key="t_end"):
    t0, t1 = window
    return [r for r in rows if t0 <= r[key] < t1]


def decode_steps(run):
    return [s for s in in_window(run["records"]["steps"], run["window"])
            if s["kind"] == "decode"]


def prefill_steps(run):
    return [s for s in in_window(run["records"]["steps"], run["window"])
            if s["kind"].startswith("prefill")]


def traced_steps(run, name: str, module: str):
    """(step record, device seconds of ``module``) for every annotated
    step of that name in the trace whose record the run still has."""
    trace = run.get("trace")
    if not trace:
        return []
    by_seq = {s["seq"]: s for s in run["records"]["steps"]}
    out = []
    for st in trace["steps"]:
        if st["name"] != name or st["step_num"] not in by_seq:
            continue
        dev = sum(d for n, d in st["modules"] if n == module)
        if dev > 0:
            out.append((by_seq[st["step_num"]], dev))
    return out
