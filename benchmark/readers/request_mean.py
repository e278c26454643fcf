"""A mean over the counted requests: ``value`` is ``e2e_s`` (finish
minus DUE time) or ``ttft_s`` (first token minus due time)."""
from benchmark.harness import stats


def read(run, args):
    reqs, window = run["records"]["requests"], run["window"]
    what = args["value"]
    if what == "e2e_s":
        vals = stats.e2e_values(reqs, window)
    elif what == "ttft_s":
        vals = stats.ttft_values(reqs, window)
    else:
        raise ValueError(f"unknown value {what!r}")
    return sum(vals) / len(vals) if vals else None
