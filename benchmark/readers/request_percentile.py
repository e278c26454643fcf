"""A percentile over the counted requests: ``value`` is ``ttft_s``,
``tpot_ms``, ``queue_wait_s`` (admission start minus DUE time) or
``late_ms`` (send time minus due time)."""
from benchmark.harness import stats


def read(run, args):
    reqs, window = run["records"]["requests"], run["window"]
    what = args["value"]
    if what == "ttft_s":
        vals = stats.ttft_values(reqs, window)
    elif what == "tpot_ms":
        vals = stats.tpot_values_ms(reqs, window)
    elif what == "queue_wait_s":
        vals = [max(0.0, r["admitted_at"] - r["due"])
                for r in stats.counted(reqs, window) if r["admitted_at"]]
    elif what == "late_ms":
        vals = [1e3 * (r["sent"] - r["due"])
                for r in stats.counted(reqs, window)]
    else:
        raise ValueError(f"unknown value {what!r}")
    return stats.percentile(vals, args["p"]) if vals else None
