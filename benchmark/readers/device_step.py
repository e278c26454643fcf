"""Device time of one decode step: the device time of the decode
program in each traced dispatch over the steps it runs."""
from benchmark.readers import _select


def read(run, args):
    rows = _select.traced_steps(run, args["step"], args["module"])
    if not rows:
        return None
    per = run["records"]["engine"]["steps_per_dispatch"]
    return 1e3 * sum(d for _s, d in rows) / (len(rows) * per)
