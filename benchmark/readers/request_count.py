"""How many requests were due inside the counted interval."""
from benchmark.harness import stats


def read(run, args):
    n = len(stats.counted(run["records"]["requests"], run["window"]))
    return float(n) if n else None
