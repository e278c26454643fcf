"""Backend compilations that ended inside the counted interval."""

def read(run, args):
    t0, t1 = run["window"]
    return float(sum(1 for t in run["compile_times"] if t0 <= t < t1))
