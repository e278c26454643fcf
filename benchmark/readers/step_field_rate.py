"""A count the program writes on its step records, per second of the
counted interval: the sum of ``field`` over the dispatches that ended
in it, over its length. Records without the field (a program that does
not count it): no value."""
from benchmark.readers import _select


def read(run, args):
    steps = [s for s in _select.in_window(run["records"]["steps"],
                                          run["window"])
             if args["field"] in s]
    if not steps:
        return None
    t0, t1 = run["window"]
    return sum(s[args["field"]] for s in steps) / (t1 - t0)
