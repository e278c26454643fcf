"""One count the program writes on its step records over another, both
summed over the dispatches that ended in the counted interval (of
``kind``, where given): ``num`` over ``den``, times ``scale``. ``den``
``"decode_steps"`` counts the decode steps those dispatches made.
Records without the field (a program that does not count it), or a
denominator of zero: no value."""
from benchmark.readers import _select


def read(run, args):
    steps = [s for s in _select.in_window(run["records"]["steps"],
                                          run["window"])
             if args["num"] in s
             and (not args.get("kind") or s["kind"] == args["kind"])]
    if args["den"] == "decode_steps":
        den = run["records"]["engine"]["steps_per_dispatch"] * sum(
            1 for s in steps if s["kind"] == "decode")
    else:
        den = sum(s[args["den"]] for s in steps)
    if not den:
        return None
    return args.get("scale", 1.0) * sum(s[args["num"]] for s in steps) / den
