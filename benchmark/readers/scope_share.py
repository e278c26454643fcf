"""Share of one program's device self time spent under the named
scope(s) (``harness/scope_reduce.py``), in percent. ``scope`` is one
name or a list whose shares add; ``_unscoped_`` is what falls under no
name. Nothing to read (no trace, a program without scopes, a program
that did not run in the traced slice): no value."""
from benchmark.harness import scope_reduce


def read(run, args):
    table = scope_reduce.for_run(run)
    prog = (table or {}).get(args["module"])
    if not prog:
        return None
    total = sum(prog["self_s"].values())
    if total <= 0:
        return None
    names = args["scope"]
    if isinstance(names, str):
        names = [names]
    return 100.0 * sum(prog["self_s"].get(n, 0.0) for n in names) / total
