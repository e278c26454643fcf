"""``scope_share_of`` for a scope or a list of scopes whose shares add
(the router's and the grouped matmuls' together are the expert layer):
share of one program's device self time under scopes that the program
declares apart from ``SCOPES``, in percent, with the scope table
reduced once more over ``SCOPES`` plus the tuple of ``obs/profile.py``
that ``declared`` names. No trace, a program that declares no such
tuple, or a program that did not run in the traced slice: no value."""
from benchmark.readers import _declared


def read(run, args):
    self_s = _declared.self_seconds(run, args["module"], args["declared"])
    total = sum(self_s.values()) if self_s else 0.0
    if total <= 0:
        return None
    names = args["scope"]
    if isinstance(names, str):
        names = [names]
    return 100.0 * sum(self_s.get(n, 0.0) for n in names) / total
