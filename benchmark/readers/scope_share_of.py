"""Share of one program's device self time under a scope that the
program declares apart from ``SCOPES``, in percent: ``scope_share``
with the scope table reduced once more over ``SCOPES`` plus the tuple
of ``obs/profile.py`` that ``declared`` names (``EVA_SCOPES``: what
only the attention="eva" programs hold). In the accepted table such a
scope's time lies under ``_unscoped_`` or the scope around it. No
trace, a program that declares no such tuple, or a program that did
not run in the traced slice: no value."""
from benchmark.harness import scope_reduce


def read(run, args):
    planes = (run.get("trace") or {}).get("device_planes")
    scopes = scope_reduce.program_scopes()
    try:
        from copilot_for_consensus_tpu.obs import profile
        more = getattr(profile, args["declared"])
    except (ImportError, AttributeError):
        return None
    if not planes or scopes is None:
        return None
    prog = scope_reduce.reduce_planes(
        planes, tuple(scopes) + tuple(more)).get(args["module"])
    total = sum(prog["self_s"].values()) if prog else 0.0
    if total <= 0:
        return None
    return 100.0 * prog["self_s"].get(args["scope"], 0.0) / total
