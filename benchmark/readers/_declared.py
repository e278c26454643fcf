"""A program's device self time by scope, with the scope table reduced
over ``SCOPES`` plus a tuple that the program declares apart from it
(``obs/profile.py``: ``XING_SCOPES``), for the readers that name such a
tuple in their ``declared`` argument."""
from benchmark.harness import scope_reduce


def self_seconds(run, module: str, declared: str) -> dict | None:
    """{scope: seconds} of ``module`` over the traced slice, or None: no
    trace, a program that declares no such tuple, or a program that did
    not run in the slice."""
    planes = (run.get("trace") or {}).get("device_planes")
    scopes = scope_reduce.program_scopes()
    try:
        from copilot_for_consensus_tpu.obs import profile
        more = tuple(getattr(profile, declared))
    except (ImportError, AttributeError):
        return None
    if not planes or scopes is None:
        return None
    prog = scope_reduce.reduce_planes(
        planes, tuple(scopes) + more).get(module)
    return prog["self_s"] if prog else None
