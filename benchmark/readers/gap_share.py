"""Share of the traced window in which the device sat idle while the
host was in the named phases: the seconds of ``trace["idle_gaps"]``
owned by ``owners`` (or by every owner but ``except``) over
``trace["window_s"]``, in percent. A gap's owner is the program's host
span that holds its midpoint (``harness/trace_reduce.py``); a program
that emits no host phases (``obs/profile.py:HOST_PHASES``) gives no
value."""


def read(run, args):
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    try:
        from copilot_for_consensus_tpu.obs.profile import HOST_PHASES
    except ImportError:
        return None
    gaps = dict(trace["idle_gaps"])
    if not set(gaps) & set(HOST_PHASES):
        return None
    if "owners" in args:
        mine = [gaps.get(o, 0.0) for o in args["owners"]]
    else:
        mine = [v for o, v in gaps.items() if o not in args["except"]]
    return 100.0 * sum(mine) / trace["window_s"]
