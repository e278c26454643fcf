"""Set-up's program loads: the engine's step records marked
``first_use`` (the first dispatch of a kind with its static shape key,
which traced, compiled or loaded a program) that ended before the
counted interval. ``value`` is ``count`` or ``seconds`` (their summed
host durations). The builder's tap keeps a run's warm-up steps; a run
in which none was a first use gives no value."""


def read(run, args):
    t0 = run["window"][0]
    firsts = [s for s in run["records"]["steps"]
              if s["first_use"] and s["t_end"] < t0]
    if not firsts:
        return None
    if args["value"] == "count":
        return float(len(firsts))
    if args["value"] == "seconds":
        return sum(s["duration_s"] for s in firsts)
    raise ValueError(f"unknown value {args['value']!r}")
