"""Set-up's program loads: the engine's step records marked
``first_use`` (the first dispatch of a kind with its static shape key,
which traced, compiled or loaded a program) that ended before the
counted interval. ``value`` is ``count`` or ``seconds`` (their summed
host durations). Read from the live flight recorders
(``telemetry.live()``), which keep a run's warm-up steps; a program
without them gives no value."""


def read(run, args):
    try:
        from copilot_for_consensus_tpu.engine import telemetry
        teles = telemetry.live()
    except (ImportError, AttributeError):
        return None
    t0 = run["window"][0]
    firsts = [r for t in teles for r in t.recorder.records()
              if getattr(r, "first_use", False) and r.t_end < t0]
    if not firsts:
        return None
    if args["value"] == "count":
        return float(len(firsts))
    if args["value"] == "seconds":
        return sum(r.duration_s for r in firsts)
    raise ValueError(f"unknown value {args['value']!r}")
