"""Where a counted request's time after its first token went: over the
counted requests, the sum of one ``part`` of the program's own
accounting (``stalled``: other requests' admission dispatches it sat
through; ``host``: what no dispatch covers) over the sum of finish
minus first token, in percent. The parts are on the engine's completed
``RequestTrace``s (``telemetry.live()``), joined to the benchmark's
records through ``engine_requests`` (same first-token and finish
times, then ``rid``). No value when any counted request's trace is
missing or carries no such part."""
from benchmark.harness import stats

FIELDS = {"stalled": "stalled_s", "host": "host_s"}


def read(run, args):
    try:
        from copilot_for_consensus_tpu.engine import telemetry
        teles = telemetry.live()
    except (ImportError, AttributeError):
        return None
    traces = {tr.request_id: tr for t in teles for tr in t.completed}
    by_times = {(r["first_token_at"], r["finished_at"]): r
              for r in run["records"]["engine_requests"]}
    field = FIELDS[args["part"]]
    part = total = 0.0
    rows = stats.counted(run["records"]["requests"], run["window"])
    for r in rows:
        eng = by_times.get((r["first_token_at"], r["finished_at"]))
        tr = traces.get(eng["rid"]) if eng else None
        if tr is None or tr.correlation_id != eng["correlation_id"] \
                or not hasattr(tr, field):
            return None
        part += getattr(tr, field)
        total += tr.finished_at - tr.first_token_at
    return 100.0 * part / total if total > 0 else None
