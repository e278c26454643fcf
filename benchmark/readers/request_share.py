"""Where a counted request's time after its first token went: over the
counted requests, the sum of one ``part`` of the program's own
accounting (``stalled``: other requests' admission dispatches it sat
through; ``host``: what no dispatch covers) over the sum of finish
minus first token, in percent. The parts are on the engine's completed
``RequestTrace``s, which the builder copies onto its request records.
No value when any counted request has no trace."""
from benchmark.harness import stats

FIELDS = {"stalled": "stalled_s", "host": "host_s"}


def read(run, args):
    field = FIELDS[args["part"]]
    rows = stats.counted(run["records"]["requests"], run["window"])
    if any(r[field] is None for r in rows):
        return None
    total = sum(r["finished_at"] - r["first_token_at"] for r in rows)
    return 100.0 * sum(r[field] for r in rows) / total if total > 0 \
        else None
