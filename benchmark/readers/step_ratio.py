"""Ratios of step records in the counted interval: ``decode_occupancy``
(live rows over slots, decode dispatches) and ``prefill_padding_waste``
(padded minus real prompt tokens, over padded), in percent."""
from benchmark.readers import _select


def read(run, args):
    if args["ratio"] == "decode_occupancy":
        steps = _select.decode_steps(run)
        den = sum(s["batch"] for s in steps)
        return 100.0 * sum(s["rows"] for s in steps) / den if den else None
    steps = _select.prefill_steps(run)
    den = sum(s["padded_tokens"] for s in steps)
    return (100.0 * (den - sum(s["tokens"] for s in steps)) / den
            if den else None)
