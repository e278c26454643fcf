"""Share of the HBM roofline that a decode step of a latent-attention
engine with sparse experts reaches, in percent: the bytes it has to
read (``harness/xing_roofline.py``: the matrices outside the experts
once a step, the experts that the program counted as touched, the
latent rows of the decoding sequences: ``window_tokens`` at the
dispatch's start, growing by one a step) over the chip's bandwidth and
a device time. That time is the decode program's in each traced
dispatch or, with ``scope``, the program's device self time under that
scope over the traced slice (``part`` then names the bytes that scope
reads: ``latents`` for attention, ``experts`` for the grouped
matmuls). Step records without the counts (a program that has no such
state), no trace or no such scope: no value."""
from benchmark.harness import roofline, xing_roofline
from benchmark.readers import _declared, _select


def read(run, args):
    rows = [(s, d) for s, d in _select.traced_steps(
        run, args["step"], args["module"]) if "experts_touched" in s]
    if not rows:
        return None
    per = run["records"]["engine"]["steps_per_dispatch"]
    need = sum(xing_roofline.decode_bytes(
        run["dims"], per, s["experts_touched"],
        per * s["window_tokens"] + s["rows"] * per * (per - 1) / 2,
        args["state_bytes"], args.get("part", "all"))
        for s, _d in rows)
    seconds = sum(d for _s, d in rows)
    if "scope" in args:
        self_s = _declared.self_seconds(run, args["module"],
                                        args["declared"])
        seconds = (self_s or {}).get(args["scope"])
        if not seconds:
            return None
    peak = roofline.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / seconds
