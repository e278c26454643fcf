"""Share of the HBM roofline that a decode step of an EVA-attention
engine reaches, in percent: the bytes it has to read
(``harness/eva_roofline.py``: the weights once, unless ``weight_bytes``
is left out, and the state that is live, which the program counts
itself on every step record as ``window_tokens`` + ``summary_tokens``
at the dispatch's start) over the chip's bandwidth and a device time.
That time is the decode program's in each traced dispatch or, with
``scope``, the program's device self time under that scope over the
traced slice (the attention alone against its own bytes). Step records
without the counts (a program that has no such state), no trace or no
such scope: no value."""
from benchmark.harness import eva_roofline, roofline, scope_reduce
from benchmark.readers import _select


def read(run, args):
    rows = [(s, d) for s, d in _select.traced_steps(
        run, args["step"], args["module"]) if "window_tokens" in s]
    if not rows:
        return None
    per = run["records"]["engine"]["steps_per_dispatch"]
    need = sum(per * eva_roofline.decode_bytes(
        run["dims"], s["window_tokens"] + s["summary_tokens"],
        args.get("weight_bytes", 0.0), args["state_bytes"])
        for s, _d in rows)
    seconds = sum(d for _s, d in rows)
    if "scope" in args:
        prog = (scope_reduce.for_run(run) or {}).get(args["module"])
        seconds = (prog or {"self_s": {}})["self_s"].get(args["scope"])
        if not seconds:
            return None
    peak = roofline.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / seconds
