"""Share of the HBM roofline that a decode step of a mixed-layer engine
(window and global layers, each kind with a cache of its own) reaches,
in percent: the bytes it has to read (``harness/cmda_roofline.py``: the
matrices outside the routed experts once a step, the held experts the
program counted as touched, every live position's keys and values in
the full layers, ``live_tokens``, and at most a window's in the window
layers, ``window_live_tokens``, both counted by the program over the
dispatch's steps) over the chip's bandwidth and a device time. That
time is the decode program's in each traced dispatch or, with
``scope``, the program's device self time under that scope over the
traced slice
(``part`` then names the bytes that scope reads: ``window`` or ``full``
for a kind's attention over its cache, ``experts`` for the grouped
matmuls). Step records without the counts (a program that has no such
state), no trace or no such scope: no value."""
from benchmark.harness import cmda_roofline, roofline
from benchmark.readers import _declared, _select


def read(run, args):
    rows = [(s, d) for s, d in _select.traced_steps(
        run, args["step"], args["module"]) if s.get("window_live_tokens")]
    if not rows:
        return None
    per = run["records"]["engine"]["steps_per_dispatch"]
    need = sum(cmda_roofline.decode_bytes(
        run["dims"], per, s["experts_touched"], s["live_tokens"],
        s["window_live_tokens"], args["state_bytes"],
        args.get("part", "all")) for s, _d in rows)
    seconds = sum(d for _s, d in rows)
    if "scope" in args:
        self_s = _declared.self_seconds(run, args["module"],
                                        args["declared"])
        seconds = (self_s or {}).get(args["scope"])
        if not seconds:
            return None
    peak = roofline.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / seconds
