"""Reduction of a JAX profiler trace (.xplane.pb) to what the per-layer
readers use. Reads the file with ``jax.profiler.ProfileData`` only.

Planes of a TPU trace: ``/device:TPU:<n>`` carries the lines
``XLA Ops`` (one event per executed op) and ``XLA Modules`` (one event
per executed program, named ``jit_<fn>(<hash>)``); ``/host:CPU``
carries one line per host thread, on which the program's
``StepTraceAnnotation``s appear under their name with a ``step_num``
stat and the benchmark's own ``TraceAnnotation``s under ``bench:*``.
All planes share one clock, in nanoseconds.
"""

from __future__ import annotations

import re
import warnings

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
OWN_PREFIX = "bench:"
COMPILE_MARK = "XLA::TPU lowering and optimization"
CLOCK_SLACK_S = 5e-4


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals, lo: float, hi: float):
    """The idle stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def short_name(op: str) -> str:
    """``%fusion.16 = (...) fusion(...)`` -> ``fusion.16``;
    ``jit__decode(1564...)`` -> ``jit__decode``."""
    op = op.strip()
    if op.startswith("%"):
        return op[1:].split(" ", 1)[0]
    return op.split("(", 1)[0]


def _stats(event) -> dict:
    with warnings.catch_warnings():       # the binding's own deprecation
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def read_planes(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations, compiles = [], [], 0
    lo, hi = float("inf"), float("-inf")
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                key = "ops" if line.name == OPS_LINE else "modules"
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dev[key].append((short_name(e.name), s,
                                     s + e.duration_ns * 1e-9))
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    end = s + e.duration_ns * 1e-9
                    lo, hi = min(lo, s), max(hi, end)
                    if e.name.startswith("$"):
                        continue
                    if e.name == COMPILE_MARK:
                        compiles += 1
                        continue
                    step = None
                    if not e.name.startswith(OWN_PREFIX):
                        step = _stats(e).get("step_num")
                        if step is None:
                            continue
                    annotations.append({"name": e.name, "step_num": step,
                                        "start": s, "end": end})
    for dev in devices:
        for _n, s, e in dev["ops"] + dev["modules"]:
            lo, hi = min(lo, s), max(hi, e)
    return {"devices": devices, "annotations": annotations,
            "compiles": compiles, "lo": lo, "hi": hi}


def reduce_planes(planes: dict, top: int = 10,
                  scope_of_op: dict | None = None) -> dict:
    """busy / window / idle gaps by host annotation / per-op sums /
    device time of each annotated step. Busy seconds are averaged over
    the device planes. The sums are per HLO name; with ``scope_of_op``
    (HLO name -> the program's scope it ran under) an op is printed as
    ``ffn/fusion.231``, which says what it is where a bare
    ``fusion.231`` does not."""
    devices = planes["devices"]
    if not devices:
        # a CPU rehearsal: no device plane, nothing to reduce
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "steps": [],
                "compiles": planes["compiles"]}
    lo, hi = planes["lo"], planes["hi"]
    busy, op_sums, gap_sums = [], {}, {}
    notes = sorted(planes["annotations"], key=lambda a: a["start"])
    for dev in devices:
        events = dev["ops"] or dev["modules"]
        spans = [(s, e) for _n, s, e in events]
        busy.append(union_length(spans))
        for name, s, e in events:
            op_sums[name] = op_sums.get(name, 0.0) + (e - s)
        for a, b in gaps_of(spans, lo, hi):
            mid = (a + b) / 2
            owner = "_no_annotation_"
            for n in notes:
                if n["start"] <= mid <= n["end"]:
                    owner = n["name"]
                    break
            gap_sums[owner] = gap_sums.get(owner, 0.0) + (b - a)
    steps = []
    modules = sorted((m for dev in devices for m in dev["modules"]),
                     key=lambda m: m[1])
    for n in notes:
        if n["step_num"] is None:
            continue
        # host and device clocks differ by up to a few hundred
        # microseconds: match a program to the step that holds its
        # midpoint, with that much slack
        mine = [(name, e - s) for name, s, e in modules
                if n["start"] - CLOCK_SLACK_S <= (s + e) / 2
                <= n["end"] + CLOCK_SLACK_S]
        steps.append({"name": n["name"], "step_num": int(n["step_num"]),
                      "host_s": n["end"] - n["start"], "modules": mine})
    n_dev = len(devices)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    named = scope_of_op or {}
    return {
        "busy_s": sum(busy) / n_dev, "window_s": hi - lo,
        "devices": n_dev,
        "device_ops": [[f"{named[k]}/{k}" if k in named else k, v / n_dev]
                       for k, v in rank(op_sums)],
        "idle_gaps": [[k, v / n_dev] for k, v in rank(gap_sums)],
        "steps": steps, "compiles": planes["compiles"],
    }


def reduce_file(path: str) -> dict:
    """The reduction of one trace file, its ops named by scope where
    the program declares scopes. ``device_planes`` keeps what the scope
    reader decoded, so that the file is decoded once a run."""
    from benchmark.harness import scope_reduce    # it imports this file

    device_planes = scope_reduce.read_device_planes(path)
    scopes = scope_reduce.program_scopes()
    named = scope_reduce.scope_of_ops(device_planes, scopes) \
        if scopes else None
    out = reduce_planes(read_planes(path), scope_of_op=named)
    out["device_planes"] = device_planes
    return out
