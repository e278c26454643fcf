"""Device self time by named scope, from a run's ``.xplane.pb``.

The program wraps what it does on the device in ``jax.named_scope``s
(``copilot_for_consensus_tpu/obs/profile.py:SCOPES``); the name becomes
part of every HLO instruction's ``op_name``
(``jit(_decode)/while/body/closed_call/ffn/dot_general``). Where that
path is in the trace, found by hand in
``tests/data/tiny_qa.xplane.pb`` (TPU v5 lite, jax 0.9.0): on plane
``/device:TPU:<n>``, line ``XLA Ops``, NOT on the event but on the
event's *metadata* (``XPlane.event_metadata[event.metadata_id]``), as
the stat whose ``XStatMetadata.name`` is ``tf_op``, a string with a
trailing colon. ``jax.profiler.ProfileData`` shows an event's own stats
only (``device_offset_ps``, ``device_duration_ps``), so this module
decodes the few ``XSpace`` fields it needs from the protobuf wire
format itself; it imports neither tensorflow nor xprof. Field numbers
are those of ``tsl/profiler/protobuf/xplane.proto``.

An ``XLA Ops`` line nests: a ``while`` event holds its body's events.
An event's **self time** is its duration less what its direct children
on the same line cover, so nested loops count once and a program's
self times add up to the time its ops cover. Each event belongs to the
``XLA Modules`` event (one executed program) that holds its start, and
to the innermost scope name in its path, or to ``_unscoped_``.
"""

from __future__ import annotations

import bisect

from benchmark.harness import trace_reduce

UNSCOPED = "_unscoped_"
OUTSIDE = "_no_program_"
PATH_STAT = "tf_op"

# XSpace.planes=1 | XPlane: name=2 lines=3 event_metadata=4 (map: key=1
# value=2) stat_metadata=5 | XLine: name=2 timestamp_ns=3 events=4 |
# XEvent: metadata_id=1 offset_ps=2 duration_ps=3 stats=4 |
# XEventMetadata: id=1 name=2 stats=5 | XStatMetadata: id=1 name=2 |
# XStat: metadata_id=1 str_value=5 ref_value=7 (a stat_metadata id whose
# name is the string)
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def wire_fields(buf):
    """(field number, wire type, value) of one message; a value is an
    int (varint) or a memoryview (everything else)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == _VARINT:
            val, i = _varint(buf, i)
        elif wire == _BYTES:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == _FIXED64:
            val, i = buf[i:i + 8], i + 8
        elif wire == _FIXED32:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    for f, _w, v in wire_fields(entry):
        if f == 2:
            return v
    return None


def _path_stat(stats, stat_names) -> str | None:
    for stat in stats:
        fields = {f: v for f, _w, v in wire_fields(stat)}
        if stat_names.get(fields.get(1)) != PATH_STAT:
            continue
        if 5 in fields:
            return text(fields[5])
        if 7 in fields:
            return stat_names.get(fields[7])
    return None


def _read_plane(plane) -> dict:
    name, lines, metas, stat_names = "", [], [], {}
    for f, _w, v in wire_fields(plane):
        if f == 2:
            name = text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta = _map_value(v)
            if meta is not None:
                metas.append(meta)
        elif f == 5:
            meta = _map_value(v)
            if meta is not None:
                d = {g: x for g, _w2, x in wire_fields(meta)}
                stat_names[d.get(1)] = text(d[2]) if 2 in d else ""
    if not trace_reduce.DEVICE_PLANE.match(name):
        return {"name": name, "ops": [], "modules": []}
    by_id = {}
    for meta in metas:
        mid, mname, stats = None, "", []
        for f, _w, v in wire_fields(meta):
            if f == 1:
                mid = v
            elif f == 2:
                mname = text(v)
            elif f == 5:
                stats.append(v)
        by_id[mid] = (mname, _path_stat(stats, stat_names))
    out = {"name": name, "ops": [], "modules": []}
    for line in lines:
        lname, t0_ns, events = "", 0, []
        for f, _w, v in wire_fields(line):
            if f == 2:
                lname = text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        if lname not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        key = "ops" if lname == trace_reduce.OPS_LINE else "modules"
        for ev in events:
            mid = off = dur = 0
            stats = []
            for f, _w, v in wire_fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
                elif f == 4:
                    stats.append(v)
            mname, path = by_id.get(mid, ("", None))
            if key == "ops":
                path = _path_stat(stats, stat_names) or path
            start = t0_ns * 1000 + off                  # picoseconds
            out[key].append((start, start + dur,
                             trace_reduce.short_name(mname), path))
    return out


def read_device_planes(path: str) -> list[dict]:
    """Per device plane: ``ops`` and ``modules`` as (start ps, end ps,
    short name, scope path or None)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = [_read_plane(v) for f, _w, v in wire_fields(data) if f == 1]
    return [p for p in planes if p["ops"] or p["modules"]]


def self_times(events) -> list[int]:
    """Self time of each (start, end, ...) event of one line, in the
    order given: duration less what its direct children cover."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [0] * len(events)
    stack: list[int] = []
    for i in order:
        start, end = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        own[i] = end - start
        if stack:
            own[stack[-1]] -= min(end, events[stack[-1]][1]) - start
        stack.append(i)
    return own


def scope_of(path: str | None, scopes) -> str:
    """The innermost of ``scopes`` among the path's components."""
    if path:
        for part in reversed(path.rstrip(":").split("/")):
            if part in scopes:
                return part
    return UNSCOPED


def scope_of_ops(planes: list[dict], scopes) -> dict:
    """HLO name -> the scope under which the events of that name spent
    most of their time. One name is one op of one program, so as a rule
    it has one scope; two programs can each have a ``fusion.7``, and
    the per-name sums of ``trace_reduce`` add them as they always did."""
    seconds: dict[str, dict] = {}
    for plane in planes:
        for start, end, name, path in plane["ops"]:
            by = seconds.setdefault(name, {})
            scope = scope_of(path, scopes)
            by[scope] = by.get(scope, 0) + end - start
    return {name: max(by, key=by.get) for name, by in seconds.items()}


def program_scopes():
    """The scope names the program declares, or None."""
    try:
        from copilot_for_consensus_tpu.obs.profile import SCOPES
    except ImportError:
        return None
    return SCOPES


def reduce_planes(planes: list[dict], scopes) -> dict:
    """{program: {"device_s": seconds of its XLA Modules events,
    "self_s": {scope: seconds}}}, averaged over the device planes."""
    out: dict[str, dict] = {}
    for plane in planes:
        modules = sorted(plane["modules"])
        starts = [m[0] for m in modules]
        for _s, _e, name, _p in modules:
            prog = out.setdefault(name, {"device_s": 0.0, "self_s": {}})
            prog["device_s"] += (_e - _s) * 1e-12
        for (start, _end, _name, path), own in zip(
                plane["ops"], self_times(plane["ops"])):
            k = bisect.bisect_right(starts, start) - 1
            prog_name = modules[k][2] if k >= 0 \
                and start < modules[k][1] else OUTSIDE
            prog = out.setdefault(prog_name,
                                  {"device_s": 0.0, "self_s": {}})
            sc = scope_of(path, scopes)
            prog["self_s"][sc] = prog["self_s"].get(sc, 0.0) + own * 1e-12
    n = max(1, len(planes))
    for prog in out.values():
        prog["device_s"] /= n
        prog["self_s"] = {k: v / n for k, v in prog["self_s"].items()}
    return out


def reduce_file(path: str, scopes) -> dict:
    return reduce_planes(read_device_planes(path), scopes)


def for_run(run: dict) -> dict | None:
    """The scope table of a traced run (computed once, kept on the
    run), or None: no trace, no device plane, or a program that
    declares no scopes."""
    if "scope_table" in run:
        return run["scope_table"]
    run["scope_table"] = None
    planes = (run.get("trace") or {}).get("device_planes")
    scopes = program_scopes()
    if not planes or scopes is None:
        return None
    table = reduce_planes(planes, scopes)
    for name, prog in sorted(table.items()):
        total = sum(prog["self_s"].values())
        if total > 0:
            shares = {k: round(100 * v / total, 2) for k, v in sorted(
                prog["self_s"].items(), key=lambda kv: -kv[1])}
            print(f"[bench] scope self time {name}: device "
                  f"{prog['device_s']:.4f}s self {total:.4f}s "
                  f"shares % {shares}", flush=True)
    run["scope_table"] = table or None
    return run["scope_table"]
