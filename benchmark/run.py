#!/usr/bin/env python3
"""benchmark/run.py --workload <config>.<traffic> --seed n --seconds s
--trace 0|1 [--rehearse]

Runs one cell of BENCHMARK.json on the machine it is started on and
prints, as the last line of stdout, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` and, last, under
``compared`` each number ``correct`` was decided by beside its limit
(the same lines end standard error). Everything a cell is made of is
found by name: ``configs/<config>.json`` (which names its builder and
its plain reference), ``traffic/<traffic>.json`` (which names its
generator) and every ``metrics/*.json`` that lists the cell (each names
its reader).

``--rehearse`` runs the same builders and generators at the ``tiny``
sizes wherever JAX was told to run, prints ``platform: cpu`` (or
whatever it found), never a result line, and exits 3.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_PROCESS = time.monotonic()
REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

REHEARSAL_EXIT = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def execute(args) -> dict:
    """One run. Returns the result object; ``main`` decides whether it
    may be printed."""
    from benchmark.harness import spec

    cell = spec.load_cell(args.workload)
    if not (REPO / "copilot_for_consensus_tpu").is_dir():
        raise SystemExit("the system under test is not in this "
                         "directory: nothing to measure")

    import jax

    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    log(f"platform: {dev.platform}  kind: {dev.device_kind}  "
        f"count: {len(devices)}  compile cache: {cache_dir}")
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        raise SystemExit(
            f"{args.workload} needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(devices)} x {dev.platform}. No result.")
    if not args.rehearse:
        from benchmark.harness import roofline
        roofline.peaks(dev.device_kind)      # unknown kind: error now

    compile_times: list[float] = []
    compile_events: list[tuple] = []

    def on_duration(name, secs, **_kw):
        if "compile" in name or "trace" in name:
            compile_events.append((time.monotonic(), name, secs))
        if name == "/jax/core/compile/backend_compile_duration":
            compile_times.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    traffic = dict(cell["traffic_data"])
    if args.rehearse:
        traffic.update(traffic.get("rehearsal", {}))
    plan = spec.module("generators", traffic["generator"]).plan(
        traffic, args.seed, args.seconds)
    builder = spec.module("builders", cell["config_data"]["builder"])
    system = builder.System(dict(cell, traffic_data=traffic), args.seed,
                            args.rehearse, log)
    t0 = time.monotonic()
    system.prepare(plan)
    system.parts["traffic_s"] = time.monotonic() - t0
    system.warm(plan)
    warm_compiles = len(compile_times)
    system.start()
    setup_s = time.monotonic() - T_PROCESS
    log("set-up parts: " + json.dumps(
        {k: round(v, 3) for k, v in system.parts.items()})
        + f"  setup_s {setup_s:.3f}  compiles in set-up {warm_compiles}")

    from benchmark.harness import drive, tracing

    tracer = None
    if args.trace:
        tracer = tracing.Tracer(
            REPO / "benchmark" / ".cache" / "trace" / args.workload)
        now = time.monotonic()
        w0, w1 = plan["window"]
        share = float(traffic.get("trace_share", 0.2))
        mid = now + (w0 + w1) / 2
        tracer.schedule(mid - share * (w1 - w0) / 2,
                        mid + share * (w1 - w0) / 2)
    driven = drive.drive(system, plan, log)
    window = driven["window"]
    trace = tracer.finish() if tracer else None
    system.stop()
    records = system.collect(plan)
    stats_now = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    log(f"window {window[1] - window[0]:.3f}s  "
        f"memory limit {stats_now.get('bytes_limit')}")

    run = {"cell": cell, "seconds": args.seconds, "window": window,
           "records": records, "drive": driven, "trace": trace,
           "device": device, "compile_times": compile_times,
           "dims": system.dims, "setup_s": setup_s}
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec.metric_files(args.workload, kind):
        value = spec.module("readers", m["reader"]).read(
            run, m.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    _facts(run, log)
    inside = [(round(t - window[0], 2), n.rsplit("/", 1)[-1], round(s, 2))
              for t, n, s in compile_events if window[0] <= t < window[1]]
    log(f"tracing/compile events inside the counted interval "
        f"(at s, name, took s): {inside}")

    # ---- correct: outside the window, after the program's state is
    # freed, against the plain reference -------------------------------
    from benchmark.harness import correct

    checks = []
    attempted, failed = system.outcome(records, window)
    limits = dict(cell["config_data"]["correct"])
    if args.rehearse:
        limits.update(cell["config_data"]["rehearsal"].get("correct", {}))
    t0 = time.monotonic()
    sample = correct.sample_requests(
        records["engine_requests"], window[0] - plan["window"][0],
        args.seed, int(limits["sample_requests"]))
    freed = correct.free_device_memory(system.weights)
    gaps = correct.logit_gaps(
        spec.module("reference", cell["config_data"]["reference"]),
        system.weights, system.dims, sample)
    for name in ("logit_gap_max", "logit_gap_mean", "not_best_share"):
        if name in limits:
            checks.append({"name": name, "value": gaps.get(name),
                           "limit": limits[name]})
    checks.append({"name": "served_tokens_compared",
                   "value": gaps["tokens"],
                   "limit": int(limits.get("served_tokens_min", 1)),
                   "at_least": True})
    checks.append({"name": "engine_errors",
                   "value": records["engine"]["errors"], "limit": 0})
    checks.append({"name": "failed_requests", "value": failed,
                   "limit": 0})
    ok = True
    for c in checks:
        if c["value"] is None:
            c["ok"] = False
        elif c.get("at_least"):
            c["ok"] = c["value"] >= c["limit"]
        else:
            c["ok"] = c["value"] <= c["limit"]
        ok = ok and c["ok"]
    log(f"reference over {gaps['requests']} requests / {gaps['tokens']} "
        f"served tokens took {time.monotonic() - t0:.1f}s after freeing "
        f"{freed} device arrays ({gaps.get('seconds_each')} s each); "
        f"served tokens that are not the "
        f"reference's best: {gaps.get('not_best_share')}")

    result = {"correct": bool(ok), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    # what the driver's record keeps of a run that is not correct: the
    # end of standard error and the end of the result line
    result["compared"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"]}
        for c in checks}
    for c in checks:
        print(f"compared {c['name']} {c['value']} "
              f"{'at least' if c.get('at_least') else 'limit'} "
              f"{c['limit']}{'' if c['ok'] else ' NOT OK'}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    result = execute(args)
    if args.rehearse:
        log(f"rehearsal on platform: {result['device']['platform']} — "
            f"host-side counts only, no device metric and no result "
            f"line: " + json.dumps(
                {"correct": result["correct"],
                 "attempted": result["attempted"],
                 "failed": result["failed"],
                 "metric_names": sorted(result["metrics"])}))
        return REHEARSAL_EXIT
    print(json.dumps(result), flush=True)
    return 0


def _facts(run, log) -> None:
    """Earlier lines: the prompt lengths the engine saw, and an open
    loop's tails beside its metrics."""
    import collections

    lens = collections.Counter(
        r["prompt_len"] for r in run["records"]["engine_requests"]
        if r["enqueued_at"] >= run["window"][0])
    if lens:
        top = sorted(lens.items())
        log(f"prompt_len seen by the engine in the interval "
            f"(len: count): {top[:6]} ... {top[-6:]}  n={sum(lens.values())}")
    if run["drive"]["late_s"]:
        from benchmark.harness import stats
        reqs, w = run["records"]["requests"], run["window"]
        ttft = stats.ttft_values(reqs, w)
        tpot = stats.tpot_values_ms(reqs, w)
        log("tails beside the metrics: " + json.dumps({
            "ttft_p50_s": stats.percentile(ttft, 0.5),
            "ttft_p90_s": stats.percentile(ttft, 0.9),
            "tpot_p50_ms": stats.percentile(tpot, 0.5),
            "tpot_p90_ms": stats.percentile(tpot, 0.9),
            "ttft_mean_s": sum(ttft) / len(ttft),
            "e2e_p90_s": stats.percentile(
                stats.e2e_values(reqs, w), 0.9),
            "counted": len(ttft),
            "late_max_ms": 1e3 * max(run["drive"]["late_s"])}))


if __name__ == "__main__":
    raise SystemExit(main())
