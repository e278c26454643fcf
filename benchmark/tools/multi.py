#!/usr/bin/env python3
"""Several runs of one engine cell in ONE process (one set-up), for the
two things that need many: the sweep that finds the knee of an open
loop, and the readings over a dozen seeds that the correctness limits
are set from (sound runs, and the reference in a lower precision as
the control). Not part of a benchmark run.

  multi.py sweep --workload W --rates 1.5,2,2.5 --seconds 30 --seed 7
  multi.py seeds --workload W --seeds 11,12,13 --seconds 15 \
           --control int4,fp8kv --sample 24 --out chiprun_out/x.npz
  multi.py seeds --workload W --seeds 21,22,23 --seconds 15 \
           --engine kv_dtype=float8_e4m3fn      (the program's own
           lower-precision path as the control)
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def log(msg: str) -> None:
    print(f"[multi] {msg}", flush=True)


def one_run(system, spec, traffic, seed, seconds, tag, reseed):
    from benchmark.harness import drive

    system.reset(tag, seed if reseed else None)
    plan = spec.module("generators", traffic["generator"]).plan(
        traffic, seed, seconds)
    system.prepare(plan)
    driven = drive.drive(system, plan, log)
    system.runner.drain(60.0)
    system.tap.poll()
    return plan, driven, system.collect(plan)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("sweep", "seeds"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control", default="")
    ap.add_argument("--control-seeds", type=int, default=10**6,
                    help="compute --control for the first so many "
                         "seeds only")
    ap.add_argument("--sample", type=int, default=0,
                    help="requests replayed per seed (default: the "
                         "configuration's)")
    ap.add_argument("--engine", default="",
                    help="key=value,... over the configuration's engine "
                         "arguments: the program's own lower precision")
    ap.add_argument("--out", default="",
                    help=".npz of every gap, request by request")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmark.harness import correct, spec, stats
    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
    )

    enable_compile_cache()
    dev = jax.devices()[0]
    log(f"platform: {dev.platform} kind: {dev.device_kind}")
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("needs a TPU")
    cell = spec.load_cell(args.workload)
    for pair in filter(None, args.engine.split(",")):
        key, value = pair.split("=")
        cell["config_data"]["engine"][key] = value
    traffic = dict(cell["traffic_data"])
    if args.rehearse:
        traffic.update(traffic.get("rehearsal", {}))
    builder = spec.module("builders", cell["config_data"]["builder"])
    seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
    t0 = time.monotonic()
    system = builder.System(dict(cell, traffic_data=traffic), seeds[0],
                            args.rehearse, log)
    # warm with the widest traffic of the call
    warm_traffic = dict(traffic)
    if args.rates:
        warm_traffic["rate_per_s"] = max(
            float(r) for r in args.rates.split(","))
    plan = spec.module("generators", traffic["generator"]).plan(
        warm_traffic, seeds[0], args.seconds)
    system.warm(plan)
    system.start()
    log(f"set-up {time.monotonic() - t0:.1f}s parts {system.parts}")

    if args.mode == "sweep":
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            tr = dict(traffic, rate_per_s=rate)
            plan, driven, rec = one_run(system, spec, tr, args.seed,
                                        args.seconds, f"s{k}_", False)
            w = driven["window"]
            reqs = stats.counted(rec["requests"], w)
            ttft = stats.ttft_values(rec["requests"], w)
            tpot = stats.tpot_values_ms(rec["requests"], w)

            def depth(t):
                return sum(1 for r in rec["requests"]
                           if r["sent"] <= t and (
                               not r["first_token_at"]
                               or r["first_token_at"] > t))
            span = w[1] - w[0]
            thirds = [depth(w[0] + span * f) for f in (0.0, 1 / 3, 2 / 3, 1.0)]
            done = [r for r in reqs if r["ok"]]
            print(json.dumps({
                "rate": rate, "counted": len(reqs), "ok": len(done),
                "waiting_at_0_1/3_2/3_1": thirds,
                "ttft_p50": stats.percentile(ttft, 0.5),
                "ttft_p90": stats.percentile(ttft, 0.9),
                "tpot_p50": stats.percentile(tpot, 0.5),
                "tpot_p90": stats.percentile(tpot, 0.9),
                "late_p90_ms": 1e3 * stats.percentile(driven["late_s"], 0.9),
                "out_tok_s": stats.out_tok_s(rec["steps"], w),
                "drain_s": driven["end"] - w[1],
            }), flush=True)
        system.stop()
        return 0

    lowers = tuple(m for m in args.control.split(",") if m)
    k_sample = args.sample or int(
        cell["config_data"]["correct"]["sample_requests"])
    kept = {}
    for k, seed in enumerate(seeds):
        plan, driven, rec = one_run(system, spec, traffic, seed,
                                    args.seconds, f"k{k}_", True)
        since = driven["window"][0] - plan["window"][0]
        sample = correct.sample_requests(rec["engine_requests"], since,
                                         seed, k_sample)
        t1 = time.monotonic()
        gaps = correct.logit_gaps(
            spec.module("reference", cell["config_data"]["reference"]),
            system.weights, system.dims, sample,
            lowers if k < args.control_seeds else ())
        for kind, rows in gaps.pop("rows", {}).items():
            for j, row in enumerate(rows):
                kept[f"{seed}.{kind}.{j}"] = row
        gaps.update(seed=seed, ref_s=time.monotonic() - t1,
                    engine=args.engine,
                    finished=sum(1 for r in rec["requests"] if r["ok"]),
                    lens=[len(r["prompt"]) + len(r["tokens"])
                          for r in sample])
        print(json.dumps(gaps), flush=True)
    system.stop()
    if args.out:
        import numpy as np

        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        np.savez_compressed(args.out, **kept)
    return 0


if __name__ == "__main__":
    sys.exit(main())
