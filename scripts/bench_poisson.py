"""Continuous-arrival (Poisson) serving bench on the real TPU.

The batch bench (`bench.py`) measures an all-at-once wave: admit 128
prompts, decode them together. Real serving sees requests trickle in;
the VERDICT r2 concern was that one admission wave stalls all decode
slots. This bench drives the async dispatcher (`engine/async_runner`)
with Poisson arrivals at a configurable fraction of the batch bench's
measured capacity and reports sustained throughput + latency
percentiles. Done-criterion: sustained ≥90% of batch throughput at
0.9× offered load.

Usage: python scripts/bench_poisson.py [--rate REQ_S] [--duration S]
Env: BENCH_* knobs as in bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> None:
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )
    from copilot_for_consensus_tpu.engine.generation import GenerationEngine
    from copilot_for_consensus_tpu.models import decoder_config
    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
        require_accelerator,
    )

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="arrivals/s (default 0.9x batch capacity)")
    ap.add_argument("--duration", type=float, default=45.0)
    ap.add_argument("--batch-tok-s", type=float, default=3215.0,
                    help="measured batch-bench tok/s for the same config"
                         " (capacity reference)")
    ap.add_argument("--poll-harvest", action="store_true",
                    help="legacy 2ms polling harvest loop (the r4 "
                         "host-tax baseline) instead of completion "
                         "callbacks — for A/B measurement only")
    ap.add_argument("--switch-interval", type=float, default=0.0,
                    help="sys.setswitchinterval override (default: "
                         "leave CPython's 5ms); raising it cuts GIL "
                         "handoffs during the dispatch call")
    args = ap.parse_args()
    if args.switch_interval:
        sys.setswitchinterval(args.switch_interval)

    model = os.environ.get("BENCH_MODEL", "mistral-7b")
    slots = int(os.environ.get("BENCH_SLOTS", "128"))
    max_len = int(os.environ.get("BENCH_MAX_LEN", "256"))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", "128"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "96"))
    window = int(os.environ.get("BENCH_DECODE_WINDOW", "32"))

    dev = require_accelerator("scripts/bench_poisson.py")
    enable_compile_cache()
    cfg = decoder_config(model)
    print(f"building {model} engine ({slots} slots) on "
          f"{dev.device_kind} ({dev.platform})...", file=sys.stderr)
    eng = GenerationEngine(
        cfg, num_slots=slots, max_len=max_len,
        prefill_buckets=(prompt_len,), dtype=jnp.bfloat16,
        kv_dtype=os.environ.get("BENCH_KV_DTYPE", "float8_e4m3fn"),
        quantize=os.environ.get("BENCH_WEIGHT_DTYPE", "int8"),
        decode_window=window,
        windows_per_dispatch=int(os.environ.get(
            "BENCH_WINDOWS_PER_DISPATCH", "1")),
        admit_min_rows=int(os.environ.get("BENCH_ADMIT_MIN_ROWS", "1")),
        admit_max_wait_s=float(os.environ.get("BENCH_ADMIT_MAX_WAIT",
                                              "1.5")),
        admit_hold_strict=os.environ.get("BENCH_ADMIT_STRICT",
                                         "0") == "1",
        # chunked-prefill piggybacking: short prompts pack into the
        # decode dispatches' chunk lanes instead of stalling decode in
        # admission waves (BENCH_PIGGYBACK=0 restores pure waves)
        # C=32 sizes the chunk grid (W*C*P = 4096 tokens/dispatch) so
        # its flops just fill the decode bandwidth floor at this load;
        # an oversized grid pays its padding flops whether or not
        # arrivals fill it (measured: empty 8192 grid = +1.0 s/dispatch)
        prefill_chunk=int(os.environ.get("BENCH_PREFILL_CHUNK", "32")),
        prefill_rows=int(os.environ.get("BENCH_PREFILL_ROWS", "4")),
        piggyback_min_prompt=(
            10**9 if os.environ.get("BENCH_PIGGYBACK", "0") != "1"
            else int(os.environ.get("BENCH_PIGGYBACK_MIN", "64"))),
        seed=0)

    rng = np.random.default_rng(0)

    def mk_prompt():
        return rng.integers(3, cfg.vocab_size, size=prompt_len).tolist()

    # Warmup: compile admit + the decode kv buckets the run will hit.
    print("warmup (compiles)...", file=sys.stderr)
    runner = AsyncEngineRunner(eng).start()
    for h in [runner.submit(mk_prompt(), new_tokens)
              for _ in range(slots)]:
        h.result(timeout=600)

    # Offered load: each request consumes new_tokens of decode budget.
    cap_req_s = args.batch_tok_s / new_tokens
    rate = args.rate or 0.9 * cap_req_s
    print(f"offered load {rate:.1f} req/s "
          f"(capacity ~{cap_req_s:.1f} req/s)", file=sys.stderr)

    # Two harvest modes. Callback mode (default) is the r5 host-tax
    # fix: the arrival thread sleeps until the NEXT arrival and does
    # nothing else; completions are accounted on the dispatcher thread
    # as they resolve. Poll mode is the r4 baseline: wake every 2ms and
    # scan every in-flight handle — measured to inflate the dispatch
    # call 0.77s -> 0.90s under load via GIL contention (PERF.md r4).
    import threading

    lat: list[float] = []
    served = [0]
    acct = threading.Lock()

    def _account(t_sub: float, h) -> None:
        try:
            c = h.result(0)
        except Exception:
            return                      # failed/stopped request
        with acct:
            lat.append(time.monotonic() - t_sub)
            served[0] += len(c.tokens)

    handles: list = []
    t_start = time.monotonic()
    t_next = t_start
    submitted = 0
    if args.poll_harvest:
        while True:
            now = time.monotonic()
            if now - t_start >= args.duration:
                break
            if now >= t_next:
                handles.append((now, runner.submit(mk_prompt(),
                                                   new_tokens)))
                submitted += 1
                t_next += rng.exponential(1.0 / rate)
            else:
                time.sleep(min(0.002, t_next - now))
            still = []
            for t_sub, h in handles:
                if h.done():
                    _account(t_sub, h)
                else:
                    still.append((t_sub, h))
            handles = still
    else:
        # No handle list: retaining every resolved handle (and its
        # Completion token list) grows memory for the whole run. The
        # done-callback both accounts AND retires; a plain counter +
        # condition is all the drain needs.
        inflight = [0]
        drained = threading.Condition()

        def _retire(t_sub, h):
            _account(t_sub, h)
            with drained:
                inflight[0] -= 1
                drained.notify()

        while True:
            now = time.monotonic()
            if now - t_start >= args.duration:
                break
            if now < t_next:
                time.sleep(t_next - now)    # ONE sleep per arrival
                continue
            t_sub = time.monotonic()
            h = runner.submit(mk_prompt(), new_tokens)
            with drained:
                inflight[0] += 1
            h.add_done_callback(lambda hh, t=t_sub: _retire(t, hh))
            submitted += 1
            t_next += rng.exponential(1.0 / rate)
    # drain what's in flight (counts toward throughput window only up
    # to the measured elapsed time below)
    if args.poll_harvest:
        for t_sub, h in handles:
            try:
                h.result(timeout=120)
            except Exception:
                pass
            _account(t_sub, h)
    else:
        deadline = time.monotonic() + 120
        with drained:
            while inflight[0] and time.monotonic() < deadline:
                drained.wait(timeout=1.0)
    elapsed = time.monotonic() - t_start
    runner.stop()
    served_tokens = served[0]

    print(f"dispatches: piggy {eng.piggy_dispatches} "
          f"({eng.piggy_s:.1f}s, {eng.piggy_rows} rows / "
          f"{eng.piggy_tokens} prompt tokens), plain "
          f"{eng.plain_dispatches} ({eng.plain_s:.1f}s), waves "
          f"{eng.admitted_s:.1f}s", file=sys.stderr)
    tok_s = served_tokens / elapsed
    frac = tok_s / args.batch_tok_s
    lat_arr = np.asarray(sorted(lat)) if lat else np.asarray([0.0])
    print(f"{submitted} arrivals, {len(lat)} served, "
          f"{served_tokens} tokens in {elapsed:.1f}s", file=sys.stderr)
    print(json.dumps({
        "metric": f"{model} Poisson-arrival serving throughput "
                  f"({slots} slots, {rate:.1f} req/s offered)",
        "value": round(tok_s, 1),
        "unit": "tok/s",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "fraction_of_batch": round(frac, 3),
        "p50_latency_s": round(float(lat_arr[len(lat_arr) // 2]), 2),
        "p95_latency_s": round(float(lat_arr[int(len(lat_arr) * 0.95)
                                             - 1]), 2),
        # the r4 host-tax telemetry: mean plain decode dispatch under
        # serving load (quiet baseline ~0.77s at 128 slots; 0.90s was
        # the polling-harvest contention figure)
        "mean_dispatch_s": round(
            eng.plain_s / max(1, eng.plain_dispatches), 3),
        "harvest": "poll" if args.poll_harvest else "callback",
    }))


if __name__ == "__main__":
    main()
