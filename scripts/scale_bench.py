#!/usr/bin/env python3
"""Host-pipeline scale benchmark: synthetic archive → per-stage p95 vs
the reference's SLO thresholds.

The reference's north-star corpus is ≥100k messages (BASELINE.json); its
SLOs are alert thresholds (``infra/prometheus/alerts/slo_latency.yml``):
parsing p95 < 5s, chunking p95 < 2s, embedding batch p95 < 10s,
summarization p95 < 30s, reporting API p95 < 0.5s. This bench generates
a threaded synthetic mbox at any scale, runs the full pipeline on the
indexed sqlite store, and prints one JSON line per stage with measured
p95 against the SLO.

  python scripts/scale_bench.py --messages 100000        # the north star
  python scripts/scale_bench.py --messages 5000          # quick check

Mock embedding/LLM drivers isolate host-pipeline throughput (the TPU
engines are benchmarked by bench.py); --embedding tpu swaps in the real
encoder.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import random
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# SLO thresholds (seconds): reference slo_latency.yml p95 rows.
SLOS = {
    "parsing": 5.0,
    "chunking": 2.0,
    "embedding": 10.0,
    "summarization": 30.0,
}
REPORTING_API_SLO = 0.5

# The 100k-message single-consumer-per-stage broker run this repo's
# scale work is measured against (SCALE_BROKER.json, PR-10 era):
# every later run's speedup_vs_baseline column divides by this.
BROKER_BASELINE_MSG_S = 59.6

# The host-bound stages a bare "--workers N" scales; "name=N" pairs can
# target any service.
SCALABLE_STAGES = ("parsing", "chunking", "embedding")


def parse_workers_spec(spec: str) -> dict[str, int]:
    """``"4"`` → 4 workers on every host-bound stage;
    ``"parsing=2,chunking=6"`` → per-stage counts. Empty → {} (one
    consumer per stage, the pre-scale-out wiring)."""
    spec = (spec or "").strip()
    if not spec:
        return {}
    if "=" not in spec:
        n = int(spec)
        return {s: n for s in SCALABLE_STAGES} if n > 1 else {}
    out: dict[str, int] = {}
    for part in spec.split(","):
        name, _, n = part.partition("=")
        out[name.strip()] = int(n)
    return out


def services_config(workers: dict[str, int], prefetch: int = 0,
                    batch: bool = True) -> dict[str, dict]:
    """The ``cfg["services"]`` block (runner.py stage scale-out knobs)
    for a worker spec + optional per-fetch prefetch override.
    ``batch=False`` pins every stage to per-envelope dispatch — the
    pre-scale-out wiring, kept as a measurable baseline arm."""
    cfg: dict[str, dict] = {}
    for name, n in workers.items():
        cfg[name] = {"workers": n}
    if prefetch:
        for name in set(workers) | set(SCALABLE_STAGES):
            cfg.setdefault(name, {})["prefetch"] = prefetch
    if not batch:
        for name in set(workers) | set(SCALABLE_STAGES):
            cfg.setdefault(name, {})["batch"] = False
    return cfg


def broker_artifact(*, messages: int, gen_s: float, run_s: float,
                    events: int, max_depth: dict, workers: dict,
                    prefetch: int, failure_audit: dict, stats: dict,
                    ok: bool, watermark: int = 0) -> dict:
    """The SCALE_BROKER.json artifact shape — one place so the bench
    and the contract tests agree on the columns (speedup_vs_baseline
    and workers are the ISSUE-11 additions)."""
    worst = max(max_depth.values() or [0])
    msg_s = round(messages / max(run_s, 1e-9), 1)
    return {
        "stage": "broker_total", "messages": messages,
        "generate_s": round(gen_s, 1), "pipeline_s": round(run_s, 1),
        "messages_per_s": msg_s,
        "baseline_messages_per_s": BROKER_BASELINE_MSG_S,
        "speedup_vs_baseline": round(msg_s / BROKER_BASELINE_MSG_S, 2),
        "workers": {s: int(workers.get(s, 1)) for s in SCALABLE_STAGES}
        | {k: int(v) for k, v in workers.items()
           if k not in SCALABLE_STAGES},
        "prefetch": int(prefetch) or 16,
        "high_watermark": int(watermark),
        "broker_events": events,
        "broker_events_per_s": round(events / max(run_s, 1e-9), 1),
        "max_queue_depth": max_depth,
        "queue_depth_slo": {"warn": 1000, "crit": 10000,
                            "worst": worst},
        "failure_audit": failure_audit,
        "stats": stats, "ok": ok,
    }

_WORDS = ("consensus rough running code draft review thread mail archive "
          "protocol header token budget window chunk merge split rfc "
          "discussion agree disagree object support propose revise").split()


def synthetic_mbox(path: pathlib.Path, n_messages: int,
                   thread_size: int = 8, seed: int = 0,
                   prefix: str = "a0") -> None:
    """``prefix`` keeps message ids and subjects distinct across archives
    so threads never merge between them."""
    rng = random.Random(seed)
    with path.open("w", encoding="utf-8") as f:
        thread_root = None
        for i in range(n_messages):
            if i % thread_size == 0:
                thread_root = f"<t{prefix}-{i}@bench>"
                subject = f"Draft discussion {prefix}-{i // thread_size}"
                refs = ""
            else:
                refs = (f"In-Reply-To: {thread_root}\n"
                        f"References: {thread_root}\n")
                subject = f"Re: Draft discussion {prefix}-{i // thread_size}"
            body = " ".join(rng.choice(_WORDS) for _ in range(120))
            f.write(
                f"From m{i}@bench Thu Jan  1 00:00:00 2026\n"
                f"From: Person {i % 37} <p{i % 37}@example.org>\n"
                f"To: wg@example.org\n"
                f"Message-ID: <m{prefix}-{i}@bench>\n"
                f"{refs}"
                f"Subject: {subject}\n"
                f"Date: Thu, 1 Jan 2026 {i % 24:02d}:00:00 +0000\n"
                f"\n{body}\n\n")


class _SamplingMetrics:
    """InMemoryMetrics plus raw samples, for exact percentiles."""

    def __init__(self, inner):
        self._inner = inner
        self.samples: dict[str, list[float]] = {}

    def observe(self, name, value, labels=None):
        self.samples.setdefault(name, []).append(float(value))
        self._inner.observe(name, value, labels)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _p95(values: list[float]) -> float:
    if not values:
        return 0.0
    values = sorted(values)
    return values[min(len(values) - 1, int(0.95 * len(values)))]


def _cpu_jax() -> None:
    """This bench measures HOST throughput (mock inference): pin jax to
    CPU in every role-split process. The chip has one owner at a time —
    a second process initializing it fails or hangs — and nothing here
    needs it."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def _worker(tmp: pathlib.Path, port: int, roles: str,
            workers_spec: str = "", prefetch: int = 0,
            watermark: int = 0) -> int:
    """Role-split worker process: consume the given stages off the
    broker until the stop file appears (the container role of the
    reference's docker-compose.services.yml workers). ``workers_spec``
    sizes the per-stage consumer pools (services/pool.py) inside this
    process — the in-process version of adding replica containers."""
    import threading

    _cpu_jax()
    from copilot_for_consensus_tpu.services.runner import build_pipeline

    role_list = roles.split(",")
    workers = {name: n for name, n in
               parse_workers_spec(workers_spec).items()
               if name in role_list}
    p = build_pipeline({
        "bus": {"driver": "broker", "port": port,
                "high_watermark": watermark},
        "roles": role_list,
        "services": services_config(
            workers, prefetch,
            batch=os.environ.get("SCALE_NO_BATCH", "") != "1"),
        "document_store": {"driver": "sqlite",
                           "path": str(tmp / "docs.sqlite3")},
        "archive_store": {"driver": "document"},
        "vector_store": {"driver": "tpu", "dtype": "float32"},
        "embedding": {"driver": "mock", "dimension": 384},
        "llm": {"driver": "mock"},
    })
    stop = threading.Event()
    stop_file = tmp / "stop"

    def watch():
        while not stop_file.exists():
            time.sleep(0.5)
        stop.set()

    threading.Thread(target=watch, daemon=True).start()
    p.run_forever(stop)
    return 0


def _broker_raw(args, tmp: pathlib.Path) -> int:
    """Broker ceiling characterization: publish + consume/ack no-op
    events as fast as one client can — distinguishes 'the broker caps
    throughput' from 'the host's CPU does'."""
    import subprocess

    from copilot_for_consensus_tpu.bus.factory import (
        create_publisher,
        create_subscriber,
    )
    from copilot_for_consensus_tpu.core.events import ArchiveIngested

    port = 5912
    br = subprocess.Popen(
        [sys.executable, "-m", "copilot_for_consensus_tpu", "broker",
         "--port", str(port), "--db", str(tmp / "raw.sqlite3")],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    time.sleep(1.5)
    try:
        n = args.messages
        pub = create_publisher({"driver": "broker", "port": port},
                               validate=False)
        pub.connect()
        t0 = time.monotonic()
        for i in range(n):
            pub.publish(ArchiveIngested(archive_id=f"a{i}",
                                        source_id="s"))
        pub_s = time.monotonic() - t0
        sub = create_subscriber({"driver": "broker", "port": port},
                                validate=False)
        sub.connect()
        sub.subscribe(["archive.ingested"], lambda e: None)
        t0 = time.monotonic()
        got = sub.drain(n)
        con_s = time.monotonic() - t0
        print(json.dumps({
            "stage": "broker_raw", "messages": n,
            "publish_msg_s": round(n / pub_s, 1),
            "consume_ack_msg_s": round(got / con_s, 1),
            "ok": got == n,
        }))
        return 0 if got == n else 1
    finally:
        br.terminate()
        br.wait(timeout=10)


def _broker_mode(args, tmp: pathlib.Path, n_arch: int, gen_s: float) -> int:
    """100k-message proof THROUGH the durable broker with role-split
    processes (VERDICT r2 weak item 6: the in-proc path bypassed the
    broker entirely)."""
    import subprocess

    _cpu_jax()
    from copilot_for_consensus_tpu.services.runner import build_pipeline

    port = 5899
    procs = [subprocess.Popen(
        [sys.executable, "-m", "copilot_for_consensus_tpu", "broker",
         "--port", str(port), "--db", str(tmp / "broker.sqlite3")],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)]
    time.sleep(1.5)
    for roles in ("parsing,chunking",
                  "embedding,orchestrator,summarization,reporting"):
        procs.append(subprocess.Popen(
            [sys.executable, __file__, "--worker", roles,
             "--tmp", str(tmp), "--port", str(port),
             "--workers", args.workers,
             "--prefetch", str(args.prefetch),
             "--watermark", str(args.watermark)],
            stdout=subprocess.DEVNULL, stderr=sys.stderr))
    try:
        p = build_pipeline({
            "bus": {"driver": "broker", "port": port,
                    "high_watermark": args.watermark},
            "roles": ["ingestion"],
            "document_store": {"driver": "sqlite",
                               "path": str(tmp / "docs.sqlite3")},
            "archive_store": {"driver": "document"},
            "vector_store": {"driver": "tpu", "dtype": "float32"},
            "embedding": {"driver": "mock", "dimension": 384},
            "llm": {"driver": "mock"},
        })
        for a in range(n_arch):
            p.ingestion.create_source({
                "source_id": f"bench-{a}", "name": f"bench-{a}",
                "fetcher": "local",
                "location": str(tmp / f"archive-{a}.mbox")})
        expected_reports = sum(
            -(-(args.messages // n_arch if a < n_arch - 1 else
                args.messages - (args.messages // n_arch) * (n_arch - 1))
              // args.thread_size) for a in range(n_arch))
        t1 = time.monotonic()
        # Ingestion backpressure (the r3 run's crit breach diagnosis:
        # triggering all 40 archives at once floods json.parsed to
        # 17,946 on a 1-core host and starves parsing — r3
        # SCALE_BROKER.json). Pace triggers against the parsed-queue
        # depth instead: the ingestion scheduler holds the next archive
        # until the pipeline has drained below the threshold — the same
        # role the reference's scheduler plays for periodic sources.
        backpressure = int(os.environ.get("SCALE_BACKPRESSURE", "2000"))
        pending_triggers = list(range(n_arch))
        triggered = 0
        max_depth: dict[str, int] = {}
        # Archives in flight scale with the parsing pool: one archive
        # per parsing worker (min 2) keeps every worker fed without
        # flooding downstream queues past the watermark gate below.
        inflight_cap = max(2, parse_workers_spec(args.workers)
                           .get("parsing", 1))
        deadline = time.monotonic() + max(600, args.messages / 30)
        while time.monotonic() < deadline:
            try:
                depths = p.routing_key_depths()
            except Exception:
                # transient broker-loop saturation under load: skip
                # this tick (conservative: nothing triggers) rather
                # than crash the run
                time.sleep(1.0)
                continue
            for rk, d in depths.items():
                max_depth[rk] = max(max_depth.get(rk, 0), d)
            # The parsed-queue depth LAGS triggering by the archive's
            # whole parse latency, so gate primarily on archives
            # outstanding (triggered − parsed): at most inflight_cap
            # archives in flight bounds every downstream queue
            # regardless of how slowly the host drains.
            parsed_archives = p.store.count_documents(
                "archives", {"parsed": True})
            if (pending_triggers
                    and triggered - parsed_archives < inflight_cap
                    and max(depths.get("json.parsed", 0),
                            depths.get("chunks.prepared", 0),
                            depths.get("embeddings.generated", 0),
                            depths.get("summarization.requested", 0),
                            depths.get("summary.complete", 0))
                    < backpressure):
                p.ingestion.trigger_source(
                    f"bench-{pending_triggers.pop(0)}")
                triggered += 1
                continue
            # Completion needs BOTH counts: racing orchestrations can
            # mint duplicate reports before parsing finishes, so the
            # report count alone declares victory early.
            if (p.store.count_documents("messages", {}) >= args.messages
                    and p.store.count_documents("reports", {})
                    >= expected_reports):
                break
            time.sleep(1.0)
        run_s = time.monotonic() - t1
        # Settle to quiescence before auditing: the completion check
        # fires on message+report counts while late summarizations are
        # still in the queues. If anything is STILL missing after the
        # queues quiet down (retry-exhausted orchestrations), run the
        # production recovery spine — the stuck-document retry job —
        # exactly as the deployed cron does, and let it drain.
        from copilot_for_consensus_tpu.tools.retry_job import (
            RetryStuckDocumentsJob,
            default_rules,
        )

        def _missing() -> int:
            return p.store.count_documents(
                "threads", {"summary_id": {"$exists": False}})

        settle_deadline = min(deadline + 600,
                              time.monotonic()
                              + max(240, args.messages / 80))
        swept = False
        while time.monotonic() < settle_deadline:
            try:
                depths = p.routing_key_depths()
            except Exception:
                time.sleep(1.0)       # transient: not quiescent yet
                continue
            busy = sum(d for rk, d in depths.items()
                       if not rk.endswith(".failed"))
            if busy == 0:
                if _missing() == 0:
                    break
                if not swept:
                    # sweep as the cron WOULD after the backoff window:
                    # min_stuck=0 alone still gates on backoff_minutes
                    # anchored at parsed_at, which would skip threads
                    # parsed in the run's final minutes
                    RetryStuckDocumentsJob(
                        p.store, p.orchestrator.publisher,
                        default_rules(),
                        min_stuck_seconds=0.0).run_once(
                        now=time.time() + 600)
                    swept = True
                    continue
                break                       # swept and drained: final
            time.sleep(1.0)
        stats = p.reporting.stats()
        # Failure audit (r3 verdict: 313 unexplained orchestration.failed
        # events): drain the failure queue, classify the errors, and
        # verify NO thread actually lost its summary — retry-exhausted
        # orchestrations are re-covered by the threads-stage recovery
        # rule (tools/retry_job.py default_rules), so transient
        # cross-process visibility races under load degrade to retries,
        # not lost work.
        from copilot_for_consensus_tpu.bus.broker import BrokerSubscriber

        failures: list[dict] = []
        audit = BrokerSubscriber({"port": port}, group="bench-audit")
        audit.subscribe(["orchestration.failed",
                         "summarization.failed"],
                        lambda env: failures.append(env))
        audit.drain()
        audit.close()
        by_error: dict[str, int] = {}
        for env in failures:
            key = (env.get("data", {}).get("error_type", "?") + ": "
                   + env.get("data", {}).get("error", "")[:60])
            by_error[key] = by_error.get(key, 0) + 1
        threads_missing_summary = p.store.count_documents(
            "threads", {"summary_id": {"$exists": False}})
        # every pipeline event crossed the broker: archives + 3 hops per
        # message (parsed->chunked->embedded) + 3 per thread
        events = (n_arch + 3 * args.messages
                  + 3 * stats.get("reports", 0))
        worst = max(max_depth.values() or [0])
        ok = (stats.get("reports", 0) >= expected_reports
              and worst <= 10000
              and threads_missing_summary == 0)
        out = broker_artifact(
            messages=args.messages, gen_s=gen_s, run_s=run_s,
            events=events, max_depth=max_depth,
            workers=parse_workers_spec(args.workers),
            prefetch=args.prefetch, watermark=args.watermark,
            failure_audit={
                "events": len(failures),
                "by_error": by_error,
                "threads_missing_summary": threads_missing_summary,
                "note": ("failure events are retries exhausted under "
                         "load; the threads-stage recovery rule "
                         "re-orchestrates them — ok requires zero "
                         "threads left without a summary"),
            },
            stats=stats, ok=ok)
        print(json.dumps(out))
        if not args.smoke:
            # the smoke arm is a CI correctness check at toy scale —
            # it must never overwrite the measured artifact
            (pathlib.Path(__file__).resolve().parent.parent
             / "SCALE_BROKER.json").write_text(json.dumps(out, indent=2)
                                               + "\n")
        return 0 if ok else 1
    finally:
        (tmp / "stop").touch()
        time.sleep(1.5)
        for pr in procs[1:]:
            pr.terminate()
        procs[0].terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pr.kill()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--messages", type=int, default=5000)
    ap.add_argument("--archives", type=int, default=0,
                    help="split into N archives (0 = ~2500 msgs each, "
                         "the reference's monthly-mbox shape)")
    ap.add_argument("--thread-size", type=int, default=8)
    ap.add_argument("--embedding", default="mock", choices=["mock", "tpu"])
    ap.add_argument("--bus", default="inproc",
                    choices=["inproc", "broker", "broker-raw"],
                    help="broker = role-split processes over the "
                         "durable ZMQ broker; broker-raw = no-op "
                         "publish/consume ceiling")
    ap.add_argument("--keep-db", action="store_true")
    ap.add_argument("--workers", default="",
                    help="per-stage consumer pools: '4' (all host "
                         "stages) or 'parsing=2,chunking=6,embedding=2'"
                         " — one pool per service sharing its broker "
                         "group (empty = 1 consumer per stage)")
    ap.add_argument("--prefetch", type=int, default=0,
                    help="bus.prefetch override: envelopes leased per "
                         "fetch (0 = driver default 16); batched stages"
                         " dispatch a whole fetch as one wave")
    ap.add_argument("--watermark", type=int, default=0,
                    help="bus.high_watermark: publishers pace and "
                         "services throttle when a key's broker depth "
                         "crosses it (0 = off); set ~half the 1000 "
                         "warn SLO to hold depths inside it")
    ap.add_argument("--smoke", action="store_true",
                    help="small-N broker-mode smoke arm for CI: tiny "
                         "corpus, pools + batching on, does NOT "
                         "overwrite SCALE_BROKER.json")
    ap.add_argument("--worker", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--tmp", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=5899,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.worker:
        return _worker(pathlib.Path(args.tmp), args.port, args.worker,
                       args.workers, args.prefetch, args.watermark)

    # Durability-contract preflight: the host pipeline is exactly the
    # plane the dura rule family governs (commit/publish windows, ack
    # swallows, ledger hygiene), so gate the run on it the way
    # bench.py's engine presets gate on shardcheck — same rc-2/
    # ok:false artifact contract, BENCH_PREFLIGHT=0 skips, analyzer
    # infra trouble warns and continues.
    import bench as _bench

    artifact = _bench.duracheck_preflight(
        paths=["copilot_for_consensus_tpu/bus",
               "copilot_for_consensus_tpu/services"])
    if artifact is not None:
        print(json.dumps(artifact))
        return 2

    if args.smoke:
        args.bus = "broker"
        args.messages = min(args.messages, 400)
        args.archives = args.archives or 2
        args.workers = args.workers or "2"
        args.prefetch = args.prefetch or 8

    from copilot_for_consensus_tpu.services.runner import build_pipeline

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="scale-bench-"))
    if args.bus == "broker-raw":
        # no-op events only: the synthetic archives are never read
        try:
            return _broker_raw(args, tmp)
        finally:
            if not args.keep_db:
                import shutil

                shutil.rmtree(tmp, ignore_errors=True)
    n_arch = args.archives or max(1, args.messages // 2500)
    per = args.messages // n_arch
    t0 = time.monotonic()
    for a in range(n_arch):
        n = per if a < n_arch - 1 else args.messages - per * (n_arch - 1)
        synthetic_mbox(tmp / f"archive-{a}.mbox", n, args.thread_size,
                       seed=a, prefix=f"a{a}")
    gen_s = time.monotonic() - t0

    if args.bus == "broker":
        try:
            return _broker_mode(args, tmp, n_arch, gen_s)
        finally:
            if not args.keep_db:
                import shutil

                shutil.rmtree(tmp, ignore_errors=True)

    p = build_pipeline({
        "document_store": {"driver": "sqlite",
                           "path": str(tmp / "docs.sqlite3")},
        # The production ANN driver: inverted-index metadata filters, so
        # per-thread context queries stay O(candidates) not O(corpus).
        "vector_store": {"driver": "tpu", "dtype": "float32"},
        "embedding": ({"driver": "tpu"} if args.embedding == "tpu"
                      else {"driver": "mock", "dimension": 384}),
        "llm": {"driver": "mock"},
    })
    # Distributed tracing (obs/trace.py): size the span ring to the
    # corpus so tools/tracepath can attribute per-stage latency and
    # name the bottleneck over the whole run.
    from copilot_for_consensus_tpu.obs import trace as trace_mod

    trace_mod.configure(capacity=min(500_000,
                                     args.messages * 40 + 20_000))
    metrics = _SamplingMetrics(p.metrics)
    for svc in p.services:
        svc.metrics = metrics
    for a in range(n_arch):
        p.ingestion.create_source({
            "source_id": f"bench-{a}", "name": f"bench-{a}",
            "fetcher": "local", "location": str(tmp / f"archive-{a}.mbox")})

    t1 = time.monotonic()
    for a in range(n_arch):
        p.ingestion.trigger_source(f"bench-{a}")
    p.drain()
    stats = p.reporting.stats()
    run_s = time.monotonic() - t1

    ok = True
    for stage, slo in SLOS.items():
        p95 = _p95(metrics.samples.get(f"{stage}_handle_seconds", []))
        good = p95 < slo
        ok &= good
        print(json.dumps({"stage": stage, "p95_s": round(p95, 4),
                          "slo_s": slo, "ok": good}))

    # Per-stage queue-wait vs service-time attribution + the named
    # bottleneck, from the pipeline trace (tools/tracepath.py).
    from copilot_for_consensus_tpu.tools import tracepath

    tp = tracepath.analyze(trace_mod.get_collector().spans())
    print(json.dumps({
        "stage": "tracepath",
        "stage_p95_s": tp["stage_p95_s"],
        "queue_wait_p95_s": tp["queue_wait_p95_s"],
        "bottleneck_stage": tp["bottleneck_stage"],
        "orphan_spans": tp["orphan_spans"],
        "traces": tp["traces"],
    }))

    # Reporting read path on the full corpus (reference SLO p95 < 0.5s).
    # One warmup query first: the semantic search path jit-compiles the
    # ANN scan on first use (one-time cost, not steady-state latency).
    p.reporting.search_reports("warmup", limit=1)
    api_samples = []
    for _ in range(20):
        t = time.monotonic()
        p.reporting.get_reports(limit=20)
        api_samples.append(time.monotonic() - t)
    for _ in range(5):
        t = time.monotonic()
        p.reporting.search_reports("consensus draft", limit=10)
        api_samples.append(time.monotonic() - t)
    api_p95 = _p95(api_samples)
    good = api_p95 < REPORTING_API_SLO
    ok &= good
    print(json.dumps({"stage": "reporting_api", "p95_s": round(api_p95, 4),
                      "slo_s": REPORTING_API_SLO, "ok": good}))

    print(json.dumps({
        "stage": "total", "messages": args.messages,
        "generate_s": round(gen_s, 1), "pipeline_s": round(run_s, 1),
        "messages_per_s": round(args.messages / max(run_s, 1e-9), 1),
        "stats": stats, "ok": ok,
    }))
    if not args.keep_db:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
