#!/usr/bin/env python3
"""End-to-end summarization latency on the real TPU serving engines.

Measures what the reference's summarization SLO alerts watch
(``slo_latency.yml``: summarization p95 < 30 s, p99 < 120 s) and
BASELINE.md's "p50 summary latency" metric, through the REAL pipeline:
fixture mbox → parse → chunk → TPU embed → retrieve → TPU Mistral-class
generate → report. Weights are random (text quality is exercised by the
checkpoint golden-logit tests); latency and throughput are real.

    python scripts/bench_summarize.py            # on the TPU chip
    python scripts/bench_summarize.py --model tiny --threads 8   # smoke

Prints one JSON line with per-summary latency percentiles and
aggregate threads/min.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="mistral-7b")
    ap.add_argument("--threads", type=int, default=96,
                    help="how many threads to summarize (fixture threads "
                         "are replicated to reach this)")
    ap.add_argument("--max-new-tokens", type=int, default=160)
    ap.add_argument("--num-slots", type=int, default=64)
    args = ap.parse_args()

    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
        require_accelerator,
    )
    from copilot_for_consensus_tpu.services.runner import build_pipeline

    dev = require_accelerator("scripts/bench_summarize.py")
    enable_compile_cache()
    t0 = time.monotonic()
    p = build_pipeline({
        "embedding": {"driver": "tpu", "model": "minilm-l6"},
        "llm": {"driver": "tpu", "model": args.model,
                "num_slots": args.num_slots,
                "max_len": 1024,
                "kv_dtype": "float8_e4m3fn",
                "max_new_tokens": args.max_new_tokens,
                # async submission keeps the decode slots full even
                # though bus events arrive one at a time (without this
                # the wall time is ~7 s x threads, slot count moot)
                "pipelined": True},
    })
    build_s = time.monotonic() - t0
    print(f"pipeline with TPU engines built in {build_s:.1f}s",
          file=sys.stderr)

    # Replicate the fixture's threads by rewriting message-ids/subjects
    # so each copy forms distinct threads.
    mbox = (REPO / "tests" / "fixtures" / "ietf-sample.mbox").read_text()
    copies = []
    n_copies = max(1, -(-args.threads // 3))      # fixture has 3 threads
    for i in range(n_copies):
        copies.append(mbox.replace("@example.org", f"@r{i}.example.org")
                          .replace("@example.net", f"@r{i}.example.net")
                          .replace("@example.com", f"@r{i}.example.com")
                          .replace("@example.io", f"@r{i}.example.io")
                          .replace("@nowhere.org", f"@r{i}.nowhere.org")
                          .replace("Subject: ", f"Subject: [r{i}] "))
    big = "\n".join(copies)
    src_dir = pathlib.Path("/tmp/bench_summarize")
    src_dir.mkdir(exist_ok=True)
    (src_dir / "archive.mbox").write_text(big)

    p.ingestion.create_source({
        "source_id": "bench", "name": "bench", "fetcher": "local",
        "location": str(src_dir / "archive.mbox")})

    t0 = time.monotonic()
    stats = p.ingest_and_run("bench")
    wall = time.monotonic() - t0

    lats = sorted(s.get("generation_seconds", 0.0)
                  for s in p.store.query_documents("summaries"))
    n = len(lats)
    pct = (lambda q: lats[min(n - 1, int(q * n))]) if n else (lambda q: 0)
    out = {
        "metric": f"{args.model} end-to-end thread summarization "
                  f"({n} threads, TPU embed+generate)",
        "value": round(n / wall * 60, 2),
        "unit": "threads/min",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "p50_summary_latency_s": round(pct(0.50), 2),
        "p95_summary_latency_s": round(pct(0.95), 2),
        "pipeline_wall_s": round(wall, 1),
        "engine_build_s": round(build_s, 1),
        "stats": stats,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
