#!/usr/bin/env python3
"""Benchmark: embedding encoder throughput (texts/s) on the chip.

The second BASELINE.json metric ("embed msgs/sec") next to bench.py's
decode number. The reference embeds ONE text per ``embed()`` call inside
its batch loop (``embedding/app/service.py:284,393`` — no cross-text
batching); this engine tokenizes, bucket-batches, and runs single MXU
passes, so the honest comparison is aggregate texts/s at pipeline-like
text lengths.

Run on real TPU (no JAX_PLATFORMS override). Prints ONE JSON line.
Env knobs: BENCH_TEXTS (default 4096), BENCH_WORDS (words/text, 90),
BENCH_BATCH (engine batch, 2048).
"""

from __future__ import annotations

import json
import os
import pathlib
import random
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_WORDS = ("consensus rough draft review thread mail archive protocol "
          "header token budget window chunk merge split rfc discussion "
          "agree disagree object support propose revise working group").split()


def main() -> None:
    n_texts = int(os.environ.get("BENCH_TEXTS", "4096"))
    words = int(os.environ.get("BENCH_WORDS", "90"))
    batch = int(os.environ.get("BENCH_BATCH", "2048"))

    from copilot_for_consensus_tpu.engine.embedding import EmbeddingEngine
    from copilot_for_consensus_tpu.models import encoder_config
    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
        require_accelerator,
    )

    dev = require_accelerator("scripts/bench_embed.py")
    enable_compile_cache()
    cfg = encoder_config("minilm-l6")
    log(f"device: {dev.device_kind} ({dev.platform}), encoder: {cfg.name} "
        f"d={cfg.d_model} L={cfg.n_layers}")
    eng = EmbeddingEngine(cfg, batch_size=batch)

    rng = random.Random(0)
    texts = [" ".join(rng.choice(_WORDS) for _ in range(words))
             for _ in range(n_texts)]

    t0 = time.monotonic()
    eng.embed_batch(texts[:batch])       # compile warmup
    log(f"warmup (compile) {time.monotonic() - t0:.1f}s")

    t0 = time.monotonic()
    vecs = eng.embed_batch(texts)
    elapsed = time.monotonic() - t0
    assert vecs.shape == (n_texts, cfg.d_model)
    print(json.dumps({
        "metric": f"{cfg.name} embedding throughput "
                  f"(1 chip, batch {batch}, ~{words}-word texts)",
        "value": round(n_texts / elapsed, 1),
        "unit": "texts/s",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }))


if __name__ == "__main__":
    main()
