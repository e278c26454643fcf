"""Benchmark: Mistral-7B-class continuous-batching decode throughput.

Run on the chip with no JAX_PLATFORMS override. Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": N,
     "ok": true, "platform": "tpu", "device_kind": "...",
     "device_count": 1}

Baseline: the reference's best published generation number — Mistral-7B
via Ollama on an RTX 4090 at 150–200 tok/s (midpoint 175; reference
``docs/operations/ollama-gpu-setup.md:151``, mirrored in BASELINE.md).
The reference path serves ONE blocking request at a time
(``local_llm_summarizer.py:106-115``); ours decodes a continuous batch,
so aggregate tok/s is the apples-to-apples serving-throughput number.

One process per chip: the chip belongs to the process that first
touches JAX, so the engine presets run entirely in THIS process and
start nothing that needs the device. Every other row is its own
command (``BENCH_PRESET=rag2k python bench.py``,
``BENCH_PRESET=cap3072 python bench.py``, ``scripts/bench_embed.py``),
run one after another. Only the two
presets whose parent never imports jax (``multichip_serving``: virtual
CPU devices pinned per child; ``pipeline_chaos``: host-only) spawn
children.

No fallback: without a TPU — and without an explicit
``JAX_PLATFORMS`` — the engine presets refuse to start
(``parallel.mesh.require_accelerator``). Any failure (refusal,
preflight violation, a gate preset's ``*_ok`` verdict false, a raised
exception) exits non-zero; ``ok: false`` never rides exit code 0.

Env knobs: BENCH_MODEL (default mistral-7b), BENCH_SLOTS, BENCH_MAX_LEN,
BENCH_PROMPT_LEN, BENCH_NEW_TOKENS, BENCH_SPEC_DECODE (speculative
decoding; BENCH_PRESET=spec_decode sets it with copy-heavy prompts),
BENCH_TELEMETRY (engine flight recorder,
default 1 — the artifact's TTFT/ITL/occupancy columns come from it;
set 0 for the overhead-measurement arm of BENCH_PRESET=decode_heavy),
BENCH_SHIP (telemetry spool shipping during the timed run, obs/ship.py,
default 1; set 0 for the off arm of the shipping-overhead comparison).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

BASELINE_TOK_S = 175.0  # Ollama Mistral-7B on RTX 4090 (midpoint 150-200)

REPO = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


PRESETS = {
    # The pipeline's real serving shape: the orchestrator budgets ~3000
    # context tokens per summary (reference orchestrator/app/service.py
    # :57) and asks for ~160 new tokens — a prefill-heavy workload. At
    # 2048-token prompts HBM caps concurrent streams well below the
    # short-prompt bench (the KV cache is 9x larger per slot), so slots
    # drop to 32 and the honest headline is TOTAL processed tokens/s
    # (prompt + generated), reported alongside decode-only tok/s.
    # windows_per_dispatch stays 1 here: XLA compiles the long-extent
    # multi-window chain pathologically (28.5 s vs 6.2 s decode for the
    # same 160 steps), and at 38 ms/step the per-dispatch sync is noise.
    "rag2k": {"BENCH_PROMPT_LEN": "2048", "BENCH_MAX_LEN": "2304",
              "BENCH_NEW_TOKENS": "160", "BENCH_SLOTS": "32",
              "BENCH_DECODE_WINDOW": "32",
              "BENCH_WINDOWS_PER_DISPATCH": "1"},
    # int4 capacity envelope: 32 whole-thread streams at 3072-token
    # context fit on one chip ONLY at int4 weights (int8 OOMs by ~191MB
    # — docs/PERF.md r4 capacity proof). This is the configuration the
    # long-context summarization engine serves.
    "cap3072": {"BENCH_PROMPT_LEN": "2816", "BENCH_MAX_LEN": "3072",
                "BENCH_NEW_TOKENS": "160", "BENCH_SLOTS": "32",
                "BENCH_WEIGHT_DTYPE": "int4", "BENCH_ADMIT_TOKENS": "8192",
                "BENCH_DECODE_WINDOW": "32",
                "BENCH_WINDOWS_PER_DISPATCH": "1"},
    # Prefix KV-cache reuse (engine/prefix_cache.py): every stream's
    # prompt opens with the same 384-token span (the RAG workload's
    # shared system prompt + template head); the radix cache seeds it
    # from the block pool and prefills only the 128-token tail. The
    # artifact adds prefix_hit_rate and prefill_tokens_saved (timed-run
    # deltas) next to the throughput number.
    "shared_prefix": {"BENCH_PROMPT_LEN": "512", "BENCH_MAX_LEN": "768",
                      "BENCH_NEW_TOKENS": "96", "BENCH_SLOTS": "32",
                      "BENCH_SHARED_PREFIX": "384",
                      "BENCH_PREFIX_BLOCKS": "64",
                      "BENCH_DECODE_WINDOW": "32",
                      "BENCH_WINDOWS_PER_DISPATCH": "1"},
    # Speculative decoding (engine spec_decode): copy-heavy
    # summarization-shaped prompts — each prompt's back half repeats
    # spans of its front half, the way abstractive summaries and RAG
    # answers copy quotes/names/draft identifiers verbatim — so the
    # prompt-lookup index drafts from the stream's own context and the
    # verify dispatch scores k+1 positions per weight pass. The
    # artifact adds draft_hit_rate, mean_accepted_per_step and
    # tokens_per_weight_pass (timed-run deltas) next to throughput.
    "spec_decode": {"BENCH_PROMPT_LEN": "512", "BENCH_MAX_LEN": "896",
                    "BENCH_NEW_TOKENS": "192", "BENCH_SLOTS": "32",
                    "BENCH_SPEC_DECODE": "1",
                    "BENCH_DECODE_WINDOW": "8",
                    "BENCH_WINDOWS_PER_DISPATCH": "1"},
    # Decode-dominated shape: short prompts, long generations — the
    # workload where per-dispatch host overhead (and therefore the
    # telemetry layer's host-side bookkeeping) is the largest fraction
    # of wall time. This is the telemetry-overhead gate's preset: run
    # it with BENCH_TELEMETRY=1 (default) vs 0 and the tok/s delta is
    # the recorder's true cost; the budget is <1%
    # (docs/OBSERVABILITY.md).
    "decode_heavy": {"BENCH_PROMPT_LEN": "64", "BENCH_MAX_LEN": "512",
                     "BENCH_NEW_TOKENS": "384",
                     "BENCH_DECODE_WINDOW": "32",
                     "BENCH_WINDOWS_PER_DISPATCH": "1"},
    # SLO-aware scheduler (engine/scheduler.py): adversarial mixed
    # traffic — long batch-lane prompts (the ITL killers), short
    # interactive chats from a second tenant, and embed bursts riding
    # the same host loop. The artifact runs the SAME mix twice —
    # scheduler ON (chunked prefill + DRR + shedding) and OFF (FIFO) —
    # and records TTFT p99 / ITL p95 against the declared SLO bounds
    # both ways, plus shed_rate and fairness_jain_index; greedy
    # per-request outputs must be bit-identical between the arms —
    # which requires kv_dtype == compute dtype: a chunk continuation
    # re-reads earlier chunks' KV FROM the cache, so an fp8 cache
    # would perturb the long prompts' logits vs the monolithic wave
    # (same argument as prefix-cache seeding; docs/SCHEDULER.md).
    # Fault-injected, self-healing serving (engine/faults.py +
    # engine/supervisor.py): a mixed_traffic-style workload (short
    # chats + long prompts, spec decode ON) runs twice — fault-free
    # baseline, then under a seeded three-phase fault script
    # (transient exceptions on every dispatch kind, ONE hang past the
    # watchdog deadline, one persistent verify fault that trips the
    # spec breaker). The gate: ZERO lost handles (every submit
    # resolves with a Completion or a structured error carrying a
    # correlation id), surviving greedy outputs bit-identical to the
    # baseline, and the recovery counters within budget — the
    # recovered/replayed/failed/breaker_trips columns + chaos_ok.
    # COMPUTE dtype is pinned to float32 (kv matches automatically):
    # a replayed request's first fresh token comes from the
    # continuation PREFILL's logits where the baseline's came from
    # DECODE logits at the same position, and those two program
    # families only agree bit-for-bit when rounding can't flip the
    # argmax — measured exact at f32, off-by-low-bits at bf16. This
    # is a correctness gate, not a throughput shape; mixed_traffic's
    # kv-dtype pin is the same move one level down
    # (docs/RESILIENCE.md#replay-semantics).
    # Paged KV capacity (GenerationEngine(kv_pool_blocks=...) +
    # ops/paged_attention.py): many concurrent short-decode streams
    # whose prompts share a 128-token head. The pool is sized at the
    # contiguous engine's 128-slot HBM budget (1024 blocks x 64 =
    # 65536 cache positions == 128 slots x max_len 512), but slots
    # stop reserving max_len each: blocks allocate on demand, prefix
    # hits admit by POINTER (table append, zero copy), so the same
    # memory sustains MORE concurrent streams than the contiguous
    # cache's 128-slot ceiling. Columns: max_concurrent_streams (the
    # engine's peak active ledger — the gate is > 128),
    # kv_pool_fragmentation (reserved-but-dead fraction of allocated
    # blocks), zero_copy_hit_rate (pointer admissions / paged
    # admissions; > 0 proves the no-gather hit path).
    "paged_capacity": {"BENCH_PROMPT_LEN": "192", "BENCH_MAX_LEN": "512",
                       "BENCH_NEW_TOKENS": "64", "BENCH_SLOTS": "256",
                       "BENCH_PAGED": "1",
                       "BENCH_KV_POOL_BLOCKS": "1024",
                       "BENCH_SHARED_PREFIX": "128",
                       "BENCH_PREFIX_BLOCKS": "64",
                       "BENCH_DECODE_WINDOW": "32",
                       "BENCH_WINDOWS_PER_DISPATCH": "1",
                       # kernel route (ISSUE 16): the headline arm lets
                       # the engine auto-select (Pallas on TPU, XLA
                       # reference elsewhere) and the second arm pins
                       # kv_kernel="pallas" to report the gather-free
                       # route's tok/s next to it (kernel_route column)
                       "BENCH_KV_KERNEL": "auto",
                       "BENCH_KV_KERNEL_ARM": "1"},
    "chaos": {"BENCH_MAX_LEN": "512", "BENCH_SLOTS": "16",
              "BENCH_CHAOS_DTYPE": "float32",
              "BENCH_NEW_TOKENS": "48",
              "BENCH_DECODE_WINDOW": "8",
              "BENCH_WINDOWS_PER_DISPATCH": "1",
              "BENCH_SPEC_DECODE": "1",
              "BENCH_CHAOS_CHAT": "24", "BENCH_CHAOS_CHAT_LEN": "96",
              "BENCH_CHAOS_LONG": "6", "BENCH_CHAOS_LONG_LEN": "320",
              "BENCH_CHAOS_SEED": "7",
              "BENCH_CHAOS_HANG_S": "12",
              "BENCH_CHAOS_DECODE_DEADLINE_S": "6"},
    # Pipeline-wide fault plane (bus/faults.py + the broker publish
    # outbox / depth-watermark backpressure / poison quarantine): a
    # HOST-ONLY gate — mock inference drivers, durable zmq broker, the
    # full parse→chunk→embed→summarize→report pipeline in one process
    # with one consume loop per service. Three arms: sustained-overload
    # with backpressure OFF then ON (the SCALE_BROKER failure mode —
    # drain deliberately slower than supply via BENCH_PIPE_DRAG_S — the
    # OFF arm must flood ≥2x past the scaled warn SLO, the ON arm must
    # hold under it), then the seeded STORM over a scaled-down
    # SCALE_BROKER corpus: broker kill/restart mid-run, transient
    # store/vector/archive faults, consumer crash-after-work (ack
    # faults → lease redelivery), consume-loop outages (fetch faults),
    # scripted publish faults (outbox park + in-order replay), and
    # schema-invalid poison envelopes. The gate (pipeline_chaos_ok):
    # zero threads without a summary, zero duplicate terminal
    # artifacts (at-least-once + idempotent ids holds), exactly the
    # injected poison quarantined with a structured reason, parked
    # publishes replayed, final depths inside the SLO. The warn SLO
    # (1000 at the 100k corpus) scales to the corpus; the watermark is
    # half of it. Unlike the engine chaos gate there is no
    # bit-identity arm: pipeline concurrency makes fault ORDER
    # scheduling-dependent — the assertions hold under any
    # interleaving, which is the actual contract
    # (docs/RESILIENCE.md#pipeline-resilience).
    "pipeline_chaos": {"BENCH_PIPE_MESSAGES": "1200",
                       "BENCH_PIPE_ARCHIVES": "8",
                       "BENCH_PIPE_FLOOD_MESSAGES": "1000",
                       "BENCH_PIPE_FLOOD_ARCHIVES": "4",
                       "BENCH_PIPE_THREAD_SIZE": "8",
                       "BENCH_PIPE_SEED": "11",
                       "BENCH_PIPE_DRAG_S": "0.01",
                       "BENCH_PIPE_WARN_SLO": "32",
                       "BENCH_PIPE_POISON": "5",
                       "BENCH_PIPE_BUDGET_S": "420",
                       # stage scale-out (ISSUE 11): pools > 1 so the
                       # delivery contracts (lost 0 / dup 0 / exact
                       # quarantine) are proven UNDER competing
                       # consumers + batched waves, not single-threaded
                       "BENCH_PIPE_WORKERS": "2",
                       # process-kill phase (ISSUE 12): a REAL child
                       # process SIGKILLed after step N of a journaled
                       # engine storm, then warm-restarted from the
                       # journal — gates lost 0 / duplicated 0 /
                       # journal_replayed > 0 / bit-identical (f32)
                       "BENCH_KILL_REQUESTS": "12",
                       "BENCH_KILL_NEW_TOKENS": "24",
                       "BENCH_KILL_STEP": "8",
                       "BENCH_KILL_SEED": "7",
                       # graceful-drain arm: a fault-free run drained
                       # mid-wave (readyz 503 → pools stop → engines
                       # drain → outbox flush) then warm-resumed —
                       # gates zero shutdown-caused redeliveries
                       "BENCH_PIPE_DRAIN_MESSAGES": "400",
                       "BENCH_PIPE_DRAIN_ARCHIVES": "2"},
    # Multi-chip paged serving (ISSUE 15): the mesh-sharded block pool
    # + disaggregated prefill/decode roles, verified on VIRTUAL CPU
    # devices (children force JAX_PLATFORMS=cpu +
    # --xla_force_host_platform_device_count, the same platform the
    # test suite and shardcheck use — docs/PERF.md#multi-chip-serving
    # is honest that tok/s SCALING on virtual devices measures
    # partitioning overhead, not speedup; real-mesh numbers need real
    # chips). Two arms: tok/s + TTFT across 1/2/4/8 virtual chips
    # (scaling_efficiency column), and a disaggregated
    # prefill/decode-role split (two engines, two threads, block-
    # granular KV handoff) whose decode ITL p95 must stay within
    # BENCH_MC_ITL_TOL of the co-located arm's WHILE prefill waves
    # keep arriving.
    "multichip_serving": {"BENCH_MC_CHIPS": "1,2,4,8",
                          "BENCH_MC_TP": "2",
                          "BENCH_MODEL": "tiny",
                          "BENCH_SLOTS": "8",
                          "BENCH_MAX_LEN": "128",
                          "BENCH_PROMPT_LEN": "32",
                          "BENCH_NEW_TOKENS": "16",
                          "BENCH_PREFILL_CHUNK": "16",
                          "BENCH_KV_POOL_BLOCKS": "64",
                          "BENCH_QUANTIZE": "0",
                          "BENCH_KV_DTYPE": "float32",
                          "BENCH_DECODE_WINDOW": "4",
                          "BENCH_MC_LONG_NEW": "48",
                          "BENCH_MC_ARRIVALS": "2",
                          "BENCH_MC_ITL_TOL": "1.5",
                          # kernel route (ISSUE 16): scale children
                          # auto-select (reference on virtual CPU
                          # devices); one extra child at the top chip
                          # count pins "pallas" so the mesh kernel
                          # route is exercised + reported every round
                          "BENCH_KV_KERNEL": "auto"},
    "mixed_traffic": {"BENCH_MAX_LEN": "1024", "BENCH_SLOTS": "32",
                      "BENCH_KV_DTYPE": "bfloat16",
                      "BENCH_NEW_TOKENS": "64",
                      "BENCH_DECODE_WINDOW": "8",
                      "BENCH_WINDOWS_PER_DISPATCH": "1",
                      "BENCH_MIX_CHAT": "48",
                      "BENCH_MIX_CHAT_LEN": "96",
                      "BENCH_MIX_LONG": "12",
                      "BENCH_MIX_LONG_LEN": "832",
                      "BENCH_MIX_EMBED_TEXTS": "192",
                      "BENCH_CHUNK_TOKENS": "128",
                      "BENCH_TTFT_SLO": "2.0",
                      "BENCH_ITL_SLO": "0.25"},
    # ANN retrieval gate (ISSUE 19): one seeded clustered corpus
    # ingested into BOTH vector-store routes — flat (the exact-scan
    # recall oracle) and ivf (the sharded two-tier index) — then the
    # same query set timed through each. The artifact carries
    # recall@10 of ivf against the flat oracle, batched QPS and
    # single-query p50/p95 per route, and lists_scanned_frac (the
    # nprobe/nlist work-saving claim: the ivf route must answer from
    # ≤15% of the posting lists while holding recall ≥0.95). Default
    # corpus is the million-chunk target; the tier-1 smoke arm runs
    # the same gate at 10k (tests/test_vectorstore_ann.py).
    "ann_retrieval": {"BENCH_ANN_N": "1000000",
                      "BENCH_ANN_DIM": "64",
                      "BENCH_ANN_CLUSTERS": "1024",
                      "BENCH_ANN_QUERIES": "256",
                      "BENCH_ANN_BATCH": "64",
                      "BENCH_ANN_TOPK": "10",
                      "BENCH_ANN_NLIST": "0",
                      "BENCH_ANN_NPROBE": "16",
                      "BENCH_ANN_MESH": "none",
                      "BENCH_ANN_SEED": "0"},
}


#: the verdict flag each GATE preset's artifact carries: a False there
#: is a failed run (ok:false, exit 1), not one more column
PRESET_GATES = {
    "chaos": "chaos_ok",
    "pipeline_chaos": "pipeline_chaos_ok",
    "multichip_serving": "multichip_ok",
    "ann_retrieval": "ann_ok",
}

#: contract modules whose jitted entrypoints each preset exercises —
#: the shardcheck preflight traces exactly these before the timed run.
PRESET_CONTRACT_MODULES = {
    "": ["copilot_for_consensus_tpu.engine.generation"],
    "rag2k": ["copilot_for_consensus_tpu.engine.generation"],
    "cap3072": ["copilot_for_consensus_tpu.engine.generation"],
    "shared_prefix": ["copilot_for_consensus_tpu.engine.generation",
                      "copilot_for_consensus_tpu.engine.prefix_cache"],
    # the generation contract declares the paged dispatch family
    # (admit/seeded/decode/verify/chunk over the block pool: donation
    # aliases on both pool halves, the engine.generation-kv layout
    # group, the engine.generation-kv-table block-table group)
    "paged_capacity": ["copilot_for_consensus_tpu.engine.generation",
                       "copilot_for_consensus_tpu.engine.prefix_cache"],
    # the generation contract already declares the _verify entrypoint
    # (donation alias, kv-layout group, draft-length bucket coverage)
    "spec_decode": ["copilot_for_consensus_tpu.engine.generation"],
    "decode_heavy": ["copilot_for_consensus_tpu.engine.generation"],
    # the scheduler contract traces the chunked-prefill continuation
    # dispatch (donation alias, engine.generation-kv layout group,
    # chunk-width bucket coverage)
    "mixed_traffic": ["copilot_for_consensus_tpu.engine.generation",
                      "copilot_for_consensus_tpu.engine.scheduler"],
    # the chaos arm exercises every generation dispatch kind (the
    # fault plane wraps them all); the contract set is the generation
    # module's — faults fire strictly at the host boundary and add no
    # jitted entrypoints of their own
    "chaos": ["copilot_for_consensus_tpu.engine.generation"],
    # host-only pipeline gate (mock inference drivers): no jitted
    # entrypoints at all — the preflight skips instead of tracing the
    # default engine set a pipeline storm never dispatches to
    "pipeline_chaos": [],
    # the generation contract now declares the MESH-sharded paged
    # dispatch family (admit/seeded/decode/verify/chunk through the dp
    # shard_map indirection + the KV-handoff import: donation on both
    # pool halves, the shared engine.generation-kv layout group, the
    # pool's PartitionSpec divisibility, block-table dtype under dp);
    # mesh/sharding carry the serving-mesh and rules contracts the
    # sharded engine builds on
    "multichip_serving": ["copilot_for_consensus_tpu.engine.generation",
                          "copilot_for_consensus_tpu.parallel.mesh",
                          "copilot_for_consensus_tpu.parallel.sharding"],
    # the vectorstore contract declares the fused ivf search dispatch
    # (peak-memory budget; zero-collective budget on the mesh-sharded
    # variant), the donated spill/posting-list patch programs, and the
    # pow2 k-bucketed flat query program-cache family
    "ann_retrieval": ["copilot_for_consensus_tpu.vectorstore.tpu"],
}


# -- artifact columns ---------------------------------------------------
#
# Each preset's extra columns are assembled by a dedicated helper so the
# column set is a TESTABLE contract (tests/test_bench.py): the telemetry
# tentpole must not rename or drop the columns earlier rounds' artifacts
# established (prefix_hit_rate / draft_hit_rate / ...), and the new
# flight-recorder columns must keep their names for the next round.


def prefix_columns(ps0: dict, ps1: dict) -> dict:
    """shared_prefix columns: timed-run deltas of the engine's
    prefix-cache ledger (the warmup's cold misses are the cache
    filling, not the steady state the preset measures)."""
    lookups = ps1["lookups"] - ps0["lookups"]
    hits = ps1["hits"] - ps0["hits"]
    return {
        "prefix_hit_rate": round(hits / lookups, 3) if lookups else 0.0,
        "prefill_tokens_saved": (ps1["prefill_tokens_saved"]
                                 - ps0["prefill_tokens_saved"]),
        "prefill_tokens": ps1["prefill_tokens"] - ps0["prefill_tokens"],
    }


def spec_columns(ss0: dict, ss1: dict) -> dict:
    """spec_decode columns: timed-run deltas of the engine's
    speculative-decoding ledger."""
    lookups = ss1["lookups"] - ss0["lookups"]
    hits = ss1["hits"] - ss0["hits"]
    acc = ss1["accepted_tokens"] - ss0["accepted_tokens"]
    rows = ss1["verify_rows"] - ss0["verify_rows"]
    rt = ss1["weight_row_tokens"] - ss0["weight_row_tokens"]
    rp = ss1["weight_row_passes"] - ss0["weight_row_passes"]
    return {
        "draft_hit_rate": round(hits / lookups, 3) if lookups else 0.0,
        "mean_accepted_per_step": round(acc / rows, 3) if rows else 0.0,
        "tokens_per_weight_pass": round(rt / rp, 3) if rp else 0.0,
    }


def paged_columns(kv0: dict, kv1: dict) -> dict:
    """paged_capacity columns: the engine's paged-KV ledger
    (``GenerationEngine.kv_pool_stats``). ``zero_copy_hit_rate`` is a
    timed-run delta (the warmup's cold misses are the trie filling);
    ``max_concurrent_streams`` and ``kv_pool_fragmentation`` read the
    engine-lifetime peak / final allocation state."""
    admits = kv1.get("paged_admits", 0) - kv0.get("paged_admits", 0)
    hits = kv1.get("zero_copy_admits", 0) - kv0.get("zero_copy_admits",
                                                    0)
    return {
        "max_concurrent_streams": int(kv1.get("peak_active", 0)),
        "kv_pool_fragmentation": float(
            kv1.get("fragmentation_ratio", 0.0)),
        "zero_copy_hit_rate": round(hits / admits, 3) if admits
        else 0.0,
    }


def kernel_route_columns(route: str, ref_tok_s: float,
                         kernel_tok_s: float) -> dict:
    """Kernel-route arm columns (ISSUE 16): which paged-attention
    dispatch route the arm's engine actually resolved (``kernel``
    proves the Pallas no-gather route compiled, not the XLA
    reference), its throughput, and the ratio against the headline
    arm. Zero-safe: a failed headline arm reports delta 0.0 instead
    of dividing by zero. On CPU the kernel runs in interpret mode, so
    the delta there measures the interpreter, not the gather
    elimination — docs/PERF.md#kernel-route."""
    return {
        "kv_route": str(route),
        "kernel_tok_s": round(float(kernel_tok_s), 2),
        "kernel_tok_s_delta": round(kernel_tok_s / ref_tok_s, 3)
        if ref_tok_s else 0.0,
    }


def sched_columns(summary: dict, sched_stats: dict) -> dict:
    """mixed_traffic columns: the SLO latencies from the engine's own
    telemetry summary plus the scheduler's shed/fairness ledger —
    exactly the four numbers ISSUE 6 gates on."""
    return {
        "ttft_p99_s": summary.get("ttft_p99_s", 0.0),
        "itl_p95_s": summary.get("itl_p95_s", 0.0),
        "shed_rate": round(sched_stats.get("shed_rate", 0.0), 4),
        "fairness_jain_index": sched_stats.get("fairness_jain_index",
                                               1.0),
    }


def chaos_columns(recovery: dict) -> dict:
    """chaos columns: the runner's recovery ledger
    (``AsyncEngineRunner.recovery_stats``) — how many requests came
    back via replay, how many replays ran, how many spent their budget
    (structured EngineFailed), and the watchdog/breaker activity."""
    return {
        "recovered": int(recovery.get("recovered", 0)),
        "replayed": int(recovery.get("replayed", 0)),
        "failed": int(recovery.get("failed", 0)),
        "breaker_trips": int(recovery.get("breaker_trips", 0)),
        "watchdog_trips": int(recovery.get("watchdog_trips", 0)),
    }


def pipeline_chaos_columns(audit: dict) -> dict:
    """pipeline_chaos columns: the storm audit ledger — work lost /
    duplicated / quarantined, the publish-outbox ride-through evidence,
    and the two overload arms' peak depths — the cross-round contract
    the pipeline fault plane gates on (tests/test_bench.py)."""
    return {
        "lost": int(audit.get("lost", 0)),
        "duplicated": int(audit.get("duplicated", 0)),
        "quarantined": int(audit.get("quarantined", 0)),
        "replayed_publishes": int(audit.get("replayed_publishes", 0)),
        "redelivered": int(audit.get("redelivered", 0)),
        "recovered_by_sweep": int(audit.get("recovered_by_sweep", 0)),
        "max_depth_backpressure_on": int(
            audit.get("max_depth_backpressure_on", 0)),
        "max_depth_backpressure_off": int(
            audit.get("max_depth_backpressure_off", 0)),
        "final_depth_max": int(audit.get("final_depth_max", 0)),
        # distributed-tracing columns (obs/trace.py + tools/tracepath):
        # per-stage p95 service time and queue wait from the overload
        # arm's stage spans, the named bottleneck stage, and the storm
        # arm's orphan-span audit (zero is the gate)
        "stage_p95_s": dict(audit.get("stage_p95_s", {})),
        "queue_wait_p95_s": dict(audit.get("queue_wait_p95_s", {})),
        "bottleneck_stage": str(audit.get("bottleneck_stage", "")),
        "orphan_spans": int(audit.get("orphan_spans", 0)),
        # process-lifecycle columns (engine/journal.py +
        # services/lifecycle.py, ISSUE 12): journal rows replayed by
        # the kill phase's warm restart, and broker redeliveries
        # CAUSED by the graceful-drain arm's shutdown (zero is the
        # gate — a clean drain nacks nothing)
        "journal_replayed": int(audit.get("journal_replayed", 0)),
        "shutdown_redeliveries": int(
            audit.get("shutdown_redeliveries", 0)),
        # cross-process telemetry columns (obs/ship.py, ISSUE 20): the
        # SIGKILLed child's committed spool rows were all recoverable
        # (seq gaps = spool_lost; zero is the gate) and the merged
        # kill+resume spools reconstructed the cross-process trace with
        # zero orphan replay spans
        "telemetry_recovered_ok": bool(
            audit.get("telemetry_recovered_ok", False)),
        "spool_rows": int(audit.get("spool_rows", 0)),
        "spool_lost": int(audit.get("spool_lost", -1)),
    }


def multichip_columns(scaling: dict, disagg: dict,
                      spool: dict | None = None) -> dict:
    """multichip_serving columns: per-chip-count throughput rows plus
    the disaggregated-arm latency comparison — the cross-round
    contract (tests/test_bench.py). ``scaling`` maps chip count →
    child result ({"tok_s", "ttft_p99_s"}); ``disagg`` is the
    role-split child's result; ``spool`` (ISSUE 20) carries the
    parent-side merge of every child's telemetry spool (obs/ship.py) —
    TTFT p99 per chip count recomputed from the shipped
    ``engine_ttft_seconds`` histograms, fleet ITL p95, spool row
    accounting, and the declarative SLO scoreboard verdict."""
    chips = sorted(int(c) for c in scaling)
    top = chips[-1]
    base = float(scaling[chips[0]].get("tok_s", 0.0)) or 1e-9
    top_tok = float(scaling[top].get("tok_s", 0.0))
    spool = spool or {}
    ttft_by_chips = dict(spool.get("ttft_p99_by_chips", {}))
    return {
        "chips": top,
        "tok_s_per_chip": round(top_tok / top, 2),
        "scaling_efficiency": round(
            (top_tok / base) / (top / chips[0]), 4),
        "ttft_p99_s": float(scaling[top].get("ttft_p99_s", 0.0)),
        "handoff_ms": float(disagg.get("handoff_ms", 0.0)),
        "itl_p95_coloc_s": float(disagg.get("itl_p95_coloc_s", 0.0)),
        "itl_p95_disagg_s": float(disagg.get("itl_p95_disagg_s", 0.0)),
        "handoffs": int(disagg.get("handoffs", 0)),
        "scaling": {str(c): {
            "tok_s": round(float(scaling[c].get("tok_s", 0.0)), 2),
            "ttft_p99_s": float(scaling[c].get("ttft_p99_s", 0.0)),
            # merged-spool TTFT: same requests, but measured from the
            # histogram the child SHIPPED, merged by the parent
            "ttft_p99_spool_s": ttft_by_chips.get(str(c)),
        } for c in chips},
        "itl_p95_s": float(spool.get("itl_p95_s", 0.0)),
        "spool_rows": int(spool.get("spool_rows", 0)),
        "spool_lost": int(spool.get("spool_lost", -1)),
        "slo_ok": spool.get("slo_ok", None),
        "slo": dict(spool.get("slo", {})),
    }


def ann_columns(corpus_size: int, recall_at_10: float,
                flat: dict, ivf: dict) -> dict:
    """ann_retrieval columns: the cross-round contract
    (tests/test_bench.py). ``flat``/``ivf`` are per-route result dicts
    ({"qps", "p50_ms", "p95_ms"} — ivf additionally carries the
    last_query_stats fields "lists_scanned_frac"/"spill_fraction" and
    the index shape "nlist"/"nprobe"). ``ann_ok`` is the gate the
    tentpole claims: approximate recall ≥0.95 against the exact-scan
    oracle while touching ≤15% of the posting lists, at higher QPS."""
    return {
        "corpus_size": int(corpus_size),
        "recall_at_10": round(float(recall_at_10), 4),
        "flat_qps": round(float(flat.get("qps", 0.0)), 2),
        "ivf_qps": round(float(ivf.get("qps", 0.0)), 2),
        "flat_query_p50_ms": round(float(flat.get("p50_ms", 0.0)), 3),
        "flat_query_p95_ms": round(float(flat.get("p95_ms", 0.0)), 3),
        "ivf_query_p50_ms": round(float(ivf.get("p50_ms", 0.0)), 3),
        "ivf_query_p95_ms": round(float(ivf.get("p95_ms", 0.0)), 3),
        "lists_scanned_frac": round(
            float(ivf.get("lists_scanned_frac", 1.0)), 4),
        "spill_fraction": round(float(ivf.get("spill_fraction", 0.0)), 4),
        "nlist": int(ivf.get("nlist", 0)),
        "nprobe": int(ivf.get("nprobe", 0)),
        "ann_ok": bool(
            float(recall_at_10) >= 0.95
            and float(ivf.get("lists_scanned_frac", 1.0)) <= 0.15
            and float(ivf.get("qps", 0.0)) > float(flat.get("qps", 0.0))),
    }


def telemetry_columns(eng, last_n: int | None = None) -> dict:
    """Flight-recorder latency columns (engine/telemetry.py), sourced
    from the engine's OWN request spans and step records instead of
    ad-hoc bench timers — the same numbers the Prometheus exposition
    serves, so a dashboard regression and a bench artifact disagree
    never. ``last_n`` restricts the percentiles to the timed run's
    completions. Empty dict when the engine was built with
    telemetry=False (BENCH_TELEMETRY=0, the overhead-measurement arm)."""
    tele = getattr(eng, "telemetry", None)
    if tele is None:
        return {}
    s = tele.latency_summary(last_n=last_n)
    return {
        "ttft_p50_s": s["ttft_p50_s"],
        "ttft_p95_s": s["ttft_p95_s"],
        "ttft_p99_s": s["ttft_p99_s"],
        "itl_mean_s": s["itl_mean_s"],
        "itl_p95_s": s["itl_p95_s"],
        "mean_occupancy": s["mean_occupancy"],
    }


def shardcheck_preflight() -> dict | None:
    """Trace-verify the selected preset's engine entrypoints on CPU
    (analysis/shardcheck.py: donation aliasing, KV-cache layout
    agreement, bucket coverage) BEFORE burning TPU time. A contract
    violation returns an ok:false artifact dict (the caller exits 2,
    matching the unknown-BENCH_PRESET behavior) — a broken donation
    alias or mismatched cache layout would otherwise surface as an OOM
    or 2x memory halfway through the timed run. Infra failures
    (missing jax, timeout) warn and let the bench proceed: the gate
    must never be the thing that eats the artifact."""
    if os.environ.get("BENCH_PREFLIGHT", "1") != "1":
        return None
    preset = os.environ.get("BENCH_PRESET", "")
    modules = os.environ.get("BENCH_SHARDCHECK_MODULES")
    if modules:
        modules = [m.strip() for m in modules.split(",") if m.strip()]
    else:
        if preset not in PRESET_CONTRACT_MODULES:
            # tests pin the map to the preset table; this is the loud
            # runtime fallback should they ever drift anyway
            log(f"shardcheck preflight: no contract-module map for "
                f"preset {preset!r}; tracing the default set")
        modules = PRESET_CONTRACT_MODULES.get(
            preset, PRESET_CONTRACT_MODULES[""])
    if not modules:
        log("shardcheck preflight: preset has no jitted entrypoints "
            "(host-only pipeline gate); skipping")
        return None
    log(f"shardcheck preflight: {', '.join(modules)}")
    from copilot_for_consensus_tpu.analysis import shardcheck

    data, detail = shardcheck.run_worker(
        modules, baseline=os.path.join(REPO, "jaxlint_baseline.json"),
        timeout=600)
    if data is None:
        log(f"shardcheck preflight: {detail}; continuing")
        return None
    findings = data.get("findings", [])
    # Worker infra trouble (jax itself unusable in the subprocess) is
    # reported as a shard-contract finding with path "jax" so CI fails
    # loudly — but for the bench it is environment, not contract, and
    # must warn-and-continue like a probe hiccup.
    infra = [f for f in findings if f.get("path") == "jax"]
    findings = [f for f in findings if f.get("path") != "jax"]
    for f in infra:
        log(f"shardcheck preflight infra failure ({f['message']}); "
            f"continuing")
    if not findings:
        if not infra:          # infra runs traced nothing — not CLEAN
            log("shardcheck preflight: CLEAN")
        return None
    rendered = [f"{f['path']}:{f['line']}: {f['rule']}: {f['message']}"
                for f in findings[:20]]
    for ln in rendered:
        log(f"shardcheck preflight: {ln}")
    return {
        "metric": "shardcheck-preflight",
        "value": 0.0,
        "unit": "",
        "ok": False,
        "reason": f"shardcheck preflight failed: {len(findings)} "
                  f"contract violation(s) in {', '.join(modules)}",
        "findings": rendered,
    }


#: presets whose timed run leans on a compiled-artifact property the
#: hlo family pins — paged routes (no-materialize fingerprints, pool
#: donation aliases, program-cache cardinality), the mesh preset
#: (collective budgets), and the decode/spec arms (HBM peak budgets).
#: The remaining presets keep preflight latency down: shardcheck
#: already traces them, and compiling is the expensive half.
HLO_PREFLIGHT_PRESETS = frozenset(
    {"paged_capacity", "multichip_serving", "decode_heavy",
     "spec_decode", "ann_retrieval"})


def hlocheck_preflight() -> dict | None:
    """Lower + compile the preset's engine dispatches on CPU
    (analysis/hlocheck.py: donation survives as input_output_alias,
    no forbidden materializing ops, collective budgets, HBM peak
    budgets, program-cache cardinality) BEFORE burning TPU time. A
    violation returns an ok:false artifact dict (the caller exits 2,
    matching shardcheck_preflight) — a dropped pool alias or a GSPMD
    reshard regression would otherwise surface as an OOM or a 2x step
    time halfway through the timed run. ``BENCH_HLOCHECK=0`` disables
    just this gate (compiling costs ~tens of seconds) without
    touching the cheaper shard/dura preflights; infra failures warn
    and let the bench proceed: the gate must never be the thing that
    eats the artifact."""
    if os.environ.get("BENCH_PREFLIGHT", "1") != "1":
        return None
    if os.environ.get("BENCH_HLOCHECK", "1") != "1":
        return None
    preset = os.environ.get("BENCH_PRESET", "")
    modules = os.environ.get("BENCH_HLOCHECK_MODULES")
    if modules:
        modules = [m.strip() for m in modules.split(",") if m.strip()]
    else:
        if preset not in HLO_PREFLIGHT_PRESETS:
            return None
        from copilot_for_consensus_tpu.analysis.contracts import (
            HLO_CONTRACT_MODULES,
        )

        # only modules that BOTH the preset exercises and the hlo
        # registry covers: multichip_serving's mesh/sharding modules
        # declare no lowering specs, so they trace (shardcheck) but
        # don't compile here
        modules = [m for m in PRESET_CONTRACT_MODULES.get(preset, [])
                   if m in HLO_CONTRACT_MODULES]
    if not modules:
        return None
    log(f"hlocheck preflight: {', '.join(modules)}")
    from copilot_for_consensus_tpu.analysis import hlocheck

    data, detail = hlocheck.run_worker(
        modules, baseline=os.path.join(REPO, "jaxlint_baseline.json"),
        timeout=600)
    if data is None:
        log(f"hlocheck preflight: {detail}; continuing")
        return None
    findings = data.get("findings", [])
    # same worker-infra convention as shardcheck: an unusable jax in
    # the subprocess reports as an hlo-contract finding with path
    # "jax" — environment for the bench, warn-and-continue
    infra = [f for f in findings if f.get("path") == "jax"]
    findings = [f for f in findings if f.get("path") != "jax"]
    for f in infra:
        log(f"hlocheck preflight infra failure ({f['message']}); "
            f"continuing")
    if not findings:
        if not infra:
            log("hlocheck preflight: CLEAN")
        return None
    rendered = [f"{f['path']}:{f['line']}: {f['rule']}: {f['message']}"
                for f in findings[:20]]
    for ln in rendered:
        log(f"hlocheck preflight: {ln}")
    return {
        "metric": "hlocheck-preflight",
        "value": 0.0,
        "unit": "",
        "ok": False,
        "reason": f"hlocheck preflight failed: {len(findings)} "
                  f"compiled-artifact violation(s) in "
                  f"{', '.join(modules)}",
        "findings": rendered,
    }


#: pipeline presets run the dura (durability-contract) rule family
#: over the planes their storm exercises, the way engine presets run
#: shardcheck; value = the source roots duracheck scans.
PRESET_DURA_PATHS = {
    "pipeline_chaos": ["copilot_for_consensus_tpu/bus",
                       "copilot_for_consensus_tpu/services"],
}


def duracheck_preflight(paths: list[str] | None = None) -> dict | None:
    """Run the dura rule family (analysis/duracheck.py: commit/publish
    crash windows, raw-publish outbox bypasses, ack swallows, journal
    ordering, idempotent writes, sqlite-ledger hygiene) over the
    preset's bus/services planes BEFORE the storm. A violation returns
    an ok:false artifact dict (the caller exits 2, matching
    shardcheck_preflight) — a handler that silently acks transient
    failures would otherwise surface as lost-work counts halfway
    through a chaos run. Analyzer infra trouble warns and lets the
    bench proceed: the gate must never be the thing that eats the
    artifact. scale_bench's host-pipeline path calls this too, with
    its own explicit ``paths``."""
    if os.environ.get("BENCH_PREFLIGHT", "1") != "1":
        return None
    env_paths = os.environ.get("BENCH_DURACHECK_PATHS")
    if env_paths:
        # explicit override wins even over caller-passed paths (the
        # contract tests point this at the fixture corpus)
        paths = [p.strip() for p in env_paths.split(",") if p.strip()]
    elif paths is None:
        paths = PRESET_DURA_PATHS.get(
            os.environ.get("BENCH_PRESET", ""), [])
    if not paths:
        return None
    log(f"duracheck preflight: {', '.join(paths)}")
    cmd = [sys.executable, "-m", "copilot_for_consensus_tpu.analysis",
           "--group", "dura", "--strict",
           *[os.path.join(REPO, p) for p in paths]]
    try:
        r = subprocess.run(cmd, cwd=REPO, capture_output=True,
                           text=True, timeout=300)
    except Exception as exc:   # infra, not contract
        log(f"duracheck preflight: {exc!r}; continuing")
        return None
    if r.returncode == 0:
        log("duracheck preflight: CLEAN")
        return None
    if r.returncode != 1:
        # usage error / analyzer crash — environment, not contract
        log(f"duracheck preflight: analyzer rc {r.returncode} "
            f"({r.stderr.strip()[-200:]}); continuing")
        return None
    rendered = [ln for ln in r.stdout.splitlines() if ln.strip()][:20]
    for ln in rendered:
        log(f"duracheck preflight: {ln}")
    return {
        "metric": "duracheck-preflight",
        "value": 0.0,
        "unit": "",
        "ok": False,
        "reason": "duracheck preflight failed: durability-contract "
                  f"violation(s) in {', '.join(paths)}",
        "findings": rendered,
    }


# -- child rows (parents that never import jax only) --------------------

def _run_row(name: str, cmd: list[str], env: dict[str, str],
             timeout: float = 900.0) -> dict:
    """Run one bench subprocess, parse its single JSON stdout line.
    Only for parents that have NOT touched jax (multichip_serving pins
    virtual CPU devices per child): a process that holds the chip must
    never start a child that needs it."""
    log(f"--- child row: {name} ---")
    t0 = time.monotonic()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout, cwd=REPO,
                           env={**os.environ, **env})
    except subprocess.TimeoutExpired:
        return {"row": name, "ok": False,
                "reason": f"timeout after {timeout:.0f}s"}
    for line in reversed((r.stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            d.setdefault("ok", True)   # keep a child's own ok:false
            d.update(row=name,
                     elapsed_s=round(time.monotonic() - t0, 1))
            return d
    tail = (r.stderr or r.stdout).strip().splitlines()[-1:]
    return {"row": name, "ok": False,
            "reason": tail[0] if tail else f"rc={r.returncode}"}


# -- mixed-traffic SLO gate (engine/scheduler.py) -----------------------

def mixed_traffic_headline() -> dict:
    """Adversarial mixed-traffic gate for the SLO-aware scheduler.

    The mix: every long batch-lane prompt arrives BEFORE the first
    chat (FIFO's worst case — the monolithic prefill waves stall every
    decode window), short interactive chats from a second tenant
    trickle in over the first steps, and an embed burst contends for
    the host loop mid-run. The same scripted arrivals run twice —
    scheduler ON (chunked prefill + weighted DRR + shedding) and OFF
    (FIFO) — and the artifact records TTFT p99 / ITL p95 against the
    declared SLO bounds for BOTH arms, plus shed_rate and
    fairness_jain_index for the scheduler arm. Greedy per-request
    outputs must be bit-identical between arms for every request that
    completed in both (ordering may change; token streams may not)."""
    import jax  # noqa: F401  (device availability probe ran already)
    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.engine.embedding import EmbeddingEngine
    from copilot_for_consensus_tpu.engine.generation import GenerationEngine
    from copilot_for_consensus_tpu.engine.scheduler import (
        EngineOverloaded,
        SchedulerConfig,
    )
    from copilot_for_consensus_tpu.models import decoder_config
    from copilot_for_consensus_tpu.models.configs import encoder_config

    preset_vals = PRESETS["mixed_traffic"]

    def knob(name: str, default: str) -> str:
        return os.environ.get(name, preset_vals.get(name, default))

    model = knob("BENCH_MODEL", "mistral-7b")
    slots = int(knob("BENCH_SLOTS", "32"))
    max_len = int(knob("BENCH_MAX_LEN", "1024"))
    new_tokens = int(knob("BENCH_NEW_TOKENS", "64"))
    window = int(knob("BENCH_DECODE_WINDOW", "8"))
    n_chat = int(knob("BENCH_MIX_CHAT", "48"))
    chat_len = int(knob("BENCH_MIX_CHAT_LEN", "96"))
    n_long = int(knob("BENCH_MIX_LONG", "12"))
    long_len = int(knob("BENCH_MIX_LONG_LEN", "832"))
    n_embed = int(knob("BENCH_MIX_EMBED_TEXTS", "192"))
    chunk_tokens = int(knob("BENCH_CHUNK_TOKENS", "128"))
    ttft_slo = float(knob("BENCH_TTFT_SLO", "2.0"))
    itl_slo = float(knob("BENCH_ITL_SLO", "0.25"))
    kv_name = knob("BENCH_KV_DTYPE", "float8_e4m3fn")
    wq = knob("BENCH_WEIGHT_DTYPE", "int8")
    quantize = (False if knob("BENCH_QUANTIZE", "1") != "1" else wq)

    cfg = decoder_config(model)
    rng = np.random.default_rng(0)
    # Scripted arrivals: (step, script_idx, tenant, priority, prompt).
    # All long prompts land at step 0 — ahead of every chat.
    script = []
    for i in range(n_long):
        script.append((0, i, "analytics", "batch", rng.integers(
            3, cfg.vocab_size, size=long_len).tolist()))
    for i in range(n_chat):
        script.append((1 + i // 8, n_long + i, "chat", "interactive",
                       rng.integers(3, cfg.vocab_size,
                                    size=chat_len).tolist()))
    embed_texts = [f"mixed traffic embed text {i} corpus chunk " * 4
                   for i in range(n_embed)]

    def run_arm(sched_on: bool) -> dict:
        sched = None
        if sched_on:
            sched = SchedulerConfig(
                chunk_tokens=chunk_tokens,
                prefill_wave_tokens=4 * chunk_tokens,
                quantum_tokens=chunk_tokens,
                tenant_weights={"chat": 2.0, "analytics": 1.0},
                max_queue_depth=48, batch_shed_depth=32,
                ttft_p99_slo_s=4 * ttft_slo,
                queue_wait_p95_slo_s=2 * ttft_slo)
        buckets = tuple(sorted({chat_len, chunk_tokens, long_len}))
        eng = GenerationEngine(
            cfg, num_slots=slots, max_len=max_len,
            prefill_buckets=buckets, dtype=jnp.bfloat16,
            kv_dtype=kv_name, seed=0, quantize=quantize,
            decode_window=window, windows_per_dispatch=1,
            scheduler=sched, telemetry=True)
        emb_model = knob("BENCH_EMBED_MODEL",
                         "tiny" if model == "tiny" else "minilm-l6")
        emb = EmbeddingEngine(encoder_config(emb_model), batch_size=32,
                              scheduler=eng._sched if sched_on
                              else None)
        # Warmup: compile the steady-state programs (admission buckets,
        # chunk widths, decode kv extents, embed tiles) OUTSIDE the
        # measured window — the timed TTFT/ITL percentiles must measure
        # scheduling, not XLA compiles.
        warm_ids = set()
        for plen, tenant, prio in ((long_len, "analytics", "batch"),
                                   (chat_len, "chat", "interactive")):
            warm_ids.add(eng.submit(
                rng.integers(3, cfg.vocab_size, size=plen).tolist(),
                new_tokens, tenant=tenant, priority=prio))
        drained = set()
        while drained < warm_ids:
            drained |= {c.request_id for c in eng.step()}
        emb.embed_batch(embed_texts[:4], tenant="ingest")
        fair0 = dict(eng._sched.fairness_snapshot()) if sched_on else {}
        outputs: dict[int, list[int]] = {}
        done = shed = 0
        rid_to_idx: dict[int, int] = {}
        pending = sorted(script)
        step_idx = 0
        embed_done = False
        t0 = time.monotonic()
        while done + shed < len(script) and step_idx < 100000:
            while pending and pending[0][0] <= step_idx:
                _, sidx, tenant, prio, prompt = pending.pop(0)
                try:
                    rid = eng.submit(prompt, new_tokens, tenant=tenant,
                                     priority=prio)
                    rid_to_idx[rid] = sidx
                except EngineOverloaded:
                    shed += 1
            if not embed_done and step_idx == 4:
                try:
                    emb.embed_batch(embed_texts, tenant="ingest")
                except EngineOverloaded:
                    pass
                embed_done = True
            for c in eng.step():
                outputs[rid_to_idx[c.request_id]] = c.tokens
                done += 1
            step_idx += 1
        elapsed = max(1e-6, time.monotonic() - t0)
        total_new = sum(len(t) for t in outputs.values())
        # Fairness over the TIMED window only (warmup ran under the
        # anonymous tenant mix), shed rate over the scripted arrivals.
        sched_stats = dict(eng.sched_stats())
        if sched_on:
            from copilot_for_consensus_tpu.engine.scheduler import (
                jain_index,
            )
            fair1 = eng._sched.fairness_snapshot()
            deltas = [v - fair0.get(t, 0.0) for t, v in fair1.items()
                      if v - fair0.get(t, 0.0) > 0]
            sched_stats["fairness_jain_index"] = round(
                jain_index(deltas), 4)
            sched_stats["shed_rate"] = round(
                shed / max(1, done + shed), 4)
        return {
            "tok_s": total_new / elapsed,
            "completed": done,
            "outputs": outputs,
            "summary": eng.telemetry.latency_summary(last_n=done),
            "sched": sched_stats,
        }

    log("mixed_traffic: scheduler ON arm")
    on = run_arm(True)
    log("mixed_traffic: scheduler OFF arm (FIFO)")
    off = run_arm(False)
    common = set(on["outputs"]) & set(off["outputs"])
    bit_identical = all(on["outputs"][k] == off["outputs"][k]
                        for k in common)

    # SLO verdicts route through the declarative registry (obs/slo.py)
    # so this gate, the `slo` CLI scoreboard and the Grafana panels all
    # judge the same objectives — thresholds come from the bench knobs
    from copilot_for_consensus_tpu.obs.slo import (
        SLObjective,
        SLORegistry,
    )

    slo_reg = SLORegistry([
        SLObjective(name="interactive-ttft-p99",
                    series="copilot_engine_ttft_seconds",
                    percentile=0.99, threshold_s=ttft_slo,
                    window="mixed_traffic", workload="interactive"),
        SLObjective(name="interactive-itl-p95",
                    series="copilot_engine_itl_seconds",
                    percentile=0.95, threshold_s=itl_slo,
                    window="mixed_traffic", workload="interactive",
                    budget=0.05),
    ])

    def slo_rows(summary: dict) -> list[dict]:
        return [
            slo_reg.get("interactive-ttft-p99").check(
                summary["ttft_p99_s"]),
            slo_reg.get("interactive-itl-p95").check(
                summary["itl_p95_s"]),
        ]

    def slo_ok(summary: dict) -> bool:
        return all(r["ok"] for r in slo_rows(summary))

    cols = sched_columns(on["summary"], on["sched"])
    log(f"mixed_traffic: ON  ttft_p99 {on['summary']['ttft_p99_s']}s "
        f"itl_p95 {on['summary']['itl_p95_s']}s "
        f"shed_rate {cols['shed_rate']} "
        f"jain {cols['fairness_jain_index']}")
    log(f"mixed_traffic: OFF ttft_p99 {off['summary']['ttft_p99_s']}s "
        f"itl_p95 {off['summary']['itl_p95_s']}s; "
        f"bit-identical over {len(common)} common requests: "
        f"{bit_identical}")
    return {
        "metric": f"{model} mixed-traffic serving under SLO "
                  f"(scheduler on, {slots} slots, {n_long} long + "
                  f"{n_chat} chat + {n_embed}-text embed burst)",
        "value": round(on["tok_s"], 2),
        "unit": "tok/s",
        "vs_baseline": round(on["tok_s"] / BASELINE_TOK_S, 3),
        **cols,
        "slo": {"ttft_p99_s": ttft_slo, "itl_p95_s": itl_slo},
        "slo_ok_sched_on": slo_ok(on["summary"]),
        "slo_ok_sched_off": slo_ok(off["summary"]),
        "slo_scoreboard": slo_rows(on["summary"]),
        "sched_off": {
            "ttft_p99_s": off["summary"]["ttft_p99_s"],
            "itl_p95_s": off["summary"]["itl_p95_s"],
            "tok_s": round(off["tok_s"], 2),
        },
        "bit_identical_greedy": bit_identical,
        "completed_on": on["completed"],
        "completed_off": off["completed"],
        "chunk_dispatches": on["sched"].get("chunk_dispatches", 0),
    }


# -- ANN retrieval gate (vectorstore/tpu.py + vectorstore/ivf.py) -------

def ann_retrieval_headline() -> dict:
    """Two vector-store routes over ONE seeded clustered corpus: flat
    (exact scan — the recall oracle) and ivf (two-tier sharded index).
    Both ingest the same vectors, answer the same queries; the artifact
    gates the tentpole claim — recall@10 ≥ 0.95 against the oracle
    while scanning ≤ 15% of the posting lists, at higher QPS. The ivf
    warmup batch is timed separately as ``index_build_s`` because the
    coarse quantizer trains lazily on the first query
    (vectorstore/ivf.py retrain policy), not during ingest — ingest
    must never block on a k-means fit."""
    import numpy as np

    from copilot_for_consensus_tpu.vectorstore.tpu import TPUVectorStore

    preset_vals = PRESETS["ann_retrieval"]

    def knob(name: str, default: str) -> str:
        return os.environ.get(name, preset_vals.get(name, default))

    n = int(knob("BENCH_ANN_N", "1000000"))
    dim = int(knob("BENCH_ANN_DIM", "64"))
    clusters = int(knob("BENCH_ANN_CLUSTERS", "1024"))
    n_queries = int(knob("BENCH_ANN_QUERIES", "256"))
    batch = int(knob("BENCH_ANN_BATCH", "64"))
    top_k = int(knob("BENCH_ANN_TOPK", "10"))
    nlist = int(knob("BENCH_ANN_NLIST", "0"))
    nprobe = int(knob("BENCH_ANN_NPROBE", "16"))
    mesh_cfg = knob("BENCH_ANN_MESH", "none")
    seed = int(knob("BENCH_ANN_SEED", "0"))

    # Clustered synthetic corpus — the shape real chunk embeddings
    # have (mailing-list threads cluster by topic), and the shape IVF
    # exists for. Queries draw from the SAME cluster mixture, so the
    # oracle's true neighbors concentrate in few posting lists.
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((clusters, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    noise = 0.15

    def draw(count: int) -> np.ndarray:
        which = rng.integers(0, clusters, size=count)
        return (centers[which] + noise * rng.standard_normal(
            (count, dim), dtype=np.float32))

    corpus = draw(n)
    queries = draw(n_queries)

    def build(index_kind: str):
        cfg: dict = {"dimension": dim, "index": index_kind}
        if index_kind == "ivf":
            cfg["mesh"] = (mesh_cfg if mesh_cfg in ("none", "auto")
                           else int(mesh_cfg))
            cfg["ivf_nprobe"] = nprobe
            if nlist:
                cfg["ivf_nlist"] = nlist
        store = TPUVectorStore(cfg)
        t0 = time.perf_counter()
        store.add_embeddings(
            (str(i), corpus[i], None) for i in range(n))
        return store, time.perf_counter() - t0

    def run_route(store) -> dict:
        # Warmup batch OUTSIDE the timed window: compiles the search
        # programs, and on the ivf route trains the coarse quantizer.
        t0 = time.perf_counter()
        store.query_batch(list(queries[:min(batch, n_queries)]),
                          top_k=top_k)
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        results = []
        for s in range(0, n_queries, batch):
            results.extend(store.query_batch(
                list(queries[s:s + batch]), top_k=top_k))
        qps = n_queries / max(time.perf_counter() - t0, 1e-9)
        lat = []
        for q in queries[:min(64, n_queries)]:
            t1 = time.perf_counter()
            store.query(q, top_k=top_k)
            lat.append((time.perf_counter() - t1) * 1e3)
        lat.sort()
        stats = dict(store.last_query_stats or {})
        return {
            "ids": [[h.id for h in hits] for hits in results],
            "qps": qps,
            "p50_ms": lat[len(lat) // 2],
            "p95_ms": lat[min(len(lat) - 1, int(0.95 * len(lat)))],
            "warm_s": warm_s,
            **{k: stats[k] for k in ("lists_scanned_frac",
                                     "spill_fraction") if k in stats},
        }

    log(f"ann_retrieval: ingesting {n} x {dim} into flat route")
    flat_store, flat_ingest_s = build("flat")
    log("ann_retrieval: flat route (exact oracle)")
    flat = run_route(flat_store)
    flat_store.close()
    log(f"ann_retrieval: ingesting {n} x {dim} into ivf route")
    ivf_store, ivf_ingest_s = build("ivf")
    log("ann_retrieval: ivf route")
    ivf = run_route(ivf_store)
    ivf.update(nlist=getattr(ivf_store._ivf, "nlist", 0) or 0,
               nprobe=nprobe)

    recalls = [len(set(a) & set(b)) / max(len(b), 1)
               for a, b in zip(ivf["ids"], flat["ids"]) if b]
    recall = float(np.mean(recalls)) if recalls else 0.0
    cols = ann_columns(n, recall, flat, ivf)
    ivf_store.close()
    log(f"ann_retrieval: recall@{top_k} {cols['recall_at_10']} "
        f"lists_scanned_frac {cols['lists_scanned_frac']} "
        f"qps ivf {cols['ivf_qps']} vs flat {cols['flat_qps']}")
    return {
        "metric": f"ANN retrieval recall@{top_k} vs exact scan "
                  f"({n}-vector corpus, {cols['nlist']}-list ivf, "
                  f"nprobe {nprobe})",
        "value": cols["recall_at_10"],
        "unit": f"recall@{top_k}",
        # the speedup the approximate route buys at this recall
        "vs_baseline": round(cols["ivf_qps"]
                             / max(cols["flat_qps"], 1e-9), 3),
        **cols,
        "index_build_s": round(ivf["warm_s"], 3),
        "flat_ingest_s": round(flat_ingest_s, 3),
        "ivf_ingest_s": round(ivf_ingest_s, 3),
        "queries": n_queries,
        "dim": dim,
    }


# -- chaos gate (engine/faults.py + engine/supervisor.py) ---------------

def chaos_headline() -> dict:
    """Fault-injected self-healing gate: the same scripted cohorts run
    fault-free (baseline outputs) and then through a seeded three-
    phase fault script against ONE engine+runner — (1) a transient
    exception on every dispatch kind (request replay must recover,
    bit-identically), (2) a hang past the watchdog deadline (handles
    must fail structured, the dispatcher must stay live), (3) a
    persistent verify fault (the spec breaker must flip to plain
    decode, then restore via the half-open probe once cleared). Every
    handle must resolve — Completion or structured error carrying a
    correlation id — and every chaos-arm COMPLETION must be
    bit-identical to the baseline (replayed requests included: the
    continuation resubmit is greedy bit-identical by the chunked-
    prefill identity argument, docs/RESILIENCE.md)."""
    import numpy as np

    import jax.numpy as jnp

    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )
    from copilot_for_consensus_tpu.engine.faults import (
        PERSISTENT,
        FaultInjector,
        FaultPlan,
        FaultSpec,
    )
    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine,
    )
    from copilot_for_consensus_tpu.engine.supervisor import (
        SupervisorConfig,
    )
    from copilot_for_consensus_tpu.models import decoder_config

    preset_vals = PRESETS["chaos"]

    def knob(name: str, default: str) -> str:
        return os.environ.get(name, preset_vals.get(name, default))

    model = knob("BENCH_MODEL", "mistral-7b")
    slots = int(knob("BENCH_SLOTS", "16"))
    max_len = int(knob("BENCH_MAX_LEN", "512"))
    new_tokens = int(knob("BENCH_NEW_TOKENS", "48"))
    window = int(knob("BENCH_DECODE_WINDOW", "8"))
    n_chat = int(knob("BENCH_CHAOS_CHAT", "24"))
    chat_len = int(knob("BENCH_CHAOS_CHAT_LEN", "96"))
    n_long = int(knob("BENCH_CHAOS_LONG", "6"))
    long_len = int(knob("BENCH_CHAOS_LONG_LEN", "320"))
    seed = int(knob("BENCH_CHAOS_SEED", "7"))
    hang_s = float(knob("BENCH_CHAOS_HANG_S", "12"))
    deadline = float(knob("BENCH_CHAOS_DECODE_DEADLINE_S", "6"))
    # compute dtype pinned f32 for exact replay bit-identity (see the
    # preset comment); kv cache matches the compute dtype
    dtype = {"float32": jnp.float32,
             "bfloat16": jnp.bfloat16}[knob("BENCH_CHAOS_DTYPE",
                                            "float32")]
    wq = knob("BENCH_WEIGHT_DTYPE", "int8")
    quantize = (False if knob("BENCH_QUANTIZE", "1") != "1" else wq)

    cfg = decoder_config(model)
    rng = np.random.default_rng(seed)

    # Copy-heavy prompts (the spec_decode preset's shape) so the
    # persistent verify fault actually has verify dispatches to hit.
    def copy_heavy(plen: int) -> list[int]:
        half = plen // 2
        head = rng.integers(3, cfg.vocab_size, size=half).tolist()
        tail: list[int] = []
        while len(tail) < plen - half:
            s0 = int(rng.integers(0, max(1, half - 16)))
            tail.extend(head[s0:s0 + 16])
        return head + tail[:plen - half]

    prompts = [copy_heavy(chat_len) for _ in range(n_chat)] \
        + [copy_heavy(long_len) for _ in range(n_long)]
    buckets = tuple(sorted({chat_len, long_len}))
    # cohorts: phase 1 (replay) / phase 2 (hang) / phase 3 (breaker)
    thirds = max(1, len(prompts) // 3)
    cohorts = [list(range(0, thirds)),
               list(range(thirds, 2 * thirds)),
               list(range(2 * thirds, len(prompts)))]

    def build_engine():
        return GenerationEngine(
            cfg, num_slots=slots, max_len=max_len,
            prefill_buckets=buckets, dtype=dtype,
            kv_dtype=dtype, seed=0, quantize=quantize,
            decode_window=window, windows_per_dispatch=1,
            spec_decode=True, telemetry=True)

    def drain(runner, idxs):
        outputs: dict[int, list] = {}
        errors: dict[int, BaseException] = {}
        handles = [(i, runner.submit(list(prompts[i]), new_tokens,
                                     correlation_id=f"chaos-{i}"))
                   for i in idxs]
        for i, h in handles:
            try:
                outputs[i] = h.result(timeout=900.0).tokens
            except Exception as exc:   # noqa: BLE001 — classified below
                errors[i] = exc
        return outputs, errors

    log("chaos: fault-free baseline arm")
    base_eng = build_engine()
    base_runner = AsyncEngineRunner(base_eng).start()
    base_out: dict[int, list] = {}
    for cohort in cohorts:
        out, errs = drain(base_runner, cohort)
        assert not errs, errs
        base_out.update(out)
    base_runner.stop()

    log("chaos: fault-injected arm (supervisor on)")
    eng = build_engine()
    sup_cfg = SupervisorConfig(
        deadlines_s={k: deadline for k in
                     ("prefill", "prefill_seeded", "decode", "verify")},
        step_deadline_s=20 * deadline,
        watchdog_poll_s=0.05, replay_budget=6,
        verify_breaker_threshold=2, breaker_probe_after_s=1.0)
    runner = AsyncEngineRunner(eng, supervisor=sup_cfg).start()
    # warm every program OUTSIDE the fault window with one full fault-
    # free pass (every bucket + the admission batch shapes): a first-
    # call XLA compile inside a tight-deadline dispatch frame would
    # read as a hang (production deadlines are minutes; the chaos
    # knobs shrink them so the gate runs in bench time)
    warm, warm_errs = drain(runner, list(range(len(prompts))))
    assert warm and not warm_errs, ("warmup failed", warm_errs)

    plans = {
        # phase 1: one transient exception on the 2nd occurrence of
        # EVERY dispatch kind — replay must recover all of it
        "transient": FaultPlan(seed=seed, specs=[
            FaultSpec(kind="*", at=2, count=1)]),
        # phase 2: the first dispatch hangs past the watchdog deadline
        "hang": FaultPlan(seed=seed, specs=[
            FaultSpec(kind="*", at=1, count=1, mode="hang",
                      hang_s=hang_s)]),
        # phase 3: persistent verify faults — the spec breaker must
        # flip the engine to plain decode and traffic keep completing
        "verify-breaker": FaultPlan(seed=seed, specs=[
            FaultSpec(kind="verify", at=1, count=PERSISTENT)]),
    }
    outputs: dict[int, list] = {}
    errors: dict[int, BaseException] = {}
    fired = []
    settle_ok = True
    t0 = time.monotonic()
    for cohort, (phase, plan) in zip(cohorts, plans.items()):
        log(f"chaos: phase {phase}")
        inj = FaultInjector(plan)
        eng.faults = inj
        out, errs = drain(runner, cohort)
        inj.release_hangs()
        eng.faults = None
        # settle barrier: the hang phase's drain returns at the
        # watchdog trip, while the dispatcher is still stuck inside
        # the hung dispatch — one fault-free probe request (pending
        # submits survive a suspect event) resolves only after the
        # dispatcher has recovered and purged the zombie work, so the
        # next phase starts against a clean engine instead of racing
        # the recovery.
        probe_idx = cohort[0]
        settle, settle_errs = drain(runner, [probe_idx])
        settle_ok = settle_ok and not settle_errs and \
            settle.get(probe_idx) == base_out[probe_idx]
        outputs.update(out)
        errors.update(errs)
        fired.extend({"phase": phase, **f}
                     for f in inj.stats()["log"])
    # post-storm: once the faults are gone and the breaker cooldown
    # has elapsed, the half-open probe must restore speculation and
    # the engine must still serve bit-identically
    verify_hit = any(f["kind"] == "verify" for f in fired)
    spec0 = eng.spec_dispatches
    if verify_hit:
        # let the open breaker reach its probe window so the post
        # drain can actually exercise the restore path
        time.sleep(sup_cfg.breaker_probe_after_s + 0.2)
    post, post_errs = drain(runner, [cohorts[0][0]])
    elapsed = max(1e-6, time.monotonic() - t0)
    rec = runner.recovery_stats()
    breaker_state = rec["breakers"]["spec_verify"]["state"]
    spec_restored = (not verify_hit
                     or (breaker_state == "closed"
                         and eng.spec_dispatches > spec0))
    runner.stop()

    submitted = sum(len(c) for c in cohorts)
    zero_lost = (len(outputs) + len(errors) == submitted
                 and not post_errs
                 and not any(isinstance(e, TimeoutError)
                             for e in errors.values()))
    structured = all(
        hasattr(e, "correlation_id") for e in errors.values())
    bit_identical = (
        settle_ok
        and all(outputs[i] == base_out[i] for i in outputs)
        and post.get(cohorts[0][0]) == base_out[cohorts[0][0]])
    cols = chaos_columns(rec)
    # within budget: replays recovered phase 1, no budget spent, the
    # watchdog caught the phase-2 hang, and (when verify dispatches
    # ran at all) the spec breaker tripped AND the half-open probe
    # restored speculation after the faults cleared
    budget_ok = (cols["replayed"] >= 1 and cols["failed"] == 0
                 and cols["watchdog_trips"] >= 1
                 and (cols["breaker_trips"] >= 1 or not verify_hit)
                 and spec_restored)
    chaos_ok = bool(zero_lost and structured and bit_identical
                    and budget_ok)
    total_new = sum(len(t) for t in outputs.values())
    tok_s = total_new / elapsed
    log(f"chaos: {len(outputs)} completed / {len(errors)} "
        f"structured-failed of {submitted}; bit-identical "
        f"{bit_identical}, recovery {cols}, "
        f"breaker {breaker_state}, chaos_ok {chaos_ok}")
    return {
        "metric": f"{model} fault-injected serving "
                  f"(supervisor on, {slots} slots, {n_chat} chat + "
                  f"{n_long} long, 3-phase seeded fault script)",
        "value": round(tok_s, 2),
        "unit": "tok/s",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 3),
        **cols,
        "completed": len(outputs),
        "failed_structured": len(errors),
        "zero_lost_handles": zero_lost,
        "bit_identical_greedy": bit_identical,
        "verify_breaker_state": breaker_state,
        "spec_restored": spec_restored,
        "chaos_ok": chaos_ok,
        "faults_fired": fired,
        "fault_plan": {k: p.to_dict() for k, p in plans.items()},
    }


# -- pipeline chaos gate (bus/faults.py + broker ride-through) ----------

def journal_kill_phase(tmp, knob) -> dict:
    """Process-kill chaos (ISSUE 12): three REAL child processes over
    the journal-storm driver (tools/journal_storm.py) —

    1. reference: uninterrupted journaled run → per-request outputs;
    2. kill: same storm, SIGKILL after step N (mid-storm: queued
       requests, active slots, partially-checkpointed tokens);
    3. resume: fresh process over the SAME journal — the engine
       warm-restarts, resubmits unfinished work as prompt+generated
       continuations, and serves it to completion.

    Gate: every request completes exactly once across kill+resume
    (lost 0, duplicated 0), the resume replayed journal rows
    (journal_replayed > 0), the journal drained (final depth 0), and
    every greedy output is bit-identical (f32) to the reference.

    Telemetry recovery gate (ISSUE 20): the kill and resume children
    each ship metric deltas + step records + submit/replay spans into
    a crash-safe spool (obs/ship.py), flushed per step. After the
    SIGKILL the driver reads the dead child's spool: committed rows
    lost must be 0 (seq-contiguity — the WAL discipline's promise),
    spans/steps must be present, and the resume child's engine_replay
    spans must join the killed child's engine_submit spans with zero
    orphans once the two spools merge (tools/tracepath.py) —
    ``telemetry_recovered_ok``."""
    import pathlib

    tmp = pathlib.Path(tmp)
    tmp.mkdir(parents=True, exist_ok=True)
    requests = int(knob("BENCH_KILL_REQUESTS", "12"))
    new_tokens = int(knob("BENCH_KILL_NEW_TOKENS", "24"))
    kill_step = int(knob("BENCH_KILL_STEP", "8"))
    seed = int(knob("BENCH_KILL_SEED", "7"))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def child(journal, out, result, kill_after=0, spool="", proc=""):
        cmd = [sys.executable, "-m",
               "copilot_for_consensus_tpu.tools.journal_storm",
               "--journal", str(journal), "--out", str(out),
               "--result", str(result),
               "--requests", str(requests),
               "--new-tokens", str(new_tokens), "--seed", str(seed)]
        if kill_after:
            cmd += ["--kill-after-step", str(kill_after)]
        if spool:
            cmd += ["--spool", str(spool), "--proc", proc]
        try:
            return subprocess.run(cmd, env=env, capture_output=True,
                                  text=True, timeout=300)
        except subprocess.TimeoutExpired as exc:
            # a wedged child is a FAILED gate, not a bench crash: the
            # other arms' results must survive it
            return subprocess.CompletedProcess(
                cmd, returncode=-999,
                stdout="", stderr=f"child timed out: {exc}")

    def read_lines(path):
        out, dup = {}, 0
        if not os.path.exists(path):
            return out, dup
        with open(path, encoding="utf-8") as f:
            for line in f:
                d = json.loads(line)
                if d["cid"] in out:
                    dup += 1
                out[d["cid"]] = d["tokens"]
        return out, dup

    log("pipeline_chaos: kill phase — reference child")
    r = child(tmp / "ref.sqlite3", tmp / "ref.jsonl", tmp / "ref.json")
    if r.returncode != 0:
        log(f"pipeline_chaos: reference child failed: {r.stderr[-400:]}")
        return {"kill_ok": False, "reason": "reference-child-failed"}
    ref, _ = read_lines(tmp / "ref.jsonl")

    log(f"pipeline_chaos: kill phase — SIGKILL after step {kill_step}")
    kill_spool = tmp / "storm-kill.spool.sqlite3"
    resume_spool = tmp / "storm-resume.spool.sqlite3"
    r = child(tmp / "kill.sqlite3", tmp / "kill.jsonl",
              tmp / "kill.json", kill_after=kill_step,
              spool=kill_spool, proc="storm-kill")
    killed = r.returncode in (-signal.SIGKILL, 128 + signal.SIGKILL,
                              137)
    if not killed:
        log(f"pipeline_chaos: kill child was NOT killed "
            f"(rc {r.returncode}); storm finished before step "
            f"{kill_step}?")

    log("pipeline_chaos: kill phase — warm-restart child")
    r = child(tmp / "kill.sqlite3", tmp / "kill.jsonl",
              tmp / "resume.json",
              spool=resume_spool, proc="storm-resume")
    if r.returncode != 0:
        log(f"pipeline_chaos: resume child failed: {r.stderr[-400:]}")
        return {"kill_ok": False, "reason": "resume-child-failed",
                "process_killed": killed}
    with open(tmp / "resume.json", encoding="utf-8") as f:
        resume = json.load(f)

    # telemetry recovery audit (ISSUE 20): read the SIGKILLed child's
    # spool the way a post-mortem would — committed rows must all be
    # there (seq gaps = loss), with spans and step records present,
    # and the resume child's replay spans must join the killed child's
    # submit spans with zero orphans once the spools merge.
    telemetry = {"spool_rows": 0, "spool_lost": -1, "spans": 0,
                 "steps": 0, "merged_orphans": -1,
                 "cross_proc_edges": 0}
    try:
        from copilot_for_consensus_tpu.obs.ship import (
            TelemetryAggregator,
            read_spool,
        )
        from copilot_for_consensus_tpu.tools import tracepath

        recovered = read_spool(kill_spool)
        kinds = [k for _seq, k, _p in recovered["rows"]]
        agg = TelemetryAggregator()
        agg.ingest_spool(kill_spool)
        agg.ingest_spool(resume_spool)
        audit = tracepath.analyze(agg.spans())
        telemetry = {
            "spool_rows": len(recovered["rows"]),
            "spool_lost": int(recovered["lost"]),
            "spans": kinds.count("span"),
            "steps": kinds.count("step"),
            "merged_orphans": int(audit["orphan_spans"]),
            "cross_proc_edges": int(audit["cross_proc_edges"]),
        }
    except Exception as exc:  # a broken spool is a FAILED gate
        telemetry["error"] = f"{type(exc).__name__}: {exc}"
    telemetry_recovered_ok = bool(
        telemetry["spool_lost"] == 0 and telemetry["spool_rows"] > 0
        and telemetry["spans"] > 0 and telemetry["steps"] > 0
        and telemetry["merged_orphans"] == 0)

    got, dup = read_lines(tmp / "kill.jsonl")
    lost = [c for c in ref if c not in got]
    mismatched = [c for c in got if got[c] != ref.get(c)]
    out = {
        "requests": requests,
        "process_killed": killed,
        "lost": len(lost),
        "duplicated": dup,
        "mismatched": len(mismatched),
        "journal_replayed": int(resume.get("journal_replayed", 0)),
        "journal_abandoned": int(resume.get("journal_abandoned", 0)),
        "journal_depth": int(resume.get("journal_depth", -1)),
        "bit_identical": not mismatched and not lost,
        "telemetry": telemetry,
        "telemetry_recovered_ok": telemetry_recovered_ok,
    }
    out["kill_ok"] = bool(
        killed and not lost and dup == 0 and not mismatched
        and out["journal_replayed"] > 0 and out["journal_depth"] == 0
        and telemetry_recovered_ok)
    log(f"pipeline_chaos: kill phase — lost {out['lost']}, dup "
        f"{out['duplicated']}, journal_replayed "
        f"{out['journal_replayed']}, depth {out['journal_depth']}, "
        f"bit_identical {out['bit_identical']}, telemetry_recovered "
        f"{telemetry_recovered_ok} (spool rows "
        f"{telemetry['spool_rows']}, lost {telemetry['spool_lost']}, "
        f"orphans {telemetry['merged_orphans']}, cross-proc edges "
        f"{telemetry['cross_proc_edges']}), ok {out['kill_ok']}")
    return out


def pipeline_chaos_headline() -> dict:
    """Pipeline-wide fault gate (the PR-8 tentpole; see the preset
    comment for the arm/phase script). Runs the REAL deployment
    topology at bench scale: durable zmq broker on a sqlite db, one
    ``build_pipeline`` process with a consume loop per service, sqlite
    document store, mock inference drivers — so what it proves is the
    bus/storage machinery, not the engines (those have their own chaos
    gate)."""
    import pathlib
    import shutil
    import tempfile
    import threading

    scripts_dir = os.path.join(REPO, "scripts")
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    from scale_bench import synthetic_mbox

    from copilot_for_consensus_tpu.bus import broker as broker_mod
    from copilot_for_consensus_tpu.services.runner import build_pipeline
    from copilot_for_consensus_tpu.tools.retry_job import (
        RetryStuckDocumentsJob,
        default_rules,
    )

    preset_vals = PRESETS["pipeline_chaos"]

    def knob(name: str, default: str) -> str:
        return os.environ.get(name, preset_vals.get(name, default))

    msgs_storm = int(knob("BENCH_PIPE_MESSAGES", "1200"))
    n_arch = int(knob("BENCH_PIPE_ARCHIVES", "8"))
    msgs_flood = int(knob("BENCH_PIPE_FLOOD_MESSAGES", "1000"))
    n_arch_flood = int(knob("BENCH_PIPE_FLOOD_ARCHIVES", "4"))
    thread_size = int(knob("BENCH_PIPE_THREAD_SIZE", "8"))
    seed = int(knob("BENCH_PIPE_SEED", "11"))
    drag_s = float(knob("BENCH_PIPE_DRAG_S", "0.01"))
    # SCALE_BROKER's warn SLO is 1000 at the 100k corpus; the scaled
    # gate keeps the same shape at bench size. Watermark = half the
    # SLO, so pacing holds depth with honest headroom under it.
    scaled_slo = int(knob("BENCH_PIPE_WARN_SLO", "32"))
    n_poison = int(knob("BENCH_PIPE_POISON", "5"))
    budget_s = float(knob("BENCH_PIPE_BUDGET_S", "420"))
    # Lease: production default. Tempting to shrink it into bench time
    # (the chaos preset's watchdog-deadline move), but the archive
    # parse handler legitimately holds ONE archive.ingested lease for
    # the whole archive parse — under watermark pacing that is tens of
    # seconds — so a short lease redelivers mid-parse and the arm
    # measures concurrent double-parses instead of the fault plane.
    # The storm instead pays the honest lease-expiry latency for
    # crash-after-work redeliveries (bounded by the settle budget).
    lease_s = float(knob("BENCH_PIPE_LEASE_S", "30"))
    workers = int(knob("BENCH_PIPE_WORKERS", "2"))
    hw = max(2, scaled_slo // 2)

    if not broker_mod.HAS_ZMQ:
        return {"metric": "host pipeline under seeded storm",
                "value": 0.0, "unit": "msg/s", "vs_baseline": 0.0,
                "pipeline_chaos_ok": False, "reason": "pyzmq missing",
                **pipeline_chaos_columns({})}

    def run_arm(tmp: pathlib.Path, messages: int, archives: int, *,
                watermark: int, drag: float = 0.0, faults=None,
                storm: bool = False, drain_midway: bool = False
                ) -> dict:
        """One pipeline arm over a fresh broker + stores. ``drag``
        slows the chunking handler (scripted sustained overload: drain
        deliberately below supply); ``storm`` adds the broker restart
        and poison phases on top of the ``faults`` plan;
        ``drain_midway`` executes the graceful-drain lifecycle
        (services/lifecycle.py) with waves in flight, then
        warm-resumes — the SIGTERM-mid-traffic shape, gated on zero
        shutdown-caused redeliveries."""
        tmp.mkdir(parents=True, exist_ok=True)
        per = messages // archives
        sizes = [per] * (archives - 1) + [messages - per * (archives - 1)]
        for a, n in enumerate(sizes):
            synthetic_mbox(tmp / f"archive-{a}.mbox", n,
                           thread_size=thread_size, seed=seed + a,
                           prefix=f"a{a}")
        expected_threads = sum(-(-n // thread_size) for n in sizes)

        db = str(tmp / "queues.sqlite3")
        holder = {"broker": broker_mod.Broker(
            port=0, db_path=db, lease_s=lease_s).start()}
        port, addr = holder["broker"].port, holder["broker"].address

        cfg = {
            "bus": {"driver": "broker", "port": port,
                    "high_watermark": watermark,
                    # outage-shaped client budget: publishes fail fast
                    # into the outbox instead of blocking handlers for
                    # the full default timeout
                    "timeout_ms": 400, "retries": 2,
                    "saturation_poll_s": 0.01},
            "document_store": {"driver": "sqlite",
                               "path": str(tmp / "docs.sqlite3")},
            "archive_store": {"driver": "document"},
            "vector_store": {"driver": "memory"},
            "embedding": {"driver": "mock", "dimension": 64},
            "llm": {"driver": "mock"},
            # stage scale-out: competing consumer pools + batched waves
            # on the host-bound stages — the chaos contracts must hold
            # with them enabled (ISSUE 11 acceptance)
            "services": {name: {"workers": workers}
                         for name in ("parsing", "chunking",
                                      "embedding")},
        }
        if faults:
            cfg["faults"] = {"plan": faults}
        p = build_pipeline(cfg)
        # Pipeline tracing (obs/trace.py): size the global ring to the
        # arm's span volume (≈ a few tens of spans per message across
        # publish/stage/store-write spans) and clear the previous arm's
        # spans, so the per-arm orphan audit never chases evictions.
        from copilot_for_consensus_tpu.obs import trace as trace_mod

        trace_collector = trace_mod.configure(
            capacity=min(200_000, messages * 60 + 20_000))

        if drag:
            orig = p.chunking.on_JSONParsed

            def dragged(event, _orig=orig):
                time.sleep(drag)
                return _orig(event)

            p.chunking.on_JSONParsed = dragged
            # the batched hot path must drag too (same per-message
            # cost), or the scripted overload disappears into the wave
            orig_wave = p.chunking.on_wave_JSONParsed

            def dragged_wave(events, _orig=orig_wave):
                time.sleep(drag * len(events))
                return _orig(events)

            p.chunking.on_wave_JSONParsed = dragged_wave

        # depth sampler: max PENDING per key (the SCALE_BROKER series
        # the warn SLO is declared over); paused across the restart
        stop_sampler = threading.Event()
        max_depth: dict[str, int] = {}

        def sample():
            while not stop_sampler.wait(0.02):
                b = holder["broker"]
                if b is None:
                    continue
                try:
                    counts = b.store.counts()
                except Exception:
                    continue
                for rk, st in counts.items():
                    if rk.endswith((".failed", ".dlq")):
                        continue
                    d = st.get("pending", 0)
                    if d > max_depth.get(rk, 0):
                        max_depth[rk] = d

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        # stage worker pools (services/pool.py): N stop-aware consume
        # loops per service, worker labels on the stage spans
        for pool in p.worker_pools:
            pool.start()

        for a in range(archives):
            p.ingestion.create_source({
                "source_id": f"pc-{a}", "name": f"pc-{a}",
                "fetcher": "local",
                "location": str(tmp / f"archive-{a}.mbox")})

        t0 = time.monotonic()
        deadline = t0 + budget_s
        poison_sent = 0
        for a in range(archives):
            if storm and a == max(1, archives // 3):
                # phase: broker kill/restart mid-run — in-flight
                # publishes park in the per-service outboxes, consume
                # loops ride the outage on backoff, leases of fetched-
                # but-unacked work expire and redeliver after restart
                log("pipeline_chaos: broker restart")
                b = holder["broker"]
                holder["broker"] = None
                b.stop()
                time.sleep(0.8)
                holder["broker"] = broker_mod.Broker(
                    port=port, db_path=db, lease_s=lease_s).start()
            # scripted store faults can land in the DIRECT trigger path
            # too (no bus retry envelope around it) — the driver
            # retries like the REST caller would; re-triggers are safe
            # because ingest ids are deterministic (at-least-once)
            for attempt in range(6):
                try:
                    p.ingestion.trigger_source(f"pc-{a}")
                    break
                except Exception as exc:  # noqa: BLE001 — scripted
                    log(f"pipeline_chaos: trigger retry a{a} ({exc})")
                    time.sleep(0.05)
            if storm and a == max(1, archives // 2) and not poison_sent:
                # phase: poison — schema-invalid envelopes straight at
                # a consumed key via a RAW (non-validating) publisher;
                # the validating subscriber must quarantine each with a
                # structured reason, never spend redeliveries on them
                raw = broker_mod.BrokerPublisher({"address": addr})
                for i in range(n_poison):
                    raw.publish_envelope(
                        {"event_type": "JSONParsed",
                         "poison": f"missing-required-fields-{i}"},
                        routing_key="json.parsed")
                raw.close()
                poison_sent = n_poison

        drain_info = None
        if drain_midway:
            # Graceful drain with waves in flight (the SIGTERM shape):
            # readiness flips, pools stop-and-join (in-flight
            # dispatches finish and ACK — nothing nacked), mock
            # engines have nothing to drain, outboxes flush. Then
            # warm-resume (drain aborted → READY, pools respawn) and
            # run to completion: any redelivery in this FAULT-FREE arm
            # was caused by the shutdown itself, and the gate is zero.
            from copilot_for_consensus_tpu.services.lifecycle import (
                ServiceLifecycle,
                drain_pipeline,
            )

            lc = ServiceLifecycle("pipeline")
            lc.mark_ready()
            report = drain_pipeline(p, lc, deadline_s=30.0)
            b = holder["broker"]
            counts = b.store.counts() if b is not None else {}
            drain_info = {
                "consumers_stopped": report["consumers_stopped"],
                "outbox_flushed": report["outbox_flushed"],
                "duration_s": report["duration_s"],
                # a clean drain leaves ZERO leases: nothing to expire,
                # nothing for the broker to redeliver afterwards
                "inflight_after_drain": sum(
                    st.get("inflight", 0) for st in counts.values()),
                "state_after_drain": lc.state,
            }
            log(f"pipeline_chaos: drained mid-wave "
                f"({drain_info['inflight_after_drain']} leases left) "
                f"in {drain_info['duration_s']}s; warm-resuming")
            lc.mark_ready()
            for pool in p.worker_pools:
                pool.start()

        def busy_now() -> int:
            b = holder["broker"]
            if b is None:
                return 1
            try:
                counts = b.store.counts()
            except Exception:
                return 1
            return sum(st.get("pending", 0) + st.get("inflight", 0)
                       for rk, st in counts.items()
                       if not rk.endswith((".failed", ".dlq")))

        def missing_now() -> int:
            return p.store.count_documents(
                "threads", {"summary_id": {"$exists": False}})

        # settle: drain to quiescence; if work is STILL stuck
        # mid-pipeline (in-process retry budgets spent under scripted
        # store faults → terminal failure events; orchestrations
        # deferred behind unembedded chunks), run the production
        # recovery spine — the stuck-document retry cron — and let it
        # drain. Multiple rounds, exactly like the deployed cron: one
        # sweep's chunk-stage republishes must complete before its
        # thread-stage re-orchestrations can stop deferring.
        swept_from = 0
        sweeps = 0
        while time.monotonic() < deadline:
            if (busy_now() == 0
                    and p.publisher_stats()["outbox_depth"] == 0):
                # Quiescent. Anything still stuck now is a spent
                # retry budget's terminal failure event (the service
                # acked; the *Failed event is the operator record) —
                # e.g. an archive parse that ate a store_write fault
                # window across its whole redelivery budget, leaving
                # messages unstored. That is exactly the state the
                # stuck-document cron exists for, so sweep on BOTH
                # signals: unparsed archives/messages and
                # unsummarized threads.
                stored_now = p.store.count_documents("messages", {})
                missing = missing_now()
                if stored_now >= messages and missing == 0:
                    break
                if sweeps < 4:
                    log(f"pipeline_chaos: sweep {sweeps + 1}: "
                        f"{max(0, messages - stored_now)} messages "
                        f"unstored, {missing} threads unsummarized")
                    swept_from = swept_from or missing
                    sweeps += 1
                    # Zeroed backoff schedule: the production cron's
                    # 5/10/20/60-minute ladder compressed into bench
                    # time (the lease-knob move) — with the real
                    # schedule, every sweep after the first silently
                    # skips still-stuck docs (age < next backoff rung)
                    # and the multi-round sweep only ever retries once.
                    import dataclasses as _dc
                    RetryStuckDocumentsJob(
                        p.store, p.orchestrator.publisher,
                        [_dc.replace(r, backoff_minutes=(0.0,))
                         for r in default_rules()],
                        min_stuck_seconds=0.0).run_once()
                    time.sleep(0.3)   # let the republishes enqueue
                    continue
                break
            time.sleep(0.1)
        run_s = time.monotonic() - t0

        # audit (store + broker still live)
        stored = p.store.count_documents("messages", {})
        threads_n = p.store.count_documents("threads", {})
        missing = missing_now()
        dup = 0
        for coll in ("summaries", "reports"):
            per_thread: dict[str, int] = {}
            for doc in p.store.query_documents(coll, {}):
                tid = doc.get("thread_id", "")
                per_thread[tid] = per_thread.get(tid, 0) + 1
            dup += sum(n - 1 for n in per_thread.values() if n > 1)
        dead = (holder["broker"].store.dead_letters()
                if holder["broker"] else [])
        quarantined = sum(1 for _i, _rk, _env, _at, reason in dead
                          if reason.startswith("schema validation"))
        dead_other = len(dead) - quarantined
        dead_reasons: dict[str, int] = {}
        for _i, rk, _env, _at, reason in dead:
            key = f"{rk}: {reason[:80]}"
            dead_reasons[key] = dead_reasons.get(key, 0) + 1
        final_counts = (holder["broker"].store.counts()
                        if holder["broker"] else {})
        final_depth = max(
            (st.get("pending", 0) + st.get("inflight", 0)
             for rk, st in final_counts.items()
             if not rk.endswith((".failed", ".dlq"))), default=0)
        pstats = p.publisher_stats()
        fired = (list(p.fault_boundary.stats().get("log", []))
                 if p.fault_boundary is not None else [])
        # Lost counts WORK, not event copies: a dead-lettered event
        # whose work the recovery spine re-covered (the sweep) lost
        # nothing — the dead row is the operator record
        # (dead_other/dead_reasons columns). Missing summaries,
        # missing messages and missing threads are actual loss.
        lost = (missing + max(0, messages - stored)
                + max(0, expected_threads - threads_n))

        # Per-stage latency attribution + orphan audit over the arm's
        # pipeline trace (tools/tracepath.py): names the bottleneck
        # stage and proves the span DAG stayed connected under faults.
        from copilot_for_consensus_tpu.tools import tracepath

        trace_report = tracepath.analyze(trace_collector.spans())

        p.stop_throttling()
        for pool in p.worker_pools:
            pool.stop()      # flips flags AND joins (logs stuck workers)
        for sub in p.ext_subscribers:
            sub.close()
        stop_sampler.set()
        sampler.join(timeout=2)
        for svc in p.services:
            try:
                svc.publisher.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        p.store.close()
        if holder["broker"] is not None:
            holder["broker"].stop()
        return {
            "messages": messages, "msgs_stored": stored,
            "run_s": round(run_s, 2),
            "max_depth": dict(sorted(max_depth.items())),
            "worst_depth": max(max_depth.values(), default=0),
            "final_depth_max": final_depth,
            "lost": lost, "duplicated": dup,
            "quarantined": quarantined, "dead_other": dead_other,
            "dead_reasons": dead_reasons,
            "replayed_publishes": pstats["replayed"],
            "parked_publishes": pstats["parked"],
            "throttle_waits": pstats["throttle_waits"],
            "redelivered": sum(1 for f in fired
                               if f.get("kind") == "ack"),
            "recovered_by_sweep": max(0, swept_from - missing),
            "faults_fired": len(fired),
            "threads": threads_n,
            "threads_missing_summary": missing,
            "trace": trace_report,
            # stage-span deliveries with a redelivery attempt > 0 —
            # in a fault-free arm every one was shutdown-caused
            "redelivered_spans": sum(
                1 for s in trace_collector.spans()
                if getattr(s, "attempt", 0) > 0),
            "drain": drain_info,
        }

    tmp_root = pathlib.Path(tempfile.mkdtemp(prefix="pipe-chaos-"))
    try:
        log(f"pipeline_chaos: overload arm, backpressure OFF "
            f"({msgs_flood} msgs, drag {drag_s}s)")
        off = run_arm(tmp_root / "off", msgs_flood, n_arch_flood,
                      watermark=0, drag=drag_s)
        log(f"pipeline_chaos: OFF worst depth {off['worst_depth']} "
            f"(scaled warn SLO {scaled_slo}) in {off['run_s']}s")
        log(f"pipeline_chaos: overload arm, backpressure ON (hw={hw})")
        on = run_arm(tmp_root / "on", msgs_flood, n_arch_flood,
                     watermark=hw, drag=drag_s)
        log(f"pipeline_chaos: ON worst depth {on['worst_depth']} "
            f"({on['throttle_waits']} throttle waits) in {on['run_s']}s")

        # the seeded storm plan: occurrence-window faults per boundary
        # kind (bus/faults.py shares ONE boundary across bus + stores,
        # so the windows land wherever the interleaving puts them —
        # the assertions must hold under any interleaving)
        storm_plan = {"seed": seed, "specs": [
            {"kind": "archive_read", "at": 2, "count": 1},
            {"kind": "store_write", "at": 40, "count": 2},
            {"kind": "store_write", "at": 160, "count": 9},
            {"kind": "vector_upsert", "at": 6, "count": 2},
            {"kind": "ack", "at": 30, "count": 3},
            {"kind": "fetch", "at": 120, "count": 3},
            {"kind": "publish", "at": 180, "count": 6},
        ]}
        log(f"pipeline_chaos: storm arm ({msgs_storm} msgs, broker "
            f"restart + faults + {n_poison} poison)")
        storm = run_arm(tmp_root / "storm", msgs_storm, n_arch,
                        watermark=hw, faults=storm_plan, storm=True)

        # graceful-drain arm (ISSUE 12): fault-free, drained mid-wave
        # through the lifecycle sequence then warm-resumed — zero
        # redeliveries proves shutdown itself nacked nothing
        msgs_drain = int(knob("BENCH_PIPE_DRAIN_MESSAGES", "400"))
        n_arch_drain = int(knob("BENCH_PIPE_DRAIN_ARCHIVES", "2"))
        log(f"pipeline_chaos: graceful-drain arm ({msgs_drain} msgs, "
            f"drain mid-wave + warm resume)")
        drain_arm = run_arm(tmp_root / "drain", msgs_drain,
                            n_arch_drain, watermark=hw,
                            drain_midway=True)

        # process-kill phase (ISSUE 12): journaled engine storm in a
        # child process, SIGKILL mid-storm, warm restart from the
        # journal
        kill = journal_kill_phase(tmp_root / "kill", knob)
    finally:
        shutil.rmtree(tmp_root, ignore_errors=True)

    backpressure_ok = (on["worst_depth"] < scaled_slo
                       and off["worst_depth"] >= 2 * scaled_slo)
    # zero orphan spans under faults: redelivery, outbox replay and the
    # broker restart must yield annotated retries, never disconnected
    # trace fragments (obs/trace.py orphan audit over the storm arm)
    storm_ok = (storm["lost"] == 0 and storm["duplicated"] == 0
                and storm["quarantined"] == n_poison
                and storm["replayed_publishes"] >= 1
                and storm["redelivered"] >= 1
                and storm["final_depth_max"] < scaled_slo
                and storm["trace"]["orphan_spans"] == 0)
    # graceful drain: everything still completed, the drain sequence
    # ran to the end (consumers joined, outbox flushed, zero leases
    # left behind), and the arm saw ZERO redeliveries — shutdown
    # itself nacked nothing
    drain_state = drain_arm.get("drain") or {}
    graceful_drain_ok = (
        drain_arm["lost"] == 0
        and bool(drain_state.get("consumers_stopped"))
        and bool(drain_state.get("outbox_flushed"))
        and drain_state.get("inflight_after_drain", 1) == 0
        and drain_arm["redelivered_spans"] == 0)
    kill_ok = bool(kill.get("kill_ok"))
    pipeline_chaos_ok = bool(backpressure_ok and storm_ok
                             and graceful_drain_ok and kill_ok)
    msg_s = storm["messages"] / max(storm["run_s"], 1e-6)
    audit = {
        **{k: storm[k] for k in
           ("lost", "duplicated", "quarantined", "replayed_publishes",
            "redelivered", "recovered_by_sweep", "final_depth_max")},
        "journal_replayed": kill.get("journal_replayed", 0),
        "telemetry_recovered_ok": kill.get("telemetry_recovered_ok",
                                           False),
        "spool_rows": kill.get("telemetry", {}).get("spool_rows", 0),
        "spool_lost": kill.get("telemetry", {}).get("spool_lost", -1),
        "shutdown_redeliveries": drain_arm["redelivered_spans"],
        "max_depth_backpressure_on": on["worst_depth"],
        "max_depth_backpressure_off": off["worst_depth"],
        # stage attribution from the sustained-overload arm (the
        # SCALE_BROKER failure shape): with chunking dragged below
        # supply, tracepath must name it — the measurement ROADMAP
        # item 5's parallelization work is judged against
        "stage_p95_s": on["trace"]["stage_p95_s"],
        "queue_wait_p95_s": on["trace"]["queue_wait_p95_s"],
        "bottleneck_stage": on["trace"]["bottleneck_stage"],
        "orphan_spans": storm["trace"]["orphan_spans"],
    }
    log(f"pipeline_chaos: lost {storm['lost']}, dup "
        f"{storm['duplicated']}, quarantined {storm['quarantined']}, "
        f"replayed {storm['replayed_publishes']}, redelivered "
        f"{storm['redelivered']}, depth on/off {on['worst_depth']}/"
        f"{off['worst_depth']}, bottleneck "
        f"{on['trace']['bottleneck_stage'] or '<none>'}, orphan spans "
        f"{storm['trace']['orphan_spans']}, drain_ok "
        f"{graceful_drain_ok}, kill_ok {kill_ok}, "
        f"ok {pipeline_chaos_ok}")
    return {
        "metric": f"host pipeline under seeded storm (broker restart "
                  f"+ store faults + consumer crash + poison + "
                  f"overload; {msgs_storm} msgs / {n_arch} archives, "
                  f"durable zmq broker, mock inference)",
        "value": round(msg_s, 2),
        "unit": "msg/s",
        # SCALE_BROKER.json broker_total messages_per_s on this host
        "vs_baseline": round(msg_s / 59.6, 3),
        **pipeline_chaos_columns(audit),
        "warn_slo_scaled": scaled_slo,
        "high_watermark": hw,
        "workers_per_stage": workers,
        "throttle_waits": storm["throttle_waits"]
        + on["throttle_waits"],
        "threads": storm["threads"],
        "threads_missing_summary": storm["threads_missing_summary"],
        "faults_fired": storm["faults_fired"],
        "backpressure_ok": backpressure_ok,
        "storm_ok": storm_ok,
        "graceful_drain_ok": graceful_drain_ok,
        "kill_ok": kill_ok,
        "pipeline_chaos_ok": pipeline_chaos_ok,
        "max_queue_depth_storm": storm["max_depth"],
        "fault_plan": storm_plan,
        "kill_phase": kill,
        "arms": {
            "backpressure_off": {k: off[k] for k in
                                 ("messages", "run_s", "worst_depth",
                                  "final_depth_max", "lost",
                                  "max_depth")},
            "backpressure_on": {k: on[k] for k in
                                ("messages", "run_s", "worst_depth",
                                 "final_depth_max", "lost",
                                 "throttle_waits", "max_depth")},
            "storm": {k: v for k, v in storm.items()
                      if k != "max_depth"},
            "graceful_drain": {
                "messages": drain_arm["messages"],
                "run_s": drain_arm["run_s"],
                "lost": drain_arm["lost"],
                "duplicated": drain_arm["duplicated"],
                "redelivered_spans": drain_arm["redelivered_spans"],
                "drain": drain_state,
            },
        },
    }


# -- multichip_serving (ISSUE 15): subprocess-per-chip-count ------------
#
# Every measurement runs in a CHILD interpreter whose XLA_FLAGS pin the
# virtual device count BEFORE jax initializes (the same trick the test
# conftest uses) — the parent never imports jax, so one chip count's
# platform state cannot leak into the next.


def _mc_knob(name: str, default: str) -> str:
    preset_vals = PRESETS.get("multichip_serving", {})
    return os.environ.get(name, preset_vals.get(name, default))


def _mc_child_env(chips: int, mode: str, spool_dir: str = "",
                  spool_proc: str = "") -> dict:
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}",
        "BENCH_MC_CHILD": mode,
        "BENCH_PRESET": "", "BENCH_PREFLIGHT": "0",
    }
    if spool_dir:
        # child ships its engine telemetry (obs/ship.py) into a spool
        # named after spool_proc; the parent aggregates the directory
        env["BENCH_MC_SPOOL_DIR"] = spool_dir
        env["BENCH_MC_SPOOL_PROC"] = spool_proc
    return env


def multichip_serving_headline() -> dict:
    import shutil
    import tempfile

    chip_counts = [int(c) for c in
                   _mc_knob("BENCH_MC_CHIPS", "1,2,4,8").split(",")]
    me = os.path.abspath(__file__)
    py = sys.executable
    # every child ships its engine telemetry into a spool here; the
    # parent merges the directory (obs/ship.py TelemetryAggregator)
    # into the real cross-process TTFT/ITL histograms the columns and
    # the SLO scoreboard are computed from (ISSUE 20)
    spool_dir = tempfile.mkdtemp(prefix="bench-mc-spool-")
    scaling: dict[int, dict] = {}
    rows = []
    ok = True
    try:
        for chips in chip_counts:
            row = _run_row(f"scale-{chips}", [py, me],
                           _mc_child_env(chips, f"scale:{chips}",
                                         spool_dir, f"scale-{chips}"),
                           timeout=900.0)
            rows.append(row)
            if not row.get("ok"):
                ok = False
            scaling[chips] = row
        disagg = _run_row("disagg", [py, me],
                          _mc_child_env(max(chip_counts), "disagg",
                                        spool_dir, "disagg"),
                          timeout=900.0)
        rows.append(disagg)
        if not disagg.get("ok"):
            ok = False
        # Kernel-route arm (ISSUE 16): one more child at the top chip
        # count with the Pallas route pinned on — the mesh-sharded
        # kernel dispatch family compiles (interpret mode on virtual
        # CPU devices) and its tok/s lands next to the reference
        # child's every round.
        top = max(chip_counts)
        kern = _run_row(f"kernel-{top}", [py, me],
                        {**_mc_child_env(top, f"scale:{top}",
                                         spool_dir, f"kernel-{top}"),
                         "BENCH_KV_KERNEL": "pallas"},
                        timeout=900.0)
        rows.append(kern)
        if not kern.get("ok"):
            ok = False
        spool = _mc_spool_columns(spool_dir, chip_counts)
    finally:
        shutil.rmtree(spool_dir, ignore_errors=True)
    cols = multichip_columns(scaling, disagg, spool)
    tol = float(_mc_knob("BENCH_MC_ITL_TOL", "1.5"))
    itl_ok = (disagg.get("ok", False)
              and cols["itl_p95_disagg_s"]
              <= tol * max(cols["itl_p95_coloc_s"], 1e-9))
    # telemetry gate (ISSUE 20): every child spool fully recoverable
    # (no seq gaps) and the merged registries yielded a real TTFT
    # histogram at EVERY chip count — the spool-derived columns are
    # only trustworthy if nothing was lost and nothing came up empty
    spool_ok = bool(
        spool.get("spool_lost", -1) == 0
        and spool.get("spool_rows", 0) > 0
        and all(v is not None
                for v in spool.get("ttft_p99_by_chips", {}).values())
        and len(spool.get("ttft_p99_by_chips", {})) == len(chip_counts))
    out = {
        "metric": "multi-chip sharded-paged serving "
                  f"({max(chip_counts)} virtual CPU chips, "
                  "dp-sharded block pool + prefill/decode role split)",
        "value": cols["tok_s_per_chip"],
        "unit": "tok/s/chip",
        "vs_baseline": 0.0,     # virtual chips: no cross-hw baseline
        "multichip_ok": bool(ok and itl_ok and spool_ok),
        "itl_flat_ok": bool(itl_ok),
        "itl_tolerance": tol,
        "spool_ok": spool_ok,
        "rows": rows,
    }
    out.update(cols)
    out["kernel_route"] = kernel_route_columns(
        kern.get("kv_route", ""),
        float(scaling[top].get("tok_s", 0.0)),
        float(kern.get("tok_s", 0.0)))
    if not (ok and itl_ok and spool_ok):
        out["ok"] = False
        if not ok:
            out["reason"] = "a multichip child row failed"
        elif not itl_ok:
            out["reason"] = ("disaggregated decode ITL p95 "
                             f"{cols['itl_p95_disagg_s']}s > {tol}x "
                             f"co-located {cols['itl_p95_coloc_s']}s")
        else:
            out["reason"] = ("telemetry spool audit failed: "
                             f"{spool.get('error', spool)}")
    return out


def _mc_spool_columns(spool_dir: str, chip_counts: list[int]) -> dict:
    """Merge every multichip child's spool (obs/ship.py) and derive the
    cross-process latency columns: TTFT p99 per chip count (from each
    scale child's shipped ``engine_ttft_seconds`` histogram), fleet
    ITL p95, and the declarative SLO scoreboard verdict (obs/slo.py)
    over the merged registry — real histograms crossing OS processes,
    not parsed summary lines."""
    out: dict = {"spool_rows": 0, "spool_lost": -1,
                 "ttft_p99_by_chips": {}, "itl_p95_s": 0.0,
                 "slo_ok": False, "slo": {}}
    try:
        from copilot_for_consensus_tpu.obs.ship import (
            TelemetryAggregator,
        )
        from copilot_for_consensus_tpu.obs.slo import (
            default_registry,
            histogram_percentile,
        )

        agg = TelemetryAggregator()
        stats = agg.ingest_dir(spool_dir)
        if not stats:
            out["error"] = f"no spools under {spool_dir}"
            return out
        out["spool_rows"] = sum(s["applied"] for s in stats)
        out["spool_lost"] = sum(s["lost"] for s in stats)
        for chips in chip_counts:
            v = histogram_percentile(
                agg.metrics, "engine_ttft_seconds", 0.99,
                {"proc": f"scale-{chips}"})
            out["ttft_p99_by_chips"][str(chips)] = (
                round(v, 6) if v is not None else None)
        itl = histogram_percentile(agg.metrics, "engine_itl_seconds",
                                   0.95)
        out["itl_p95_s"] = round(itl, 6) if itl is not None else 0.0
        board = default_registry().evaluate(agg.metrics)
        out["slo_ok"] = board["ok"]
        out["slo"] = {r["name"]: r["ok"] for r in board["objectives"]}
    except Exception as exc:  # a broken spool fails the spool_ok gate
        out["error"] = f"{type(exc).__name__}: {exc}"
    return out


def _mc_build_engine(mesh, role="both", **overrides):
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine,
    )
    from copilot_for_consensus_tpu.models import decoder_config

    cfg = decoder_config(_mc_knob("BENCH_MODEL", "tiny"))
    kw = dict(
        num_slots=int(_mc_knob("BENCH_SLOTS", "8")),
        max_len=int(_mc_knob("BENCH_MAX_LEN", "128")),
        prefill_buckets=(int(_mc_knob("BENCH_PROMPT_LEN", "32")),),
        dtype=jnp.float32,
        kv_dtype=_mc_knob("BENCH_KV_DTYPE", "float32"),
        attn_impl="xla",
        quantize=False,
        decode_window=int(_mc_knob("BENCH_DECODE_WINDOW", "4")),
        prefill_chunk=int(_mc_knob("BENCH_PREFILL_CHUNK", "16")),
        kv_pool_blocks=int(_mc_knob("BENCH_KV_POOL_BLOCKS", "64")),
        kv_kernel=_mc_knob("BENCH_KV_KERNEL", "auto"),
        mesh=mesh, role=role, seed=0,
    )
    kw.update(overrides)
    return GenerationEngine(cfg, **kw), cfg


def _mc_mesh(chips: int):
    if chips == 1:
        return None
    from copilot_for_consensus_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    tp = min(int(_mc_knob("BENCH_MC_TP", "2")), chips)
    while chips % tp:
        tp //= 2
    return build_mesh(MeshConfig(dp=chips // tp, tp=tp))


def _mc_child_scale(chips: int) -> dict:
    import numpy as np

    eng, cfg = _mc_build_engine(_mc_mesh(chips))
    rng = np.random.default_rng(0)
    plen = int(_mc_knob("BENCH_PROMPT_LEN", "32"))
    new = int(_mc_knob("BENCH_NEW_TOKENS", "16"))
    prompts = [rng.integers(3, cfg.vocab_size, size=plen).tolist()
               for _ in range(eng.num_slots)]
    eng.generate(prompts, max_new_tokens=new)          # warmup/compile
    # shippers baseline (mark) HERE — the shipped histograms cover the
    # timed window only, same as the direct telemetry columns
    shippers = _mc_make_shippers(
        [(eng, "", "serve")], default_proc=f"scale-{chips}")
    t0 = time.monotonic()
    comps = eng.generate(prompts, max_new_tokens=new)
    elapsed = time.monotonic() - t0
    total_new = sum(len(c.tokens) for c in comps)
    tele = telemetry_columns(eng, last_n=eng.num_slots)
    spool_rows = _mc_close_shippers(shippers)
    return {"chips": chips, "tok_s": round(total_new / elapsed, 2),
            "ttft_p99_s": tele.get("ttft_p99_s", 0.0),
            "kv_route": eng._kv_route,
            "spool_rows": spool_rows,
            "elapsed_s": round(elapsed, 2)}


def _mc_make_shippers(engines: list, default_proc: str) -> list:
    """One crash-safe spool shipper per engine under BENCH_MC_SPOOL_DIR
    (obs/ship.py; empty list when shipping is off) — the child half of
    the multichip telemetry merge. ``engines`` is ``[(engine,
    proc_suffix, role), ...]``; the spool proc name is the
    parent-assigned BENCH_MC_SPOOL_PROC plus the suffix (role-split
    children ship one spool per role). Each shipper is baselined via
    ``mark()`` so only observations AFTER this call ship."""
    spool_dir = _mc_knob("BENCH_MC_SPOOL_DIR", "")
    if not spool_dir:
        return []
    from copilot_for_consensus_tpu.obs.ship import (
        TelemetryShipper,
        spool_path,
    )

    base = _mc_knob("BENCH_MC_SPOOL_PROC", default_proc)
    shippers = []
    for eng, suffix, role in engines:
        if eng.telemetry is None:
            continue
        proc = f"{base}-{suffix}" if suffix else base
        shipper = TelemetryShipper(
            spool_path(spool_dir, proc), proc=proc, role=role,
            metrics=eng.telemetry.metrics,
            recorder=eng.telemetry.recorder)
        shipper.mark()
        shippers.append(shipper)
    return shippers


def _mc_close_shippers(shippers: list) -> int:
    """Final flush + close; returns total committed spool rows."""
    total = 0
    for shipper in shippers:
        shipper.flush()
        total += shipper.stats()["committed_rows"]
        shipper.close()
    return total


def _mc_child_disagg() -> dict:
    """Two arms on the full virtual mesh: co-located engine vs a real
    two-thread prefill-role/decode-role deployment with block-granular
    KV handoffs. Long decode streams measure ITL while short prefill
    arrivals keep hitting admission the whole run — the exact spike
    disaggregation exists to remove."""
    import queue as queue_mod
    import threading

    import numpy as np

    rng = np.random.default_rng(0)
    plen = int(_mc_knob("BENCH_PROMPT_LEN", "32"))
    long_new = int(_mc_knob("BENCH_MC_LONG_NEW", "48"))
    arrivals_per_step = int(_mc_knob("BENCH_MC_ARRIVALS", "2"))

    def _prompts(n, size):
        return [rng.integers(3, 500, size=size).tolist()
                for _ in range(n)]

    def _long_itls(telemetry, long_plen):
        itls = sorted(t.itl_s for t in telemetry.completed
                      if t.prompt_len == long_plen and t.new_tokens > 1)
        if not itls:
            return 0.0
        return itls[min(len(itls) - 1, int(0.95 * (len(itls) - 1)))]

    # ---- co-located arm: admission waves share the decode loop ----
    from copilot_for_consensus_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    mesh = build_mesh(MeshConfig(dp=2, tp=2),
                      devices=_mc_devices()[:4])
    eng, cfg = _mc_build_engine(mesh)
    longs = _prompts(4, plen)
    shorts = _prompts(64, plen - 1)
    # warmup both programs
    eng.generate(_prompts(2, plen) + _prompts(2, plen - 1),
                 max_new_tokens=4)
    long_ids = {eng.submit(p, max_new_tokens=long_new) for p in longs}
    done: set = set()
    si = 0
    while not long_ids <= done:
        for _ in range(arrivals_per_step):
            if si < len(shorts):
                eng.submit(shorts[si], max_new_tokens=4)
                si += 1
        for c in eng.step():
            done.add(c.request_id)
    itl_coloc = _long_itls(eng.telemetry, plen)

    # ---- disaggregated arm: prefill chips feed decode chips -------
    devs = _mc_devices()
    pre_mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=devs[:4])
    dec_mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=devs[4:8])
    pre, _ = _mc_build_engine(pre_mesh, role="prefill")
    dec, _ = _mc_build_engine(dec_mesh, role="decode")
    handoffs: "queue_mod.Queue" = queue_mod.Queue()
    stop = threading.Event()
    waits: list[float] = []

    def prefill_loop():
        si = 0
        for p in longs:
            pre.submit(p, max_new_tokens=long_new)
        while not stop.is_set():
            if si < len(shorts):
                for _ in range(arrivals_per_step):
                    if si < len(shorts):
                        pre.submit(shorts[si], max_new_tokens=4)
                        si += 1
            pre.step()
            for h in pre.take_prefilled():
                handoffs.put(h)

    t = threading.Thread(target=prefill_loop, daemon=True)
    # decode engine warmup BEFORE the race starts (compile off-clock)
    dec_w, _ = _mc_build_engine(dec_mesh)
    dec_w.generate(_prompts(2, plen), max_new_tokens=4)
    del dec_w
    shippers = _mc_make_shippers(
        [(pre, "prefill", "prefill"), (dec, "decode", "decode")],
        default_proc="disagg")
    t.start()
    need = len(longs)
    got = 0
    pending = []
    while got < need:
        try:
            pending.append(handoffs.get(timeout=0.05))
        except queue_mod.Empty:
            pass
        still = []
        for h in pending:
            rid = dec.admit_prefilled(h)
            if rid is None:
                still.append(h)
            else:
                waits.append(max(0.0, time.monotonic() - h.ready_at))
                if dec.telemetry is not None:
                    dec.telemetry.on_handoff(h.blocks, waits[-1])
        pending = still
        for c in dec.step():
            if c.prompt_len == plen:
                got += 1
    stop.set()
    t.join(timeout=10)
    itl_disagg = _long_itls(dec.telemetry, plen)
    # one spool per role: the parent's merge sees the prefill and
    # decode registries as distinct procs with role labels, which is
    # what the kv-handoff-wait SLO and the role-split exposition need
    spool_rows = _mc_close_shippers(shippers)
    return {
        "itl_p95_coloc_s": round(itl_coloc, 6),
        "itl_p95_disagg_s": round(itl_disagg, 6),
        "handoff_ms": round(
            1000 * sum(waits) / len(waits), 3) if waits else 0.0,
        "handoffs": len(waits),
        "spool_rows": spool_rows,
    }


def _mc_devices():
    import jax

    return jax.devices()


def _mc_child_main(mode: str) -> None:
    # the parent pinned JAX_PLATFORMS=cpu and the virtual device count
    # in this child's env (_mc_child_env) before jax initializes
    if mode.startswith("scale:"):
        out = _mc_child_scale(int(mode.split(":", 1)[1]))
    elif mode == "disagg":
        out = _mc_child_disagg()
    else:
        raise SystemExit(f"unknown BENCH_MC_CHILD mode {mode!r}")
    print(json.dumps(out))


# -- headline -----------------------------------------------------------

def headline() -> dict:
    if os.environ.get("BENCH_PRESET", "") == "pipeline_chaos":
        # Host-only pipeline gate (mock inference drivers): no jax, no
        # device — dispatched before the import below on purpose.
        return pipeline_chaos_headline()
    if os.environ.get("BENCH_PRESET", "") == "multichip_serving":
        # Subprocess-per-chip-count orchestration: the parent never
        # imports jax (each child pins its own virtual device count).
        return multichip_serving_headline()
    import jax

    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
        require_accelerator,
    )

    # From here on this process owns the device: refuse a backend
    # nobody asked for, and keep compiled programs at a fixed place.
    require_accelerator("bench.py")
    log(f"compile cache: {enable_compile_cache()}")

    if os.environ.get("BENCH_PRESET", "") == "mixed_traffic":
        # The scheduler gate is a two-arm scripted-arrival run, not a
        # generate()-to-completion throughput shape.
        return mixed_traffic_headline()
    if os.environ.get("BENCH_PRESET", "") == "chaos":
        # The resilience gate is a two-arm fault-injection run.
        return chaos_headline()
    if os.environ.get("BENCH_PRESET", "") == "ann_retrieval":
        # The retrieval gate times two vector-store routes over one
        # corpus — no generation engine at all.
        return ann_retrieval_headline()

    # Preset values fill in behind explicit env vars WITHOUT mutating
    # os.environ.
    preset_vals = PRESETS.get(os.environ.get("BENCH_PRESET", ""), {})

    def knob(name: str, default: str) -> str:
        return os.environ.get(name, preset_vals.get(name, default))

    model = knob("BENCH_MODEL", "mistral-7b")
    # fp8 KV cache (the default) halves cache HBM; 16-bit caches halve
    # the slot ceiling with it (BENCH_KV_DTYPE=bfloat16 restores the
    # full-precision cache).
    kv_name = knob("BENCH_KV_DTYPE", "float8_e4m3fn")
    # Decode is weight-bandwidth-bound, so throughput scales near-
    # linearly with batch until the KV cache fills HBM: 128 slots x
    # 256 ctx fit a 16GB v5e next to 7GB int8 weights with the fp8
    # cache, 64 with bf16.
    default_slots = 128 if kv_name.startswith("float8") else 64
    slots = int(knob("BENCH_SLOTS", str(default_slots)))
    # 256 covers prompt 128 + 96 new tokens + window slack; decode is
    # HBM-bound so cache extent is throughput (with kv-bucketed decode
    # the extent adapts, but the allocation bound still matters).
    max_len = int(knob("BENCH_MAX_LEN", "256"))
    prompt_len = int(knob("BENCH_PROMPT_LEN", "128"))
    new_tokens = int(knob("BENCH_NEW_TOKENS", "96"))
    window = int(knob("BENCH_DECODE_WINDOW", "32"))
    # Prefix-cache geometry (shared_prefix preset): streams share a
    # leading span of this many tokens; > 0 also enables the block pool.
    shared_prefix = int(knob("BENCH_SHARED_PREFIX", "0"))
    prefix_blocks = int(knob("BENCH_PREFIX_BLOCKS",
                             "64" if shared_prefix else "0"))
    # Speculative decoding (spec_decode preset): prompt-lookup drafts
    # + multi-token verify dispatch; prompts are built copy-heavy.
    spec_on = knob("BENCH_SPEC_DECODE", "0") == "1"
    # Paged KV (paged_capacity preset, or BENCH_PAGED=1 on any engine
    # preset — e.g. shared_prefix re-run paged to show the savings
    # survive with the copies removed): the block pool replaces the
    # per-slot contiguous cache; BENCH_KV_POOL_BLOCKS sizes it.
    paged_on = knob("BENCH_PAGED", "0") == "1"
    kv_pool_blocks = int(knob("BENCH_KV_POOL_BLOCKS",
                              "1024" if paged_on else "0"))
    # Paged dispatch route (ISSUE 16): "auto" lets the engine pick per
    # backend (Pallas kernel on TPU, XLA reference elsewhere);
    # "pallas"/"reference" pin it. Value typos already failed loudly in
    # main(); a pinned route without the paged engine fails the same
    # way here — the engine would raise, but the driver should record
    # a structured artifact, not a stack trace.
    kv_kernel = knob("BENCH_KV_KERNEL", "auto")
    if kv_kernel != "auto" and not paged_on:
        print(json.dumps({
            "metric": "bench-kv-kernel",
            "value": 0.0,
            "unit": "",
            "ok": False,
            "reason": f"BENCH_KV_KERNEL {kv_kernel!r} pins a paged "
                      "dispatch route but BENCH_PAGED is off",
        }))
        sys.exit(2)
    # Flight recorder / telemetry (engine/telemetry.py): default ON —
    # the artifact's TTFT/ITL/occupancy columns come from it.
    # BENCH_TELEMETRY=0 is the overhead-measurement arm (run
    # decode_heavy both ways; budget <1%).
    tele_on = knob("BENCH_TELEMETRY", "1") == "1"
    # Telemetry shipping (obs/ship.py): default ON — the timed run
    # executes with a live spool pump thread, so the headline number
    # already pays the shipping cost. BENCH_SHIP=0 is the off arm of
    # the overhead measurement (run decode_heavy both ways; the
    # on-vs-off tok/s delta is the ISSUE-20 <1% budget).
    ship_on = tele_on and knob("BENCH_SHIP", "1") == "1"
    # Chaining windows in-program amortizes the per-dispatch host sync
    # while keeping the efficient 32-step window buffers; 3×32 = the
    # full 96-token run in ONE dispatch. Past max_len 256 the default
    # is single windows. Both values predate the current chip and
    # toolchain: 3 vs 1 is not measured on the current chip.
    default_windows = "3" if max_len <= 256 else "1"
    n_windows = int(knob("BENCH_WINDOWS_PER_DISPATCH", default_windows))

    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.engine.generation import GenerationEngine
    from copilot_for_consensus_tpu.models import decoder_config

    dev = jax.devices()[0]
    log(f"device: {dev.device_kind} ({dev.platform}), model: {model}, "
        f"slots={slots} max_len={max_len}")

    # int4 halves weight HBM (and the decode step's weight traffic)
    # again over int8: ~3.5 GB for Mistral-7B, freeing cache room for
    # more concurrent streams on top of the bandwidth win.
    wq = knob("BENCH_WEIGHT_DTYPE", "int8")
    quantize = (False if knob("BENCH_QUANTIZE", "1") != "1" else wq)
    if knob("BENCH_PALLAS", "1") != "1":
        from copilot_for_consensus_tpu.models import quant
        quant.set_pallas_qmatmul(False)
    if knob("BENCH_ACT_QUANT", "0") == "1":
        from copilot_for_consensus_tpu.models import quant
        quant.set_act_quant("a8")
    cfg = decoder_config(model)
    t0 = time.monotonic()
    # With a shared prefix the steady state prefills only the unique
    # tail, so give the admission wave a tail-sized bucket next to the
    # cold-start full-prompt bucket.
    buckets = tuple(sorted({prompt_len, max(1, prompt_len - shared_prefix)}))
    # Shared ctor kwargs so the kernel-route arm below rebuilds the
    # EXACT same engine with only kv_kernel flipped — any other drift
    # between the two arms would make the delta column a lie.
    eng_kwargs = dict(
        num_slots=slots,
        max_len=max_len,
        prefill_buckets=buckets,
        prefix_cache_blocks=prefix_blocks,
        kv_pool_blocks=kv_pool_blocks if paged_on else 0,
        kv_kernel=kv_kernel,
        dtype=jnp.bfloat16,
        kv_dtype=kv_name,
        seed=0,
        quantize=quantize,
        decode_window=window,
        windows_per_dispatch=n_windows,
        admission_token_budget=int(knob("BENCH_ADMIT_TOKENS", "16384")),
        prefill_chunk=int(knob("BENCH_PREFILL_CHUNK", "64")),
        spec_decode=spec_on,
        telemetry=tele_on,
    )
    eng = GenerationEngine(cfg, **eng_kwargs)
    log(f"engine built (random {model} weights, "
        f"{quantize or 'bf16'}) in {time.monotonic() - t0:.1f}s")

    rng = np.random.default_rng(0)
    if shared_prefix:
        common = rng.integers(3, cfg.vocab_size,
                              size=shared_prefix).tolist()
        prompts = [
            common + rng.integers(
                3, cfg.vocab_size,
                size=prompt_len - shared_prefix).tolist()
            for _ in range(slots)
        ]
    elif spec_on:
        # Copy-heavy: the back half of each prompt re-quotes spans of
        # its front half (per-stream unique content), so the n-gram
        # index has verbatim copies to draft from — the
        # summarization/RAG workload shape speculation targets.
        half = prompt_len // 2
        prompts = []
        for _ in range(slots):
            head = rng.integers(3, cfg.vocab_size, size=half).tolist()
            tail = []
            while len(tail) < prompt_len - half:
                s0 = int(rng.integers(0, max(1, half - 32)))
                tail.extend(head[s0:s0 + 32])
            prompts.append(head + tail[:prompt_len - half])
    else:
        prompts = [
            rng.integers(3, cfg.vocab_size, size=prompt_len).tolist()
            for _ in range(slots)
        ]

    # Warmup: compile the steady-state programs — the fused admit
    # program (prefill + insert + first-token sample) and every decode
    # kv bucket the timed run will hit.
    t0 = time.monotonic()
    eng.generate(prompts, max_new_tokens=new_tokens)
    if prefix_blocks:
        # The first pass was all cache MISSES (blocks publish at
        # retire), so it compiled only the plain admit program; the
        # timed run is all HITS and would otherwise pay the seeded-wave
        # compile inside its measurement. One more pass compiles it.
        eng.generate(prompts, max_new_tokens=new_tokens)
    log(f"warmup (compile + first full run) {time.monotonic() - t0:.1f}s")

    # Timed run: keep all slots busy for `new_tokens` decode steps each.
    shipper = None
    ship_dir = ""
    if ship_on:
        # live pump thread for the whole timed window — the shipped
        # arm measures real background spooling, not a post-hoc flush
        import tempfile

        from copilot_for_consensus_tpu.obs.ship import TelemetryShipper

        ship_dir = tempfile.mkdtemp(prefix="bench-ship-")
        shipper = TelemetryShipper(
            os.path.join(ship_dir, "decode-heavy.spool.sqlite3"),
            proc="decode-heavy", role="serve",
            metrics=eng.telemetry.metrics,
            recorder=eng.telemetry.recorder).start()
    admit_s0 = eng.admitted_s
    ps0 = eng.prefix_stats()
    ss0 = eng.spec_stats()
    kv0 = eng.kv_pool_stats()
    t0 = time.monotonic()
    comps = eng.generate(prompts, max_new_tokens=new_tokens)
    elapsed = time.monotonic() - t0
    ship_stats = None
    if shipper is not None:
        # the timed window is over — final flush, grab the spool
        # accounting for the artifact, then tear down
        import shutil as _shutil

        shipper.stop()
        shipper.flush()
        ship_stats = shipper.stats()
        shipper.close()
        _shutil.rmtree(ship_dir, ignore_errors=True)
    total_new = sum(len(c.tokens) for c in comps)
    total_all = total_new + sum(c.prompt_len for c in comps)
    tok_s = total_new / elapsed
    admit_s = eng.admitted_s - admit_s0   # sums multi-wave admissions
    log(f"{total_new} new tokens ({total_all} incl. prompts) in "
        f"{elapsed:.2f}s across {slots} streams "
        f"(admission {admit_s:.2f}s, decode+sync {elapsed - admit_s:.2f}s; "
        f"total throughput {total_all / elapsed:.0f} tok/s)")

    out = {
        "metric": f"{model} continuous-batching decode throughput "
                  f"(1 chip, {slots} streams, {prompt_len}-tok prompts, "
                  f"{quantize or 'bf16'} weights)",
        "value": round(tok_s, 2),
        "unit": "tok/s",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 3),
        "total_tok_s": round(total_all / elapsed, 1),
        "ship_on": ship_on,
    }
    if ship_stats is not None:
        out["ship_rows"] = int(ship_stats["committed_rows"])
        out["ship_flushes"] = int(ship_stats["flushes"])
        log(f"telemetry shipping: {out['ship_rows']} spool rows over "
            f"{out['ship_flushes']} flushes (pump thread live during "
            f"the timed run)")
    # Flight-recorder columns: TTFT percentiles / mean ITL over the
    # timed run's completions (one per slot), occupancy from the step
    # records — the recorder, not ad-hoc timers, is the source.
    tcols = telemetry_columns(eng, last_n=slots)
    out.update(tcols)
    if tcols:
        log(f"telemetry: TTFT p50/p95/p99 {tcols['ttft_p50_s']}/"
            f"{tcols['ttft_p95_s']}/{tcols['ttft_p99_s']}s, "
            f"ITL {tcols['itl_mean_s']}s, "
            f"occupancy {tcols['mean_occupancy']}")
    if prefix_blocks:
        # Timed-run deltas (the warmup's cold misses are the cache
        # filling, not the steady state the preset measures).
        out.update(prefix_columns(ps0, eng.prefix_stats()))
        log(f"prefix cache: hit rate {out['prefix_hit_rate']}, "
            f"{out['prefill_tokens_saved']} prompt tokens saved vs "
            f"{out['prefill_tokens']} prefilled")
    if spec_on:
        # Timed-run deltas (warmup compiles both verify buckets and
        # fills the draft indexes' early misses).
        out.update(spec_columns(ss0, eng.spec_stats()))
        log(f"spec decode: draft hit rate {out['draft_hit_rate']}, "
            f"{out['mean_accepted_per_step']} accepted/step, "
            f"{out['tokens_per_weight_pass']} tokens/weight-pass")
    if paged_on:
        out.update(paged_columns(kv0, eng.kv_pool_stats()))
        # which dispatch route the HEADLINE arm actually compiled —
        # the engine's resolution, not the knob's request
        out["kv_route"] = eng._kv_route
        log(f"paged kv: {out['max_concurrent_streams']} peak "
            f"concurrent streams, fragmentation "
            f"{out['kv_pool_fragmentation']}, zero-copy hit rate "
            f"{out['zero_copy_hit_rate']} (route {out['kv_route']})")
    if paged_on and knob("BENCH_KV_KERNEL_ARM", "0") == "1":
        # Kernel-route arm (ISSUE 16): the same shapes re-run with the
        # Pallas route pinned on, reported as a tok/s ratio against
        # the headline arm. The headline engine is dropped first — two
        # live pools would double the cache HBM footprint mid-bench.
        del comps
        del eng
        eng_k = GenerationEngine(cfg, **{**eng_kwargs,
                                         "kv_kernel": "pallas"})
        eng_k.generate(prompts, max_new_tokens=new_tokens)  # warmup
        t0 = time.monotonic()
        comps_k = eng_k.generate(prompts, max_new_tokens=new_tokens)
        k_elapsed = time.monotonic() - t0
        k_tok_s = sum(len(c.tokens) for c in comps_k) / k_elapsed
        out["kernel_route"] = kernel_route_columns(
            eng_k._kv_route, tok_s, k_tok_s)
        log(f"kernel-route arm: {out['kernel_route']['kernel_tok_s']} "
            f"tok/s, {out['kernel_route']['kernel_tok_s_delta']}x the "
            f"{out.get('kv_route', 'reference')} headline arm")
    return out


def main() -> None:
    # multichip child mode: one measurement in a pinned-device-count
    # interpreter (dispatched before anything imports jax)
    mc_child = os.environ.get("BENCH_MC_CHILD", "")
    if mc_child:
        _mc_child_main(mc_child)
        return
    # A typo'd preset must fail LOUDLY: silently running the default
    # shapes under the requested label would record a mislabeled
    # artifact the next round trusts.
    preset = os.environ.get("BENCH_PRESET", "")
    if preset and preset not in PRESETS:
        print(json.dumps({
            "metric": "bench-preset",
            "value": 0.0,
            "unit": "",
            "ok": False,
            "reason": f"unknown BENCH_PRESET {preset!r}; "
                      f"valid: {sorted(PRESETS)}",
        }))
        sys.exit(2)
    # Same discipline for the paged dispatch-route knob (ISSUE 16): a
    # typo'd BENCH_KV_KERNEL silently running the default route would
    # record an artifact labeled with a route it never measured.
    kv_kernel = os.environ.get(
        "BENCH_KV_KERNEL",
        PRESETS.get(preset, {}).get("BENCH_KV_KERNEL", "auto"))
    if kv_kernel not in ("auto", "pallas", "reference"):
        print(json.dumps({
            "metric": "bench-kv-kernel",
            "value": 0.0,
            "unit": "",
            "ok": False,
            "reason": f"unknown BENCH_KV_KERNEL {kv_kernel!r}; "
                      "valid: ['auto', 'pallas', 'reference']",
        }))
        sys.exit(2)
    # Semantic contract preflight (CPU, subprocess): fail fast with a
    # structured artifact — same rc-2/ok:false shape as a bad preset —
    # rather than discovering a dropped donation alias or KV-layout
    # mismatch as an OOM mid-run on the TPU.
    preflight_artifact = shardcheck_preflight()
    if preflight_artifact is None:
        # the paged/mesh/decode presets additionally gate on the
        # compiled artifact (hlocheck: aliases survive compilation,
        # no materializing ops, collective/HBM budgets) — trace-level
        # cleanliness alone has shipped both failure classes
        preflight_artifact = hlocheck_preflight()
    if preflight_artifact is None:
        # pipeline presets gate on the durability contracts instead of
        # (not before) jitted-entrypoint tracing — engine presets map
        # to no dura paths and skip this, mirror-image of shardcheck
        preflight_artifact = duracheck_preflight()
    if preflight_artifact is not None:
        print(json.dumps(preflight_artifact))
        sys.exit(2)
    out = headline()
    if preset in ("pipeline_chaos", "multichip_serving"):
        # pipeline_chaos is host-only and multichip_serving's parent
        # leaves jax to its virtual-CPU children (headline() dispatches
        # both before importing jax): this process held no device
        out.update(platform="none", device_kind="none", device_count=0)
    else:
        import jax

        dev = jax.devices()[0]
        out.update(platform=dev.platform, device_kind=dev.device_kind,
                   device_count=len(jax.devices()))
    # a gate preset's verdict flag IS the run's outcome
    out["ok"] = bool(out.get("ok", True)
                     and out.get(PRESET_GATES.get(preset, ""), True))
    print(json.dumps(out))
    if not out["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
