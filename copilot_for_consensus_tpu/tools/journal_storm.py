"""Process-kill chaos driver for the engine journal (engine/journal.py).

One deterministic tiny-engine storm per process, SIGKILL-able at an
exact step — the child half of the ``pipeline_chaos`` kill phase
(bench.py) and the @slow real-process test
(tests/test_engine_journal.py):

    python -m copilot_for_consensus_tpu.tools.journal_storm \
        --journal /tmp/j.sqlite3 --out /tmp/completions.jsonl \
        --result /tmp/result.json [--kill-after-step 8]

* Fresh journal → submit ``--requests`` deterministic prompts (seeded
  rng; correlation ids ``js-<i>``) and serve them.
* Non-empty journal → submit NOTHING: the engine warm-restarts from
  the journal at construction and this process serves only the
  recovered work.
* Every completion appends one JSON line (``{"cid", "tokens",
  "finish_reason"}``) to ``--out``, flushed+fsynced per step, so a
  SIGKILL loses no record of work that retired (the journal row for a
  retired request is already gone, so the line is the only witness —
  the harness merges pre-kill and post-restart lines and gates
  lost==0 / duplicated==0 across the union).
* ``--kill-after-step N``: after the Nth ``engine.step()`` (lines
  flushed), the process SIGKILLs ITSELF — a real, unhandled process
  death at a deterministic point mid-storm, with queued requests,
  active slots and partially-checkpointed tokens all live.
* ``--spool PATH --proc NAME``: ship telemetry (metric deltas, step
  records, submit/replay spans) into a crash-safe spool (obs/ship.py),
  flushed synchronously per step BEFORE the kill check — so the
  SIGKILLed process's committed spans/steps are recoverable from its
  spool, the ``telemetry_recovered_ok`` gate of the kill phase. Span
  ids are derived deterministically from correlation ids, so the
  resume process's ``engine_replay`` spans parent onto the killed
  process's ``engine_submit`` spans — a real cross-OS-process trace
  the tracepath orphan audit must join, not miscount.

Weights come from the fixed tiny config + seed at f32, so every child
process builds the bit-identical engine and greedy outputs across
kill/restart must equal an uninterrupted run's exactly
(docs/RESILIENCE.md#replay-semantics).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import sys
import time


def build_engine(journal, telemetry: bool = False):
    """The shared tiny deterministic engine (f32 compute AND kv: exact
    greedy bit-identity for continuations, the chaos-preset dtype
    argument). ``telemetry`` is host-side bookkeeping only — token
    streams stay bit-identical either way."""
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine,
    )
    from copilot_for_consensus_tpu.models.configs import DecoderConfig

    cfg = DecoderConfig(name="journal-storm-tiny", vocab_size=128,
                        d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=64, max_seq_len=256)
    return GenerationEngine(
        cfg, num_slots=4, max_len=192, prefill_buckets=(32, 64),
        dtype=jnp.float32, kv_dtype=jnp.float32, seed=0,
        decode_window=4, windows_per_dispatch=1, telemetry=telemetry,
        journal=journal)


def _span_ids(cid: str) -> tuple[str, str, str]:
    """Deterministic (trace_id, submit_span_id, replay_span_id) from a
    correlation id — both sides of a kill/resume pair derive the SAME
    ids, which is what lets the replay span (resume process) parent
    onto the submit span (killed process) across spools."""
    digest = hashlib.sha256(cid.encode()).hexdigest()
    return digest[:32], digest[32:48], digest[48:64]


def storm_prompts(n: int, seed: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(3, 120, size=16 + (i % 7)).tolist()
            for i in range(n)]


def _busy(eng) -> bool:
    return bool(eng._queue or eng._active or eng._done
                or getattr(eng, "_chunking", None)
                or getattr(eng, "_chunk_pending", None))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m copilot_for_consensus_tpu.tools.journal_storm")
    ap.add_argument("--journal", required=True,
                    help="engine journal sqlite path (shared across "
                         "the kill and resume processes)")
    ap.add_argument("--out", required=True,
                    help="completions JSONL (appended; one line per "
                         "retired request)")
    ap.add_argument("--result", required=True,
                    help="end-of-run stats JSON (never written when "
                         "the process is killed — that's the point)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--kill-after-step", type=int, default=0,
                    help="SIGKILL this process after step N (0 = run "
                         "to completion)")
    ap.add_argument("--max-steps", type=int, default=2000)
    ap.add_argument("--spool", default="",
                    help="telemetry spool path (obs/ship.py); ships "
                         "metric deltas + step records + submit/"
                         "replay spans, flushed per step so committed "
                         "rows survive the SIGKILL")
    ap.add_argument("--proc", default="",
                    help="process name stamped on shipped telemetry "
                         "(default: storm-<pid>)")
    args = ap.parse_args(argv)

    from copilot_for_consensus_tpu.engine.journal import EngineJournal

    journal = EngineJournal(args.journal, checkpoint_every=2)
    resume = journal.depth() > 0
    # original-rid → cid, for completions the warm restart emits
    # directly (deadline-expired rows, fully-generated rows)
    old_cids = {e.request_id: e.correlation_id
                for e in journal.unfinished()}
    eng = build_engine(journal, telemetry=bool(args.spool))

    shipper = None
    collector = None
    if args.spool:
        from copilot_for_consensus_tpu.obs.ship import TelemetryShipper
        from copilot_for_consensus_tpu.obs.trace import (
            Span,
            TraceCollector,
        )

        collector = TraceCollector(capacity=4096)
        proc = args.proc or f"storm-{os.getpid()}"
        shipper = TelemetryShipper(
            args.spool, proc=proc,
            role="resume" if resume else "serve",
            metrics=eng.telemetry.metrics,
            collector=collector, recorder=eng.telemetry.recorder)

    def _record_lifecycle_span(cid: str, kind: str) -> None:
        if collector is None:
            return
        trace_id, submit_id, replay_id = _span_ids(cid)
        if kind == "engine_submit":
            span_id, parent = submit_id, ""
        else:  # engine_replay parents onto the ORIGINAL submit span,
            #    which lives in the killed process's spool
            span_id, parent = replay_id, submit_id
        collector.record(Span(
            trace_id=trace_id, span_id=span_id, parent_span_id=parent,
            name="journal_storm", kind=kind, service="journal_storm",
            start_wall=time.time(), correlation_id=cid))

    cid_of: dict[int, str] = dict(old_cids)
    cid_of.update(dict(eng.journal_recovered))
    if not resume:
        for i, p in enumerate(storm_prompts(args.requests, args.seed)):
            rid = eng.submit(p, args.new_tokens,
                             correlation_id=f"js-{i}")
            cid_of[rid] = f"js-{i}"
            _record_lifecycle_span(f"js-{i}", "engine_submit")
    else:
        for _rid, cid in eng.journal_recovered:
            _record_lifecycle_span(cid, "engine_replay")

    out = open(args.out, "a", encoding="utf-8")  # noqa: SIM115
    steps = 0
    completed = 0
    while _busy(eng) and steps < args.max_steps:
        steps += 1
        for c in eng.step():
            out.write(json.dumps({
                "cid": cid_of.get(c.request_id,
                                  f"rid-{c.request_id}"),
                "tokens": list(c.tokens),
                "finish_reason": c.finish_reason}) + "\n")
            completed += 1
        out.flush()
        os.fsync(out.fileno())
        if shipper is not None:
            # synchronous per-step flush BEFORE the kill check: every
            # step that fsynced its completions also committed its
            # telemetry — the recovery gate's invariant
            shipper.flush()
        if args.kill_after_step and steps == args.kill_after_step:
            # a REAL unhandled process death: no atexit, no flushes,
            # no journal close — exactly what the journal must survive
            os.kill(os.getpid(), signal.SIGKILL)
    out.close()
    spool_stats = None
    if shipper is not None:
        spool_stats = shipper.stats()
        shipper.close()
    with open(args.result, "w", encoding="utf-8") as f:
        json.dump({
            "resume": resume,
            "steps": steps,
            "completed": completed,
            "journal_replayed": eng.journal_replayed,
            "journal_abandoned": eng.journal_abandoned,
            "journal_depth": journal.depth(),
            "journal_stats": journal.stats(),
            "spool": spool_stats,
        }, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
