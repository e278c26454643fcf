# Durable engine request journal (engine/journal.py) + warm restart —
# the process-level "restart costs latency, not work" contract
# (ISSUE 12; docs/RESILIENCE.md#process-lifecycle).
#
# Layout (the chaos-suite convention, test_engine_chaos.py): journal
# units and stub-engine runner-integration tests are unmarked (tier-1
# fast lane); the tiny REAL-engine warm-restart gates are unmarked too
# (they share one tiny f32 CPU engine config); the real-PROCESS variant
# — an actual SIGKILL of a child interpreter mid-storm via
# tools/journal_storm.py — is @slow.
import json
import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from copilot_for_consensus_tpu.engine.journal import (
    EngineJournal,
    resolve_journal,
)


# ---------------------------------------------------------------------------
# journal units (no jax)
# ---------------------------------------------------------------------------


def test_journal_submit_retire_roundtrip(tmp_path):
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    j.record_submit(1, [5, 6, 7], 16, correlation_id="c-1",
                    tenant="t", priority="batch", deadline_wall=123.0,
                    trace_id="tr", span_id="sp")
    j.record_submit(2, [9], 8)
    assert j.depth() == 2
    rows = j.unfinished()
    assert [r.request_id for r in rows] == [1, 2]
    assert rows[0].prompt == [5, 6, 7]
    assert rows[0].max_new_tokens == 16
    assert rows[0].correlation_id == "c-1"
    assert rows[0].tenant == "t" and rows[0].priority == "batch"
    assert rows[0].deadline_wall == 123.0
    assert rows[0].trace_id == "tr" and rows[0].span_id == "sp"
    assert rows[0].tokens == [] and rows[0].attempt == 0
    j.record_retire(1)
    assert j.depth() == 1
    assert [r.request_id for r in j.unfinished()] == [2]
    j.record_abandon(2)
    assert j.depth() == 0
    s = j.stats()
    assert s["journaled"] == 2 and s["retired"] == 1 \
        and s["abandoned"] == 1
    # deleting a missing row is a no-op, not drift
    j.record_retire(99)
    assert j.depth() == 0 and j.stats()["retired"] == 1


def test_journal_checkpoint_and_supersede_preserve_identity(tmp_path):
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    j.record_submit(3, [1, 2, 3], 10, correlation_id="c-3")
    j.checkpoint(3, [50, 51])
    assert j.unfinished()[0].tokens == [50, 51]
    # crash #1: the continuation resubmits as rid 7 — the re-key is
    # ONE atomic UPDATE (the continuation's own record_submit is
    # suppressed), so at no instant does the journal hold two live
    # rows for one request (a crash around the resubmission replays
    # exactly one of original/continuation, never both)
    j.supersede(3, 7, [50, 51])
    assert j.depth() == 1
    row = j.unfinished()[0]
    assert row.request_id == 7
    assert row.prompt == [1, 2, 3]          # original, not flattened
    assert row.max_new_tokens == 10          # original budget
    assert row.tokens == [50, 51]
    assert row.attempt == 1
    assert row.correlation_id == "c-3"
    # continuation checkpoints are RELATIVE to the continuation; the
    # durable column stays relative to the original prompt
    j.checkpoint(7, [52])
    assert j.unfinished()[0].tokens == [50, 51, 52]
    # crash #2: the chain holds
    j.supersede(7, 9, [50, 51, 52])
    row = j.unfinished()[0]
    assert row.prompt == [1, 2, 3] and row.attempt == 2
    assert row.tokens == [50, 51, 52]
    # superseding a missing rid is a no-op, not drift
    j.supersede(99, 100, [1])
    assert j.depth() == 1
    j.record_retire(9)
    assert j.depth() == 0


def test_journal_survives_reopen(tmp_path):
    path = str(tmp_path / "durable.sqlite3")
    j = EngineJournal(path)
    j.record_submit(1, [4, 5], 6, correlation_id="x")
    j.checkpoint(1, [9])
    j.close()   # the SIGKILL case never even gets this
    j2 = EngineJournal(path)
    assert j2.depth() == 1
    row = j2.unfinished()[0]
    assert row.prompt == [4, 5] and row.tokens == [9]
    assert row.correlation_id == "x"


def test_journal_checkpoint_missing_row_is_noop(tmp_path):
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    j.checkpoint(42, [1, 2])
    assert j.depth() == 0 and j.stats()["checkpoints"] == 0


def test_resolve_journal_semantics(tmp_path):
    assert resolve_journal(None) is None
    assert resolve_journal(False) is None
    j = resolve_journal(str(tmp_path / "a.sqlite3"))
    assert isinstance(j, EngineJournal)
    assert resolve_journal(j) is j
    jd = resolve_journal({"path": str(tmp_path / "b.sqlite3"),
                          "checkpoint_every": 3})
    assert jd.checkpoint_every == 3
    with pytest.raises(ValueError, match="journal"):
        resolve_journal(123)


# ---------------------------------------------------------------------------
# runner integration (stub engine, no jax)
# ---------------------------------------------------------------------------


class _StubJournalEngine:
    """Minimal engine surface the runner needs, with a real journal:
    submit journals, step() either parks work forever ('park'),
    completes everything ('complete'), or raises ('fail')."""

    prompt_limit = 4096

    def __init__(self, journal, mode="park"):
        self.journal = journal
        self.mode = mode
        self.telemetry = None
        self._queue = []
        self._active = {}
        self._generated = {}
        self._done = {}
        self._next = 0

    def submit(self, prompt, max_new_tokens, **kw):
        rid = self._next
        self._next += 1
        self.journal.record_submit(
            rid, prompt, max_new_tokens,
            correlation_id=kw.get("correlation_id", ""))
        self._queue.append(SimpleNamespace(
            request_id=rid, prompt=list(prompt),
            max_new_tokens=max_new_tokens, cache_eligible_tokens=None,
            correlation_id=kw.get("correlation_id", ""), tenant="",
            priority="", deadline_at=float("inf")))
        return rid

    def step(self):
        from copilot_for_consensus_tpu.engine.generation import (
            Completion,
        )

        if self.mode == "fail":
            raise RuntimeError("stub step failure")
        if self.mode == "park":
            return []
        comps = []
        for req in self._queue:
            comps.append(Completion(
                request_id=req.request_id,
                prompt_len=len(req.prompt), tokens=[1, 2],
                finish_reason="length"))
            self.journal.record_retire(req.request_id)
        self._queue = []
        return comps


def _runner(eng, **kw):
    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )

    return AsyncEngineRunner(eng, **kw).start()


def test_runner_stop_keeps_journal_rows(tmp_path):
    """A stop (graceful or not) is the crash-only clean case: handles
    fail 'runner stopped', but the rows SURVIVE for the next process's
    warm restart — stop must not turn restart-costs-latency back into
    restart-costs-work."""
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    eng = _StubJournalEngine(j, mode="park")
    r = _runner(eng)
    h1 = r.submit([1, 2, 3], 8, correlation_id="keep-1")
    h2 = r.submit([4, 5], 8, correlation_id="keep-2")
    deadline = time.monotonic() + 5
    while not eng._queue and time.monotonic() < deadline:
        time.sleep(0.01)
    assert r.stop() is True
    for h in (h1, h2):
        with pytest.raises(RuntimeError, match="runner stopped"):
            h.result(timeout=5)
    assert j.depth() == 2
    assert {e.correlation_id for e in j.unfinished()} == {
        "keep-1", "keep-2"}


def test_runner_legacy_failure_abandons_rows(tmp_path):
    """Without a supervisor, an engine failure fails every handle —
    the callers were TOLD, so the rows must not replay at the next
    restart (that would duplicate work the caller already retried via
    the bus)."""
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    eng = _StubJournalEngine(j, mode="fail")
    r = _runner(eng)
    h = r.submit([1, 2, 3], 8, correlation_id="gone")
    with pytest.raises(RuntimeError, match="stub step failure"):
        h.result(timeout=5)
    deadline = time.monotonic() + 5
    while j.depth() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert j.depth() == 0
    assert j.stats()["abandoned"] == 1
    r.stop()


def test_runner_drain_completes_then_reports(tmp_path):
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    eng = _StubJournalEngine(j, mode="complete")
    r = _runner(eng)
    h = r.submit([1, 2], 4)
    assert r.drain(timeout=5) is True
    assert h.result(timeout=1).tokens == [1, 2]
    assert j.depth() == 0
    assert r.stop() is True


def test_runner_drain_times_out_on_parked_work(tmp_path):
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    eng = _StubJournalEngine(j, mode="park")
    r = _runner(eng)
    r.submit([1, 2], 4)
    t0 = time.monotonic()
    assert r.drain(timeout=0.3) is False
    assert time.monotonic() - t0 < 3.0
    r.stop()
    assert j.depth() == 1    # evacuate-and-journal: the row survives


def test_runner_drain_unblocks_on_stop(tmp_path):
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    eng = _StubJournalEngine(j, mode="park")
    r = _runner(eng)
    r.submit([1], 4)
    out = {}

    def drainer():
        out["drained"] = r.drain(timeout=30.0)

    t = threading.Thread(target=drainer)
    t.start()
    time.sleep(0.1)
    r.stop()
    t.join(timeout=5)
    assert not t.is_alive()
    assert out["drained"] is False


# ---------------------------------------------------------------------------
# real tiny engine (f32 CPU — the chaos-gate fixture discipline)
# ---------------------------------------------------------------------------


def _tiny_engine(journal=None, **kw):
    import jax
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine,
    )
    from copilot_for_consensus_tpu.models import decoder
    from copilot_for_consensus_tpu.models.configs import decoder_config

    cfg = decoder_config("tiny")
    params = _tiny_engine._params
    if params is None:
        params = decoder.init_params(jax.random.PRNGKey(7), cfg,
                                     dtype=jnp.float32)
        _tiny_engine._params = params
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 96)
    kw.setdefault("prefill_buckets", (48,))
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("kv_dtype", jnp.float32)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("decode_window", 4)
    kw.setdefault("telemetry", False)
    return GenerationEngine(cfg, params, journal=journal, **kw)


_tiny_engine._params = None

_PROMPTS = [
    [5, 9, 13, 6, 11, 4, 9, 2],
    [7, 8, 9, 10, 11, 12],
    [3, 4, 5, 6, 7, 8, 9, 10, 11],
    [40, 41, 42, 43, 44],
    [11, 12, 13, 14, 15, 16, 17],
    [21, 22, 23, 24],
]


def test_engine_journals_before_queue_and_retires_at_harvest(tmp_path):
    j = EngineJournal(str(tmp_path / "j.sqlite3"), checkpoint_every=2)
    eng = _tiny_engine(journal=j)
    rid = eng.submit(list(_PROMPTS[0]), 6, correlation_id="e-0")
    assert j.depth() == 1
    row = j.unfinished()[0]
    assert row.request_id == rid and row.correlation_id == "e-0"
    comps = []
    steps = 0
    while not comps and steps < 50:
        steps += 1
        comps = eng.step()
    assert comps and comps[0].request_id == rid
    assert j.depth() == 0 and j.stats()["retired"] == 1


def test_warm_restart_is_bit_identical_and_drains_journal(tmp_path):
    """The fast-lane kill gate: run a storm, 'kill' the process by
    dropping the engine mid-storm (the sqlite file IS the surviving
    state — the @slow variant does it with a real SIGKILL), rebuild on
    the same journal, and require lost 0 / duplicated 0 /
    journal_replayed > 0 / final depth 0 / greedy outputs bit-identical
    (f32) to an uninterrupted run."""
    ref = _tiny_engine()
    ref_out = {c.request_id: c.tokens
               for c in ref.generate([list(p) for p in _PROMPTS], 16)}

    path = str(tmp_path / "j.sqlite3")
    eng = _tiny_engine(journal=EngineJournal(path, checkpoint_every=2))
    rids = [eng.submit(list(p), 16, correlation_id=f"w-{i}")
            for i, p in enumerate(_PROMPTS)]
    got: dict[str, list] = {}
    dup = 0
    for _ in range(4):   # partial progress: checkpoints exist, nothing
        for c in eng.step():   # near the full set has retired
            cid = f"w-{rids.index(c.request_id)}"
            dup += cid in got
            got[cid] = c.tokens
    interrupted_depth = eng.journal.depth()
    assert interrupted_depth > 0, "storm finished before the kill"
    del eng   # process death: no close, no flush

    j2 = EngineJournal(path, checkpoint_every=2)
    eng2 = _tiny_engine(journal=j2)
    assert eng2.journal_replayed == interrupted_depth > 0
    rec = dict(eng2.journal_recovered)
    steps = 0
    while (eng2._active or eng2._queue or eng2._done) and steps < 400:
        steps += 1
        for c in eng2.step():
            cid = rec[c.request_id]
            dup += cid in got
            got[cid] = c.tokens
    assert dup == 0
    assert len(got) == len(_PROMPTS)        # lost 0
    for i, rid in enumerate(rids):
        assert got[f"w-{i}"] == ref_out[i], f"diverged: w-{i}"
    assert j2.depth() == 0                  # final depth 0


def test_warm_restart_expired_deadline_is_honest_drop(tmp_path):
    path = str(tmp_path / "j.sqlite3")
    j = EngineJournal(path)
    # a journaled request whose wall-clock deadline passed during the
    # outage: recovery must DROP it (finish_reason deadline), never
    # compute it
    j.record_submit(0, [5, 6, 7], 8, correlation_id="late",
                    deadline_wall=time.time() - 5.0)
    j.close()
    eng = _tiny_engine(journal=EngineJournal(path))
    assert eng.journal_replayed == 0
    comps = eng.step()
    assert [c.finish_reason for c in comps] == ["deadline"]
    assert eng.journal.depth() == 0


def test_warm_restart_abandons_overlong_continuation(tmp_path):
    path = str(tmp_path / "j.sqlite3")
    j = EngineJournal(path)
    # prompt+checkpointed tokens beyond prompt_limit (48 on the tiny
    # engine): resuming would head-truncate and diverge — abandon,
    # honestly counted
    j.record_submit(0, list(range(3, 43)), 64, correlation_id="big")
    j.checkpoint(0, list(range(3, 23)))
    j.close()
    eng = _tiny_engine(journal=EngineJournal(path))
    assert eng.journal_replayed == 0
    assert eng.journal_abandoned == 1
    assert eng.journal.depth() == 0
    assert eng.journal_stats()["abandoned"] == 1


def test_warm_restart_already_complete_row_emits_without_compute(
        tmp_path):
    path = str(tmp_path / "j.sqlite3")
    j = EngineJournal(path)
    j.record_submit(0, [5, 6, 7], 4, correlation_id="done")
    j.checkpoint(0, [50, 51, 52, 53])     # full budget checkpointed
    j.close()
    eng = _tiny_engine(journal=EngineJournal(path))
    assert eng.journal_replayed == 0
    comps = eng.step()
    assert len(comps) == 1
    assert comps[0].tokens == [50, 51, 52, 53]
    assert comps[0].finish_reason == "length"
    assert eng.journal.depth() == 0


def test_journal_stats_surface(tmp_path):
    eng = _tiny_engine()
    assert eng.journal_stats() == {
        "enabled": False, "replayed": 0, "abandoned": 0}
    j = EngineJournal(str(tmp_path / "j.sqlite3"))
    eng2 = _tiny_engine(journal=j)
    s = eng2.journal_stats()
    assert s["enabled"] is True and s["depth"] == 0


# ---------------------------------------------------------------------------
# real-process SIGKILL (@slow): the bench kill phase as a test
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_real_process_sigkill_and_warm_restart(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def child(journal, out, result, kill_after=0):
        cmd = [sys.executable, "-m",
               "copilot_for_consensus_tpu.tools.journal_storm",
               "--journal", str(journal), "--out", str(out),
               "--result", str(result), "--requests", "10",
               "--new-tokens", "20", "--seed", "5"]
        if kill_after:
            cmd += ["--kill-after-step", str(kill_after)]
        return subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=300)

    r = child(tmp_path / "ref.sqlite3", tmp_path / "ref.jsonl",
              tmp_path / "ref.json")
    assert r.returncode == 0, r.stderr[-2000:]

    r = child(tmp_path / "kill.sqlite3", tmp_path / "kill.jsonl",
              tmp_path / "kill.json", kill_after=6)
    assert r.returncode in (-signal.SIGKILL, 137), (
        "child was not SIGKILLed", r.returncode, r.stderr[-500:])

    r = child(tmp_path / "kill.sqlite3", tmp_path / "kill.jsonl",
              tmp_path / "resume.json")
    assert r.returncode == 0, r.stderr[-2000:]
    resume = json.loads((tmp_path / "resume.json").read_text())
    assert resume["resume"] is True
    assert resume["journal_replayed"] > 0
    assert resume["journal_depth"] == 0

    def lines(p):
        out, dup = {}, 0
        for line in p.read_text().splitlines():
            d = json.loads(line)
            dup += d["cid"] in out
            out[d["cid"]] = d["tokens"]
        return out, dup

    ref, _ = lines(tmp_path / "ref.jsonl")
    got, dup = lines(tmp_path / "kill.jsonl")
    assert dup == 0
    assert set(got) == set(ref)                       # lost 0
    assert all(got[c] == ref[c] for c in ref)         # bit-identical
