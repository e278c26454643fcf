# SLO-aware scheduler (engine/scheduler.py): DRR fairness properties,
# priority-lane ordering, closed-loop load shedding (shed BEFORE the
# EngineQueueBacklogGrowing alert threshold), the HTTP 429 mapping —
# all host-only and fast — plus slow-lane CPU e2e tests proving the
# chunked-prefill path is bit-identical to the monolithic wave and the
# shed path never trips the engine-failure machinery.
import pathlib
import re
import time

import pytest

from copilot_for_consensus_tpu.engine.scheduler import (
    PRIORITIES,
    EngineOverloaded,
    Scheduler,
    SchedulerConfig,
    jain_index,
    resolve_scheduler,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


class FakeReq:
    def __init__(self, tenant="", priority="interactive", n=64, tag=None):
        self.tenant = tenant
        self.priority = priority
        self.prompt = list(range(n))
        self.tag = tag


def _fill(sched, tenant, lane, count, n=64):
    for _ in range(count):
        sched.enqueue(FakeReq(tenant, lane, n))


# ---------------------------------------------------------------------------
# jain index
# ---------------------------------------------------------------------------


def test_jain_index_bounds():
    assert jain_index([]) == 1.0
    assert jain_index([5, 5, 5, 5]) == pytest.approx(1.0)
    # one tenant takes everything: 1/n
    assert jain_index([100, 0, 0, 0]) == pytest.approx(0.25)
    assert 0.0 < jain_index([10, 1]) < 1.0


# ---------------------------------------------------------------------------
# weighted-DRR fairness properties
# ---------------------------------------------------------------------------


def test_drr_fairness_jain_under_skewed_tenants():
    """The ISSUE-6 property: three tenants, one offering 10x the work
    of the others, equal weights — the admitted-token shares under
    sustained contention must reach Jain >= 0.9 (FIFO would give the
    heavy tenant nearly everything: Jain -> 1/3)."""
    sched = Scheduler(SchedulerConfig(
        quantum_tokens=64, max_queue_depth=10**9,
        batch_shed_depth=10**9))
    _fill(sched, "heavy", "interactive", 200, n=64)
    _fill(sched, "light-1", "interactive", 20, n=64)
    _fill(sched, "light-2", "interactive", 20, n=64)
    # drain in waves while every tenant still has queued work — the
    # contention window fairness is defined over
    while all(sched.queued_for(t) for t in ("heavy", "light-1",
                                            "light-2")):
        got = sched.select(max_requests=8, token_budget=512)
        assert got, "scheduler stopped releasing work under backlog"
    fair = sched.fairness_snapshot()
    assert jain_index(fair.values()) >= 0.9, fair


def test_drr_weights_shape_the_shares():
    """A 3x-weighted tenant gets ~3x the admitted tokens of an equal
    competitor under sustained contention."""
    sched = Scheduler(SchedulerConfig(
        quantum_tokens=64,
        tenant_weights={"gold": 3.0, "bronze": 1.0},
        max_queue_depth=10**9, batch_shed_depth=10**9))
    _fill(sched, "gold", "interactive", 100, n=64)
    _fill(sched, "bronze", "interactive", 100, n=64)
    while sched.queued_for("gold") and sched.queued_for("bronze"):
        assert sched.select(max_requests=8, token_budget=512)
    got_gold = sched._tenants["gold"].admitted_tokens
    got_bronze = sched._tenants["bronze"].admitted_tokens
    assert got_gold / got_bronze == pytest.approx(3.0, rel=0.25)
    # and the WEIGHTED shares are what Jain sees as fair
    assert jain_index(sched.fairness_snapshot().values()) >= 0.9


def test_drr_oversized_request_not_starved():
    """A request bigger than the whole wave budget must eventually be
    released alone, not starve behind the budget forever."""
    sched = Scheduler(SchedulerConfig(quantum_tokens=64))
    sched.enqueue(FakeReq("big", "interactive", n=4096))
    for _ in range(200):
        got = sched.select(max_requests=4, token_budget=256)
        if got:
            assert len(got[0].prompt) == 4096
            return
    pytest.fail("oversized request starved")


def test_priority_lane_preemption_ordering():
    """Interactive requests submitted AFTER a pile of batch work must
    still be released first — strict lane priority."""
    sched = Scheduler(SchedulerConfig(
        quantum_tokens=10**6, max_queue_depth=10**9,
        batch_shed_depth=10**9))
    _fill(sched, "t", "batch", 6, n=32)
    _fill(sched, "t", "interactive", 3, n=32)
    got = sched.select(max_requests=6, token_budget=10**9)
    lanes = [r.priority for r in got]
    assert lanes[:3] == ["interactive"] * 3, lanes
    assert set(lanes[3:]) == {"batch"}


def test_prefix_placement_groups_same_key_into_one_wave():
    """Requests sharing a radix-prefix placement key ride the same
    wave even across tenants (each charged to its own tenant)."""
    sched = Scheduler(SchedulerConfig(
        quantum_tokens=10**6, max_queue_depth=10**9,
        batch_shed_depth=10**9))
    sched.enqueue(FakeReq("a", "interactive", 32, tag="tmpl-X"))
    sched.enqueue(FakeReq("a", "interactive", 32, tag="other"))
    sched.enqueue(FakeReq("b", "interactive", 32, tag="tmpl-X"))
    sched.enqueue(FakeReq("b", "interactive", 32, tag="tmpl-X"))
    got = sched.select(max_requests=3, token_budget=10**9,
                       placement_key=lambda r: r.tag)
    assert [r.tag for r in got] == ["tmpl-X"] * 3


# ---------------------------------------------------------------------------
# load shedding: closed loop + thresholds
# ---------------------------------------------------------------------------


def _backlog_alert_threshold() -> int:
    """Read the EngineQueueBacklogGrowing depth out of the alert pack —
    the shed-before-alert contract is against the REAL rule, not a
    hard-coded copy that could drift."""
    text = (REPO / "infra" / "prometheus" / "alerts" /
            "serving.yml").read_text()
    m = re.search(r"copilot_engine_queue_depth\s*>\s*(\d+)", text)
    assert m, "EngineQueueBacklogGrowing expr not found"
    return int(m.group(1))


def test_default_shed_thresholds_sit_below_backlog_alert():
    cfg = SchedulerConfig()
    alert_depth = _backlog_alert_threshold()
    assert cfg.max_queue_depth < alert_depth
    assert cfg.batch_shed_depth < cfg.max_queue_depth


def test_shed_fires_before_backlog_alert_depth():
    """Submit storm: every request is admission-checked then enqueued;
    the hard-cap shed must kick in strictly below the alert depth, so
    EngineLoadShedding (429s) fires before EngineQueueBacklogGrowing
    ever can."""
    sched = Scheduler(SchedulerConfig())
    alert_depth = _backlog_alert_threshold()
    shed = 0
    for i in range(3 * alert_depth):
        try:
            sched.check_admission(tenant="storm",
                                  priority="interactive",
                                  prompt_tokens=64)
            sched.enqueue(FakeReq("storm", "interactive", 64))
        except EngineOverloaded as exc:
            shed += 1
            assert exc.retry_after_s >= 1.0
            assert exc.reason == "queue-full"
    assert shed > 0
    assert sched.queued < alert_depth


def test_batch_sheds_before_interactive():
    sched = Scheduler(SchedulerConfig(batch_shed_depth=8,
                                      max_queue_depth=16))
    for _ in range(8):
        sched.check_admission(tenant="t", priority="batch",
                              prompt_tokens=8)
        sched.enqueue(FakeReq("t", "batch", 8))
    # batch lane now sheds...
    with pytest.raises(EngineOverloaded) as ei:
        sched.check_admission(tenant="t", priority="batch",
                              prompt_tokens=8)
    assert ei.value.reason == "slo-pressure"
    assert ei.value.priority == "batch"
    # ...but interactive still admits until the hard cap
    sched.check_admission(tenant="t", priority="interactive",
                          prompt_tokens=8)


def test_tenant_quota_sheds_only_the_offender():
    sched = Scheduler(SchedulerConfig(
        tenant_quota_tokens={"greedy": 100}))
    sched.check_admission(tenant="greedy", priority="interactive",
                          prompt_tokens=80)
    sched.enqueue(FakeReq("greedy", "interactive", 80))
    with pytest.raises(EngineOverloaded) as ei:
        sched.check_admission(tenant="greedy", priority="interactive",
                              prompt_tokens=80)
    assert ei.value.reason == "tenant-quota"
    # other tenants unaffected
    sched.check_admission(tenant="polite", priority="interactive",
                          prompt_tokens=80)


def test_closed_loop_slo_violation_sheds_batch_lane():
    """Synthetic telemetry spans violating the queue-wait SLO while
    the slots are saturated flip the loop to level 1: batch sheds,
    interactive still admits."""

    class Trace:
        def __init__(self, qw, ttft, fin):
            self.queue_wait_s = qw
            self.ttft_s = ttft
            self.finished_at = fin

    class Tele:
        completed = [Trace(30.0, 31.0, time.monotonic())
                     for _ in range(16)]

    sched = Scheduler(SchedulerConfig(queue_wait_p95_slo_s=20.0,
                                      ttft_p99_slo_s=30.0))
    sig = sched.observe(queued=2, active=8, num_slots=8,
                        telemetry=Tele())
    assert sig["overload_level"] == 1
    with pytest.raises(EngineOverloaded):
        sched.check_admission(tenant="t", priority="batch",
                              prompt_tokens=8)
    sched.check_admission(tenant="t", priority="interactive",
                          prompt_tokens=8)
    # idle slots are not overload: same latencies, no shed
    sched2 = Scheduler(SchedulerConfig(queue_wait_p95_slo_s=20.0))
    sig2 = sched2.observe(queued=2, active=1, num_slots=8,
                          telemetry=Tele())
    assert sig2["overload_level"] == 0


def test_retry_after_tracks_drain_rate_and_clamps():
    class Trace:
        def __init__(self, fin):
            self.queue_wait_s = 0.1
            self.ttft_s = 0.2
            self.finished_at = fin

    class Tele:
        # 16 completions over the last ~4s -> ~4 req/s
        completed = [Trace(time.monotonic() - 4.0 + 0.25 * i)
                     for i in range(16)]

    sched = Scheduler(SchedulerConfig(min_retry_after_s=1.0,
                                      max_retry_after_s=60.0))
    sig = sched.observe(queued=16, active=4, num_slots=4,
                        telemetry=Tele())
    # 16 queued at ~4/s -> ~4s, within clamps
    assert 1.0 <= sig["retry_after_s"] <= 60.0
    assert sig["retry_after_s"] == pytest.approx(4.0, rel=0.5)
    # zero rate, deep queue: clamped to the max, never infinity
    sched2 = Scheduler(SchedulerConfig(max_retry_after_s=60.0))
    sig2 = sched2.observe(queued=1000, active=0, num_slots=4)
    assert sig2["retry_after_s"] == 60.0


# ---------------------------------------------------------------------------
# structured rejection -> HTTP 429 + Retry-After
# ---------------------------------------------------------------------------


def test_engine_overloaded_event_fields():
    exc = EngineOverloaded("nope", retry_after_s=7.25, tenant="t",
                           priority="batch", reason="queue-full",
                           correlation_id="corr-9")
    f = exc.as_event_fields()
    assert f["retry_after_s"] == 7.25
    assert f["tenant"] == "t"
    assert f["correlation_id"] == "corr-9"
    assert f["reason"] == "queue-full"


def test_router_maps_engine_overloaded_to_429_with_retry_after():
    from copilot_for_consensus_tpu.services.http import Router

    router = Router()

    @router.post("/api/generate")
    def gen(req):
        raise EngineOverloaded(
            "engine overloaded", retry_after_s=12.4, tenant="chat",
            priority="interactive", correlation_id="corr-42")

    resp = router.dispatch("POST", "/api/generate", {}, b"{}")
    assert resp.status == 429
    assert resp.headers["Retry-After"] == "13"      # ceil(12.4)
    import json

    body = json.loads(resp.raw)
    assert body["correlation_id"] == "corr-42"
    assert body["retry_after_s"] == 12.4
    assert body["tenant"] == "chat"


# ---------------------------------------------------------------------------
# telemetry export + resolve semantics
# ---------------------------------------------------------------------------


def test_scheduler_metrics_export():
    from copilot_for_consensus_tpu.engine.telemetry import EngineTelemetry
    from copilot_for_consensus_tpu.obs.metrics import InMemoryMetrics

    m = InMemoryMetrics(namespace="copilot")
    tele = EngineTelemetry(engine="generation", num_slots=4, metrics=m)
    sched = Scheduler(SchedulerConfig(max_queue_depth=4),
                      telemetry=tele)
    sched.enqueue(FakeReq("a", "interactive", 8))
    for _ in range(8):
        try:
            sched.check_admission(tenant="a", priority="interactive",
                                  prompt_tokens=8)
            sched.enqueue(FakeReq("a", "interactive", 8))
        except EngineOverloaded:
            pass
    body = m.render_prometheus()
    assert "copilot_engine_sched_tenant_queue_depth" in body
    assert "copilot_engine_sched_shed_total" in body
    assert 'tenant="a"' in body


def test_resolve_scheduler_semantics():
    assert resolve_scheduler(None) is None
    assert resolve_scheduler(False) is None
    s = resolve_scheduler(True)
    assert isinstance(s, Scheduler)
    cfg = SchedulerConfig(chunk_tokens=99)
    s2 = resolve_scheduler(cfg)
    assert s2.cfg.chunk_tokens == 99
    assert resolve_scheduler(s2) is s2      # shared instance
    with pytest.raises(ValueError):
        resolve_scheduler("nope")
    with pytest.raises(ValueError):
        Scheduler().check_admission(priority="urgent")


def test_embed_admit_sizes_and_sheds():
    sched = Scheduler(SchedulerConfig(embed_wave_rows=16,
                                      embed_max_burst_texts=100))
    assert sched.embed_admit(50, batch_size=64) == 16
    with pytest.raises(EngineOverloaded) as ei:
        sched.embed_admit(500, batch_size=64)
    assert ei.value.reason == "embed-burst"
    # under overload the tile halves
    sched.overload_level = 1
    assert sched.embed_admit(50, batch_size=64) == 8


def test_priorities_constant():
    assert PRIORITIES == ("interactive", "batch")


# ---------------------------------------------------------------------------
# CPU e2e (slow lane): chunked prefill bit-identity, engine-level
# shedding, async-runner containment
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine_parts():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.models import decoder
    from copilot_for_consensus_tpu.models.configs import decoder_config

    cfg = decoder_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(7), cfg,
                                 dtype=jnp.float32)
    return cfg, params


def _engine(tiny_engine_parts, **kw):
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine,
    )

    cfg, params = tiny_engine_parts
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("prefill_buckets", (16, 32, 96))
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("decode_window", 4)
    return GenerationEngine(cfg, params, **kw)


@pytest.mark.slow
def test_chunked_prefill_bit_identical_to_monolithic(tiny_engine_parts):
    """The tentpole exactness gate: greedy completions with chunked
    prefill ON (scheduler, chunk_tokens far below the prompt lengths)
    must be token-identical to the monolithic-wave FIFO engine —
    chunked prefill is a scheduling change, not a numerics change."""
    import numpy as np

    cfg, _ = tiny_engine_parts
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, cfg.vocab_size, size=n).tolist()
               for n in (60, 25, 80, 10, 33, 71)]
    want = _engine(tiny_engine_parts).generate(prompts,
                                               max_new_tokens=6)
    eng = _engine(tiny_engine_parts,
                  scheduler=SchedulerConfig(chunk_tokens=16,
                                            prefill_wave_tokens=64))
    got = eng.generate(prompts, max_new_tokens=6)
    for w, g in zip(want, got):
        assert g.tokens == w.tokens
        assert g.prompt_len == w.prompt_len
    # the long prompts actually took the chunked path
    assert eng.chunk_dispatches > 0
    assert eng.chunk_prefill_tokens > 0


@pytest.mark.slow
def test_chunked_prefill_interleaves_with_decode(tiny_engine_parts):
    """A long prompt joining mid-decode must not perturb the stream
    already decoding (chunk dispatches park active rows OOB), and its
    own output must match the solo run."""
    import numpy as np

    cfg, _ = tiny_engine_parts
    rng = np.random.default_rng(5)
    short = rng.integers(3, cfg.vocab_size, size=12).tolist()
    long_p = rng.integers(3, cfg.vocab_size, size=90).tolist()
    solo = _engine(tiny_engine_parts).generate(
        [short, long_p], max_new_tokens=10)
    eng = _engine(tiny_engine_parts,
                  scheduler=SchedulerConfig(chunk_tokens=16))
    done = {}
    rid1 = eng.submit(short, 10)
    for _ in range(2):
        for c in eng.step():
            done[c.request_id] = c
    rid2 = eng.submit(long_p, 10, tenant="late", priority="batch")
    for _ in range(100):
        for c in eng.step():
            done[c.request_id] = c
        if len(done) == 2:
            break
    assert done[rid1].tokens == solo[0].tokens
    assert done[rid2].tokens == solo[1].tokens


@pytest.mark.slow
def test_engine_submit_sheds_with_structured_rejection(
        tiny_engine_parts):
    """Engine-level closed loop: a submit storm against a tiny queue
    cap sheds with EngineOverloaded at the door, queue depth never
    reaches the cap x2, and the admitted requests all complete."""
    import numpy as np

    cfg, _ = tiny_engine_parts
    rng = np.random.default_rng(7)
    eng = _engine(tiny_engine_parts,
                  scheduler=SchedulerConfig(max_queue_depth=6,
                                            batch_shed_depth=4))
    admitted, shed = [], 0
    for i in range(24):
        p = rng.integers(3, cfg.vocab_size, size=10).tolist()
        try:
            admitted.append(eng.submit(p, 3, tenant=f"t{i % 2}"))
        except EngineOverloaded as exc:
            shed += 1
            assert exc.retry_after_s >= 1.0
        assert eng.queue_depth <= 12
    assert shed > 0 and admitted
    done = {}
    for _ in range(200):
        for c in eng.step():
            done[c.request_id] = c
        if len(done) == len(admitted):
            break
    assert set(done) == set(admitted)
    stats = eng.sched_stats()
    assert stats["shed"] == shed
    assert 0.0 < stats["shed_rate"] < 1.0


@pytest.mark.slow
def test_async_runner_propagates_shed_without_error_reports(
        tiny_engine_parts):
    """ISSUE-6 satellite: a shed is an ADMISSION outcome — the async
    runner must surface it to the caller synchronously and must NOT
    treat it as an engine failure (no error_reporter report, no
    flight-recorder error dump)."""
    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )
    from copilot_for_consensus_tpu.obs.errors import (
        CollectingErrorReporter,
    )

    eng = _engine(tiny_engine_parts,
                  scheduler=SchedulerConfig(max_queue_depth=2,
                                            batch_shed_depth=1))
    rep = CollectingErrorReporter()
    runner = AsyncEngineRunner(eng, error_reporter=rep).start()
    try:
        handles, shed = [], 0
        # long generations keep all 4 slots busy, so the burst piles
        # up and trips the 2-deep cap. A shed can surface either
        # synchronously (runner.submit precheck, once the scheduler
        # queue is visibly deep) or on the HANDLE (the dispatcher-side
        # engine.submit shed fails that handle, not the dispatcher) —
        # both are admission outcomes, neither is an engine failure.
        for i in range(16):
            try:
                handles.append(runner.submit([5, 6, 7, 8], 48))
            except EngineOverloaded:
                shed += 1
        ok = 0
        for h in handles:
            try:
                assert h.result(timeout=120.0).tokens
                ok += 1
            except EngineOverloaded as exc:
                shed += 1
                assert exc.retry_after_s >= 1.0
        assert shed > 0, "burst never shed"
        assert ok > 0, "nothing completed"
    finally:
        runner.stop()
    assert rep.reports == []
    assert eng.telemetry.errors == 0


@pytest.mark.parametrize("batch", [1, 3, 7])
def test_a_batch_handed_over_whole_is_seen_whole(tiny_engine_parts, batch):
    """``submit_many`` is ONE hand-over: the dispatcher's first step
    after it finds every request of the batch in the engine (handed
    over one by one it may find one, some or all), and each completes
    as it does alone."""
    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )

    prompts = [[5 + i, 6, 7 + i, 8] for i in range(batch)]
    alone = [c.tokens for c in _engine(tiny_engine_parts).generate(
        prompts, max_new_tokens=5)]
    eng = _engine(tiny_engine_parts)
    seen, submit, step = [], eng.submit, eng.step
    count = [0]

    def counting_submit(*a, **kw):
        count[0] += 1
        return submit(*a, **kw)

    def recording_step(*a, **kw):
        seen.append(count[0])
        return step(*a, **kw)

    eng.submit, eng.step = counting_submit, recording_step
    runner = AsyncEngineRunner(eng).start()
    try:
        handles = runner.submit_many(
            [(p, 5, {"correlation_id": f"b{i}"})
             for i, p in enumerate(prompts)])
        assert [h.correlation_id for h in handles] \
            == [f"b{i}" for i in range(batch)]
        assert [h.result(timeout=120.0).tokens for h in handles] == alone
    finally:
        runner.stop()
    assert seen[0] == batch


def test_a_batch_is_refused_whole_after_stop(tiny_engine_parts):
    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )

    runner = AsyncEngineRunner(_engine(tiny_engine_parts))
    with pytest.raises(RuntimeError, match="not started"):
        runner.submit_many([([1, 2, 3], 4, {})])
    runner.start()
    runner.stop()
    with pytest.raises(RuntimeError, match="stopped|not started"):
        runner.submit_many([([1, 2, 3], 4, {}), ([4, 5], 4, {})])
    assert runner._pending == []
