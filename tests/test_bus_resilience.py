"""Pipeline fault plane (ISSUE 8): publish-outbox ride-through,
depth-watermark backpressure, poison quarantine, durable-broker crash
recovery, and the seeded pipeline storm.

Fast lane: stub-broker units (no zmq, no subprocess) for the outbox /
backpressure / quarantine / classification machinery. @slow: the
real-broker regressions (restart ride-through, kill-and-recover,
backpressure e2e) and the multi-phase storm the bench preset
(``BENCH_PRESET=pipeline_chaos``) scales up.
"""

from __future__ import annotations

import threading
import time

import pytest

from copilot_for_consensus_tpu.bus import broker as broker_mod
from copilot_for_consensus_tpu.bus.base import (
    BusSaturated,
    PoisonEnvelope,
    PublishError,
)
from copilot_for_consensus_tpu.bus.faults import (
    FaultBoundary,
    FaultPlan,
    FaultSpec,
    FaultingArchiveStore,
    FaultingDocumentStore,
    PipelineFaultError,
    TransientPipelineFault,
    resolve_boundary,
)
from copilot_for_consensus_tpu.bus.inproc import (
    InProcBroker,
    InProcPublisher,
    InProcSubscriber,
)
from copilot_for_consensus_tpu.bus.validating import ValidatingSubscriber
from copilot_for_consensus_tpu.core.events import ArchiveIngested
from copilot_for_consensus_tpu.core.retry import RetryableError
from copilot_for_consensus_tpu.obs.metrics import InMemoryMetrics


# -- stub broker client ---------------------------------------------------


class StubClient:
    """Scriptable ``_Client`` stand-in: records every request; raises
    ``PublishError`` while ``down``; replies confirms with a scripted
    per-key depth."""

    def __init__(self):
        self.down = False
        self.requests: list[dict] = []
        self.depths: dict[str, int] = {}
        self.lock = threading.Lock()

    def request(self, req: dict) -> dict:
        with self.lock:
            if self.down:
                raise PublishError("stub broker unreachable")
            self.requests.append(dict(req))
            if req["op"] == "pub":
                return {"ok": True, "id": len(self.requests),
                        "depth": self.depths.get(req["rk"], 0)}
            if req["op"] == "depth":
                return {"ok": True,
                        "depth": self.depths.get(req["rk"], 0)}
            if req["op"] == "counts":
                return {"ok": True, "counts": {
                    rk: {"pending": d} for rk, d in self.depths.items()}}
            return {"ok": True}

    def published(self) -> list[tuple[str, dict]]:
        with self.lock:
            return [(r["rk"], r["envelope"]) for r in self.requests
                    if r["op"] == "pub"]

    def close(self):
        pass


def make_publisher(stub, **cfg):
    pub = broker_mod.BrokerPublisher(
        {"address": "tcp://stub", **cfg}, client=stub)
    pub._depth_client = stub     # pacing polls ride the stub too
    return pub


def await_cond(fn, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(0.01)
    return fn()


# -- publish outbox: broker-outage ride-through ---------------------------


def test_outbox_parks_during_outage_and_replays_in_order():
    stub = StubClient()
    pub = make_publisher(stub)
    pub.publish_envelope({"event_type": "e", "n": 0}, routing_key="k")
    stub.down = True
    for n in (1, 2, 3):
        pub.publish_envelope({"event_type": "e", "n": n},
                             routing_key="k")   # parks, no raise
    assert pub.outbox.depth() == 3
    stats = pub.outbox_stats()
    assert stats["confirmed"] == 1 and stats["parked"] == 3
    stub.down = False
    assert await_cond(lambda: pub.outbox.depth() == 0)
    # replay order == publish order (rows leave only after confirm)
    assert [env["n"] for _rk, env in stub.published()] == [0, 1, 2, 3]
    assert pub.outbox_stats()["replayed"] == 3
    pub.close()


def test_publishes_during_replay_park_behind_the_backlog():
    """While anything is parked, new publishes queue BEHIND it — a
    half-replayed outbox must not let fresh traffic overtake parked
    work and scramble per-publisher order."""
    import json

    stub = StubClient()
    pub = make_publisher(stub)
    # A parked row with no replayer running: the state right after an
    # outage began (or a publisher-process restart on a durable
    # outbox_path file).
    pub.outbox.append("k", json.dumps({"event_type": "e", "n": 0}))
    # Broker is UP, but the backlog must drain first: the new publish
    # parks behind it instead of overtaking.
    pub.publish_envelope({"event_type": "e", "n": 1}, routing_key="k")
    assert await_cond(lambda: pub.outbox.depth() == 0)
    assert [env["n"] for _rk, env in stub.published()] == [0, 1]
    assert pub.outbox_stats()["parked"] == 1      # n=1 parked, n=0 manual
    pub.close()


def test_outbox_overflow_raises_structured_bus_saturated():
    stub = StubClient()
    pub = make_publisher(stub, outbox_cap=2)
    stub.down = True
    pub.publish_envelope({"event_type": "e"}, routing_key="k")
    pub.publish_envelope({"event_type": "e"}, routing_key="k")
    with pytest.raises(BusSaturated) as ei:
        pub.publish_envelope({"event_type": "e"}, routing_key="k")
    err = ei.value
    assert err.reason == "outbox-full"
    assert err.routing_key == "k" and err.limit == 2
    assert isinstance(err, PublishError)    # services nack-transient it
    assert pub.outbox_stats()["overflow"] == 1
    # nothing was dropped silently: both parked envelopes still there
    assert pub.outbox.depth() == 2
    pub.close()


def test_injected_publish_fault_takes_the_outage_path():
    """A scripted ``publish`` fault parks the envelope exactly like a
    real outage — the chaos harness's determinism contract."""
    stub = StubClient()
    boundary = resolve_boundary(
        FaultPlan(specs=[FaultSpec(kind="publish", at=1, count=2)]))
    pub = broker_mod.BrokerPublisher({"address": "tcp://stub"},
                                     client=stub, faults=boundary)
    pub._depth_client = stub
    pub.publish_envelope({"event_type": "e", "n": 0}, routing_key="k")
    assert pub.outbox.depth() == 1          # fault == outage == park
    # replay's own publish boundary check burns occurrence 2; after
    # that the replay drains
    assert await_cond(lambda: pub.outbox.depth() == 0)
    assert [env["n"] for _rk, env in stub.published()] == [0]
    pub.close()


# -- depth-watermark backpressure -----------------------------------------


def test_publisher_paces_at_high_watermark_until_drain():
    stub = StubClient()
    pub = make_publisher(stub, high_watermark=10, low_watermark=4,
                         saturation_poll_s=0.01,
                         saturation_max_wait_s=5.0)
    stub.depths["k"] = 12        # confirm reports saturated depth

    drained = threading.Event()

    def drain_later():
        time.sleep(0.05)
        with stub.lock:
            stub.depths["k"] = 3
        drained.set()

    t = threading.Thread(target=drain_later)
    t.start()
    t0 = time.monotonic()
    pub.publish_envelope({"event_type": "e"}, routing_key="k")
    waited = time.monotonic() - t0
    t.join()
    assert drained.is_set() and waited >= 0.04   # actually paced
    assert pub.outbox_stats()["throttle_waits"] == 1
    assert pub.saturation() == {}                 # drained below high
    pub.close()


def test_saturation_surfaces_hot_keys_and_close_releases_pace():
    stub = StubClient()
    pub = make_publisher(stub, high_watermark=5, saturation_poll_s=0.01,
                         saturation_max_wait_s=30.0)
    stub.depths["k"] = 9
    done = threading.Event()

    def blocked_publish():
        pub.publish_envelope({"event_type": "e"}, routing_key="k")
        done.set()

    t = threading.Thread(target=blocked_publish)
    t.start()
    assert await_cond(lambda: pub.saturation() == {"k": 9})
    pub.close()                  # stop event releases the pace wait
    assert done.wait(5.0)
    t.join()


def test_validating_publisher_delegates_depth_feedback():
    """EventPublisher defines concrete {} defaults for saturation()/
    pending_depths(), so the validating wrapper needs EXPLICIT
    delegation — __getattr__ never fires for inherited class attributes.
    Without it every assembled pipeline (all service publishers are
    validating-wrapped) silently loses the consumption throttle and the
    ingestion pacer."""
    from copilot_for_consensus_tpu.bus.validating import (
        ValidatingPublisher,
    )

    broker = InProcBroker("sat.wrap.test")
    pub = ValidatingPublisher(
        InProcPublisher(config={"high_watermark": 2}, broker=broker))
    sub = InProcSubscriber(broker=broker)
    sub.subscribe(["archive.ingested"], lambda env: None)
    for i in range(3):
        pub.publish(ArchiveIngested(archive_id=f"w{i}"))
    assert pub.saturation() == {"archive.ingested": 3}
    assert pub.pending_depths()["archive.ingested"] == 3
    sub.drain()
    assert pub.saturation() == {}


def test_stale_hot_snapshot_repolls_and_clears():
    """A key hot at its last confirm must not read saturated forever
    once the producer goes quiet: past ``saturation_refresh_s`` the
    snapshot re-polls the broker, so a drained queue stops throttling
    consumers (and an unreachable broker reads as not-hot — outages
    are the outbox's problem, not the throttle's)."""
    stub = StubClient()
    pub = make_publisher(stub, high_watermark=10,
                         saturation_poll_s=0.01,
                         saturation_max_wait_s=0.05,
                         saturation_refresh_s=0.0)
    stub.depths["k"] = 12
    pub.publish_envelope({"event_type": "e"}, routing_key="k")
    assert pub.saturation() == {"k": 12}      # re-poll: still hot
    with stub.lock:
        stub.depths["k"] = 0                  # producer quiet, queue drains
    assert pub.saturation() == {}             # stale snapshot re-polled
    with stub.lock:
        stub.depths["k"] = 12
    pub.publish_envelope({"event_type": "e"}, routing_key="k")
    assert pub.saturation() == {"k": 12}      # hot again
    stub.down = True
    assert pub.saturation() == {}             # broker away: not-hot
    pub.close()


def test_inproc_publisher_saturation_parity():
    broker = InProcBroker("sat.test")
    pub = InProcPublisher(config={"high_watermark": 2}, broker=broker)
    sub = InProcSubscriber(broker=broker)
    sub.subscribe(["archive.ingested"], lambda env: None)
    for i in range(3):
        pub.publish(ArchiveIngested(archive_id=f"a{i}"))
    assert pub.saturation() == {"archive.ingested": 3}
    assert pub.pending_depths()["archive.ingested"] == 3
    sub.drain()
    assert pub.saturation() == {}


def test_base_service_throttles_consumption_while_saturated():
    from copilot_for_consensus_tpu.services.base import BaseService

    class HotPublisher:
        def __init__(self):
            self.hot = {"json.parsed": 50}

        def saturation(self):
            return self.hot

        def pending_depths(self):
            return dict(self.hot)

        def publish(self, event, routing_key=None):
            pass

        def publish_envelope(self, envelope, routing_key=None):
            pass

    class Svc(BaseService):
        name = "probe"
        consumes = ()

        def on_ArchiveIngested(self, event):
            pass

    metrics = InMemoryMetrics()
    svc = Svc(HotPublisher(), store=None, metrics=metrics,
              throttle_pause_s=0.03)
    env = ArchiveIngested(archive_id="a1").to_envelope()
    t0 = time.monotonic()
    svc.handle_envelope(env)
    assert time.monotonic() - t0 >= 0.02        # paused once
    assert metrics.counter_value(
        "bus_throttle_total", {"service": "probe"}) == 1
    # stop_throttling releases current and future pauses (shutdown
    # must never wait out a watermark)
    svc.stop_throttling()
    t0 = time.monotonic()
    svc.handle_envelope(env)
    assert time.monotonic() - t0 < 0.02


def test_ingestion_pacing_waits_for_queues_below_watermark():
    from copilot_for_consensus_tpu.services.ingestion import (
        IngestionService,
    )

    class DepthPublisher:
        def __init__(self):
            self.depths = {"json.parsed": 100,
                           "parsing.failed": 10**6}   # failure keys skip

        def saturation(self):
            return {}

        def pending_depths(self):
            return dict(self.depths)

        def publish(self, event, routing_key=None):
            pass

        def publish_envelope(self, envelope, routing_key=None):
            pass

    pub = DepthPublisher()
    svc = IngestionService(pub, store=None, archive_store=None,
                           fetchers={}, bus_watermark=50,
                           bus_poll_s=0.01, bus_pause_max_s=5.0)

    def drain_later():
        time.sleep(0.05)
        pub.depths["json.parsed"] = 5

    t = threading.Thread(target=drain_later)
    t.start()
    waited = svc._await_bus_capacity()
    t.join()
    assert waited >= 0.04                     # held until below SLO
    assert svc._await_bus_capacity() < 0.01   # healthy: no pause
    # unconfigured watermark is a strict no-op
    svc.bus_watermark = 0
    assert svc._await_bus_capacity() == 0.0


# -- poison quarantine ----------------------------------------------------


class StubVerdictClient(StubClient):
    """Records ack/nack verdicts for dispatch-classification tests."""

    def fetch_reply(self, msg):
        return {"ok": True, "msgs": [msg]}


def _dispatch_with(exc, metrics=None):
    stub = StubVerdictClient()
    sub = broker_mod.BrokerSubscriber({"address": "tcp://stub"},
                                      client=stub)
    sub.metrics = metrics or InMemoryMetrics()

    def handler(env):
        if exc is not None:
            raise exc

    sub.subscribe(["archive.ingested"], handler)
    sub._dispatch({"id": 7, "rk": "archive.ingested", "attempts": 0,
                   "envelope": {"event_type": "ArchiveIngested",
                                "event_id": "e-1"}})
    verdicts = [r for r in stub.requests if r["op"] in ("ack", "nack")]
    assert len(verdicts) == 1
    return verdicts[0], sub.metrics


def test_dispatch_classification_transient_vs_poison():
    ack, _ = _dispatch_with(None)
    assert ack["op"] == "ack"

    # RetryableError / bus-level PublishError → plain nack (lease/
    # redelivery budget applies)
    for exc in (RetryableError("flaky"), PublishError("bus away")):
        nack, m = _dispatch_with(exc)
        assert nack["op"] == "nack" and not nack.get("poison")
        assert m.counter_value(
            "bus_dispatch_failures_total",
            {"queue": "archive.ingested", "kind": "transient"}) == 1

    # deterministic failures → poison nack with a structured reason
    for exc, reason_part in (
            (PoisonEnvelope("schema validation failed: no data"),
             "schema validation failed"),
            (ValueError("bad id"), "ValueError: bad id"),
            (PipelineFaultError("injected terminal", kind="store_write"),
             "injected terminal")):
        nack, m = _dispatch_with(exc)
        assert nack["op"] == "nack" and nack["poison"] is True
        assert reason_part in nack["reason"]
        assert m.counter_value("bus_poison_total",
                               {"queue": "archive.ingested"}) == 1
        assert m.counter_value(
            "bus_dispatch_failures_total",
            {"queue": "archive.ingested", "kind": "poison"}) == 1

    # a scripted TRANSIENT pipeline fault is a RetryableError
    nack, _ = _dispatch_with(TransientPipelineFault("hiccup",
                                                    kind="store_write"))
    assert nack["op"] == "nack" and not nack.get("poison")


def test_queuestore_poison_nack_skips_redelivery_budget():
    store = broker_mod._QueueStore(":memory:")
    store.bind(["k"], "g")
    store.enqueue("k", "{}")
    (mid, _rk, _env, _at), = store.fetch(["k"], "g", 1, 30.0)
    store.nack([mid], max_redeliveries=3, poison=True,
               reason="schema validation failed: boom")
    dead = store.dead_letters("k")
    assert len(dead) == 1
    assert dead[0][3] == 0       # attempts untouched: never cycled
    assert dead[0][4] == "schema validation failed: boom"
    # operator requeue resets budget AND reason
    assert store.requeue_dead("k") == 1
    assert store.counts()["k"]["pending"] == 1
    (mid, _rk, _env, _at), = store.fetch(["k"], "g", 1, 30.0)
    for _ in range(3):           # transient path still budgets
        store.nack([mid], max_redeliveries=3)
        got = store.fetch(["k"], "g", 1, 30.0)
        if got:
            (mid, _rk, _env, _at), = got
    dead = store.dead_letters("k")
    assert len(dead) == 1 and dead[0][4] == "redelivery budget exhausted"
    store.close()


def test_inproc_poison_quarantines_without_redelivery():
    broker = InProcBroker("poison.test")
    pub = InProcPublisher(broker=broker)
    sub = InProcSubscriber(broker=broker)
    calls = []

    def poison_handler(env):
        calls.append(env)
        raise PoisonEnvelope("deterministic failure")

    sub.subscribe(["archive.ingested"], poison_handler)
    pub.publish(ArchiveIngested(archive_id="bad"))
    sub.drain()
    assert len(calls) == 1                    # no redelivery cycles
    assert len(broker.dead_lettered) == 1
    assert broker.dead_lettered[0][0] == "archive.ingested"


def test_validating_subscriber_raises_poison_on_schema_failure():
    broker = InProcBroker("val.poison")
    pub = InProcPublisher(broker=broker)
    invalid = []
    sub = ValidatingSubscriber(InProcSubscriber(broker=broker),
                               on_invalid=lambda e, x: invalid.append(e))
    seen = []
    sub.subscribe(["archive.ingested"], lambda env: seen.append(env))
    pub.publish_envelope({"event_type": "ArchiveIngested"},
                         "archive.ingested")           # schema-invalid
    pub.publish(ArchiveIngested(archive_id="ok"))
    sub.drain()
    assert [e["data"]["archive_id"] for e in seen] == ["ok"]
    assert len(invalid) == 1 and sub.invalid_count == 1
    # quarantined (dead-lettered once), not silently acked away
    assert len(broker.dead_lettered) == 1


def test_base_service_unexpected_error_publishes_failure_then_poisons():
    from copilot_for_consensus_tpu.services.base import BaseService

    published = []

    class Pub:
        def publish(self, event, routing_key=None):
            published.append(event)

        def publish_envelope(self, envelope, routing_key=None):
            published.append(envelope)

    class Svc(BaseService):
        name = "probe"
        consumes = ()

        def on_ArchiveIngested(self, event):
            raise KeyError("missing doc")

        def failure_event(self, envelope, error, attempts):
            return ("probe.failed", str(error))

    svc = Svc(Pub(), store=None)
    env = ArchiveIngested(archive_id="a1").to_envelope()
    with pytest.raises(PoisonEnvelope) as ei:
        svc.handle_envelope(env)
    assert "KeyError" in str(ei.value)
    assert len(published) == 1                # the *Failed event record

    class BusDownSvc(Svc):
        def on_ArchiveIngested(self, event):
            raise PublishError("broker away and outbox full")

    # bus-level trouble is transient: propagate for nack/redelivery,
    # do NOT mint a failure event the same broker couldn't carry
    published.clear()
    with pytest.raises(PublishError):
        BusDownSvc(Pub(), store=None).handle_envelope(env)
    assert published == []


# -- zombie-redelivery idempotency ----------------------------------------


_ZOMBIE_MBOX = b"""From a@example.org Mon Jan  1 00:00:00 2024
Message-ID: <m1@example.org>
Subject: consensus call
From: A <a@example.org>
Date: Mon, 1 Jan 2024 00:00:00 +0000

first message

From b@example.org Mon Jan  1 00:00:01 2024
Message-ID: <m2@example.org>
In-Reply-To: <m1@example.org>
Subject: Re: consensus call
From: B <b@example.org>
Date: Mon, 1 Jan 2024 00:00:01 +0000

second message
"""


def test_zombie_reparse_preserves_summary_link_written_mid_parse():
    """At-least-once means a ZOMBIE parse (lease expired mid-parse; the
    redelivery already completed elsewhere) can write thread docs
    minutes late — its writes must not clobber fields other writers
    own. Regression: the old read-carry-replace (get prev → copy
    summary_id → upsert) lost a summary link that landed between its
    stale read and its replace, un-summarizing a whole archive's
    threads AFTER the pipeline looked quiescent (seen as lost=19 in a
    pipeline_chaos storm under CPU contention). The parse write is now
    a field-merge update, so a summary_id landing at ANY point survives
    without ever being read."""
    from copilot_for_consensus_tpu.archive.base import (
        InMemoryArchiveStore,
    )
    from copilot_for_consensus_tpu.services.parsing import ParsingService
    from copilot_for_consensus_tpu.storage.memory import (
        InMemoryDocumentStore,
    )

    class SummaryLandsMidParse(InMemoryDocumentStore):
        """Simulates the summarizer winning the race: the instant the
        zombie parse writes a thread doc, the summary link for that
        thread has JUST been set by the concurrent (completed)
        pipeline."""

        def update_document(self, collection, doc_id, updates):
            if (collection == "threads"
                    and "summary_id" not in updates
                    and not (self.get_document("threads", doc_id)
                             or {}).get("summary_id")):
                super().update_document("threads", doc_id,
                                        {"summary_id": "sum-live"})
            return super().update_document(collection, doc_id, updates)

    store = SummaryLandsMidParse()
    store.connect()
    archive_store = InMemoryArchiveStore()
    archive_store.save("arch-z", _ZOMBIE_MBOX)
    store.upsert_document("archives", {
        "archive_id": "arch-z", "source_id": "s1", "parsed": False})
    broker = InProcBroker("zombie.test")
    svc = ParsingService(InProcPublisher(broker=broker), store,
                         archive_store)

    svc.process_archive("arch-z")           # first parse (creates docs)
    svc.process_archive("arch-z")           # zombie re-parse
    threads = store.query_documents("threads", {})
    assert threads, "fixture produced no threads"
    for th in threads:
        assert th.get("summary_id") == "sum-live", th
        assert th.get("message_count") == 2     # parse fields did land
        assert th.get("parsed_at")              # first-parse stamp kept


# -- fault plane (bus/faults.py) ------------------------------------------


def test_fault_boundary_transient_vs_terminal_kinds():
    boundary = FaultBoundary(
        FaultPlan(specs=[FaultSpec(kind="store_write", at=1, count=1),
                         FaultSpec(kind="archive_read", at=1, count=1)]),
        terminal_kinds=("archive_read",))
    with pytest.raises(TransientPipelineFault) as ti:
        boundary.check("store_write")
    assert isinstance(ti.value, RetryableError)
    assert ti.value.kind == "store_write" and ti.value.occurrence == 1
    with pytest.raises(PipelineFaultError) as pe:
        boundary.check("archive_read")
    assert not isinstance(pe.value, RetryableError)
    boundary.check("store_write")       # spec spent: no fire
    assert boundary.stats()["fired"] == 2


def test_faulting_store_wrappers_fire_and_delegate():
    class Store:
        def __init__(self):
            self.writes = []

        def upsert_document(self, collection, doc):
            self.writes.append((collection, doc))
            return "id-1"

        def find_document(self, collection, doc_id):
            return {"_id": doc_id}

    class Archive:
        def load(self, archive_id):
            return b"bytes"

    plan = FaultPlan(specs=[FaultSpec(kind="store_write", at=1, count=1),
                            FaultSpec(kind="archive_read", at=1,
                                      count=1)])
    boundary = resolve_boundary(plan)
    store = FaultingDocumentStore(Store(), boundary)
    with pytest.raises(TransientPipelineFault):
        store.upsert_document("c", {"a": 1})
    assert store.upsert_document("c", {"a": 1}) == "id-1"   # recovered
    assert store.find_document("c", "x") == {"_id": "x"}    # reads pass
    archive = FaultingArchiveStore(Archive(), boundary)     # SHARED plan
    with pytest.raises(TransientPipelineFault):
        archive.load("a1")
    assert archive.load("a1") == b"bytes"


def test_build_pipeline_wires_fault_plan_end_to_end(fixtures_dir):
    from copilot_for_consensus_tpu.services.runner import build_pipeline

    plan = FaultPlan(seed=3, specs=[
        FaultSpec(kind="store_write", at=1, count=1)]).to_dict()
    p = build_pipeline({"faults": {"plan": plan,
                                   "terminal_kinds": ["archive_read"]}})
    assert p.fault_boundary is not None
    assert p.fault_boundary.terminal_kinds == {"archive_read"}
    # the wrapped store fires the shared boundary
    with pytest.raises(TransientPipelineFault):
        p.store.upsert_document("sources", {"source_id": "s"})
    # spec spent: pipeline runs clean end-to-end afterwards — the
    # transient service-retry spine absorbs nothing here, the plan is
    # simply exhausted
    p.ingestion.create_source({
        "source_id": "m", "name": "m", "fetcher": "local",
        "location": str(fixtures_dir / "ietf-sample.mbox")})
    p.ingestion.trigger_source("m")
    p.drain()
    stats = p.reporting.stats()
    assert stats["reports"] == stats["threads"] > 0


def test_pipeline_bus_counts_and_publisher_stats_inproc():
    from copilot_for_consensus_tpu.services.runner import build_pipeline

    p = build_pipeline({})
    p.broker.publish({"event_type": "report.published"},
                     "report.published")
    counts = p.bus_counts()
    assert counts["report.published"]["pending"] == 1
    assert counts["report.published"]["dead"] == 0
    # drained keys re-report zero, not stick
    p.broker._pending.clear()
    assert p.bus_counts()["report.published"]["pending"] == 0
    # in-proc publishers have no outbox: stats aggregate to zeros
    assert p.publisher_stats()["outbox_depth"] == 0


# -- real broker (zmq): restart ride-through + crash recovery -------------

pytestmark_slow = pytest.mark.slow


@pytest.fixture
def live_broker(tmp_path):
    if not broker_mod.HAS_ZMQ:
        pytest.skip("pyzmq missing")
    b = broker_mod.Broker(port=0,
                          db_path=str(tmp_path / "q.sqlite3")).start()
    yield b
    b.stop()


@pytest.mark.slow
def test_broker_restart_costs_latency_not_work(tmp_path):
    """THE ride-through regression (acceptance bullet 4): the broker
    dies mid-run with a publisher still producing; once it returns on
    the same durable db, the outbox replays in publish order and every
    message is consumed — zero dead letters, zero loss."""
    if not broker_mod.HAS_ZMQ:
        pytest.skip("pyzmq missing")
    db = str(tmp_path / "q.sqlite3")
    port = broker_mod.Broker(port=0).start()  # steal a free port
    addr, pnum = port.address, port.port
    port.stop()
    b = broker_mod.Broker(port=pnum, db_path=db).start()
    pub = broker_mod.BrokerPublisher({"address": addr, "timeout_ms": 300,
                                      "retries": 1})
    sub = broker_mod.BrokerSubscriber({"address": addr})
    seen = []
    sub.subscribe(["archive.ingested"], lambda env: seen.append(env))
    for n in range(3):
        pub.publish_envelope({"event_type": "archive.ingested", "n": n},
                             routing_key="archive.ingested")
    b.stop()                                  # broker restart begins
    for n in range(3, 8):
        pub.publish_envelope({"event_type": "archive.ingested", "n": n},
                             routing_key="archive.ingested")   # parks
    assert pub.outbox.depth() == 5
    assert pub.outbox_stats()["parked"] == 5
    b2 = broker_mod.Broker(port=pnum, db_path=db).start()
    try:
        assert await_cond(lambda: pub.outbox.depth() == 0, timeout=15.0)
        deadline = time.monotonic() + 10
        while len(seen) < 8 and time.monotonic() < deadline:
            sub.drain()
        assert sorted(e["n"] for e in seen) == list(range(8))
        # in order per publisher: the parked tail replayed 3..7 after
        # the confirmed head 0..2
        assert [e["n"] for e in seen] == list(range(8))
        assert b2.store.dead_letters() == []
    finally:
        sub.close()
        pub.close()
        b2.stop()


@pytest.mark.slow
def test_durable_broker_crash_recovery_with_leased_messages(tmp_path):
    """Satellite: broker on a real sqlite db killed mid-run with
    messages pending AND leased; restart → pending survive, expired
    leases redeliver, consumers resume via start_consuming's backoff,
    nothing lost, nothing double-acked."""
    if not broker_mod.HAS_ZMQ:
        pytest.skip("pyzmq missing")
    import subprocess
    import sys

    db = str(tmp_path / "queues.sqlite3")
    port = 5743
    cmd = [sys.executable, "-m", "copilot_for_consensus_tpu.bus.broker",
           "--port", str(port), "--db", db, "--lease-s", "0.5"]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE)
    seen: list[dict] = []
    consumer = None
    consume_thread = None
    try:
        proc.stdout.readline()                # bound
        addr = f"tcp://127.0.0.1:{port}"
        pub = broker_mod.BrokerPublisher({"address": addr,
                                          "timeout_ms": 500})
        for i in range(12):
            pub.publish_envelope({"event_type": "archive.ingested",
                                  "n": i},
                                 routing_key="archive.ingested")
        # a consumer loop that survives the outage via backoff
        consumer = broker_mod.BrokerSubscriber(
            {"address": addr, "timeout_ms": 300, "retries": 1,
             "poll_interval_s": 0.02})
        lock = threading.Lock()

        def handle(env):
            with lock:
                seen.append(env)

        consumer.subscribe(["archive.ingested"], handle)
        consume_thread = threading.Thread(
            target=consumer.start_consuming, daemon=True)
        consume_thread.start()
        assert await_cond(lambda: len(seen) >= 2, timeout=10.0)
        # strand one message INFLIGHT: fetch on a separate group-
        # sharing client and never ack, then kill the broker
        zombie = broker_mod.BrokerSubscriber({"address": addr,
                                              "timeout_ms": 500})
        zombie.subscribe(["archive.ingested"], lambda env: None)
        zombie._client.request({"op": "fetch",
                                "rks": ["archive.ingested"], "max": 1})
        zombie.close()
        proc.kill()
        proc.wait(timeout=10)
        time.sleep(0.6)        # consumer loop rides the outage backoff
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE)
        proc.stdout.readline()
        # everything delivers: pending survived the crash, the stranded
        # lease expired and redelivered, the loop reconnected by itself
        assert await_cond(
            lambda: len({e["n"] for e in seen}) == 12, timeout=20.0)
        time.sleep(0.7)        # one more lease window: no double-acks
        counts = {}
        c = broker_mod._Client(f"tcp://127.0.0.1:{port}",
                               timeout_ms=1000)
        counts = c.request({"op": "counts"})["counts"]
        c.close()
        assert counts.get("archive.ingested", {}).get("pending", 0) == 0
        assert counts.get("archive.ingested", {}).get("inflight", 0) == 0
        # at-least-once: duplicates allowed, loss is not
        assert {e["n"] for e in seen} == set(range(12))
        pub.close()
    finally:
        if consumer is not None:
            consumer.stop()
        if consume_thread is not None:
            consume_thread.join(timeout=5)
        if consumer is not None:
            consumer.close()
        proc.kill()
        proc.wait(timeout=10)


@pytest.mark.slow
def test_poison_quarantine_and_dlq_ops_on_durable_broker(live_broker):
    """Poison goes straight to the dead-letter table with its reason;
    the failed-queues CLI surface (DeadLetterManager) triages,
    requeues, and purges it."""
    from copilot_for_consensus_tpu.tools.failed_queues import (
        DeadLetterManager,
    )

    pub = broker_mod.BrokerPublisher({"address": live_broker.address})
    sub = broker_mod.BrokerSubscriber({"address": live_broker.address})
    calls = []

    def poison(env):
        calls.append(env)
        raise ValueError("deterministic: unknown archive")

    sub.subscribe(["archive.ingested"], poison)
    pub.publish_envelope({"event_type": "archive.ingested", "n": 1},
                         routing_key="archive.ingested")
    for _ in range(3):
        sub.drain()
    assert len(calls) == 1                    # skipped the budget
    dlq = DeadLetterManager(live_broker.address)
    dead = dlq.list_dead("archive.ingested")
    assert len(dead) == 1
    assert "ValueError: deterministic" in dead[0]["reason"]
    assert dead[0]["attempts"] == 0
    summary = dlq.summarize_dead()
    assert list(summary) == ["archive.ingested"]
    # requeue → redelivers (and re-quarantines, cause unfixed)
    assert dlq.requeue_dead("archive.ingested") == 1
    sub.drain()
    assert len(calls) == 2
    assert dlq.purge_dead("archive.ingested") == 1
    assert dlq.list_dead() == []
    dlq.close()
    sub.close()
    pub.close()


@pytest.mark.slow
def test_backpressure_bounds_broker_depth_under_overload(live_broker):
    """Sustained overload with the watermark configured: broker depth
    converges under the watermark instead of growing unboundedly."""
    hw = 20
    pub = broker_mod.BrokerPublisher(
        {"address": live_broker.address, "high_watermark": hw,
         "low_watermark": 5, "saturation_poll_s": 0.01,
         "saturation_max_wait_s": 10.0})
    sub = broker_mod.BrokerSubscriber({"address": live_broker.address,
                                       "batch": 4})
    sub.subscribe(["archive.ingested"], lambda env: time.sleep(0.001))
    stop = threading.Event()
    max_depth = 0

    def consume():
        while not stop.is_set():
            sub.drain(max_messages=4)
            time.sleep(0.002)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    for n in range(200):
        pub.publish_envelope({"event_type": "archive.ingested", "n": n},
                             routing_key="archive.ingested")
        max_depth = max(max_depth,
                        live_broker.store.depth("archive.ingested"))
    stop.set()
    t.join(timeout=5)
    sub.close()
    assert pub.outbox_stats()["throttle_waits"] >= 1
    # pacing holds the flood at the watermark (+ batch slack)
    assert max_depth <= hw + 5, max_depth
    pub.close()


@pytest.mark.slow
def test_pipeline_chaos_storm_gate():
    """THE tentpole gate at test scale: the same three-arm harness
    BENCH_PRESET=pipeline_chaos runs (overload with backpressure
    off/on, then the seeded storm — broker restart, store/vector/
    archive faults, consumer crash-after-work, consume-loop outages,
    scripted publish faults, poison envelopes) over a scaled-down
    corpus. Zero threads without a summary, zero duplicate terminal
    artifacts, exactly the injected poison quarantined, parked
    publishes replayed, final depths inside the scaled SLO."""
    if not broker_mod.HAS_ZMQ:
        pytest.skip("pyzmq missing")
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    knobs = {"BENCH_PIPE_MESSAGES": "160", "BENCH_PIPE_ARCHIVES": "4",
             "BENCH_PIPE_FLOOD_MESSAGES": "120",
             "BENCH_PIPE_FLOOD_ARCHIVES": "2",
             "BENCH_PIPE_WARN_SLO": "16",
             "BENCH_PIPE_DRAG_S": "0.015",
             "BENCH_PIPE_POISON": "3",
             "BENCH_PIPE_BUDGET_S": "240"}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    try:
        out = bench.pipeline_chaos_headline()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert out["lost"] == 0, out
    assert out["duplicated"] == 0, out
    assert out["quarantined"] == 3, out
    assert out["replayed_publishes"] >= 1, out
    assert out["redelivered"] >= 1, out
    assert out["final_depth_max"] < 16, out
    # both overload arms in the artifact: pacing held depth under the
    # scaled warn SLO; the unpaced arm flooded well past it
    assert out["max_depth_backpressure_on"] < 16, out
    assert out["max_depth_backpressure_off"] >= 32, out
    # tracing tentpole: zero orphan spans under the storm (redelivery,
    # outbox replay and the broker restart all yield annotated retries)
    # and the dragged chunking handler is the NAMED bottleneck stage
    assert out["orphan_spans"] == 0, out
    assert out["bottleneck_stage"] == "chunking", out
    assert out["stage_p95_s"].get("chunking", 0) > 0, out
    assert "chunking" in out["queue_wait_p95_s"], out
    assert out["backpressure_ok"] and out["storm_ok"], out
    assert out["pipeline_chaos_ok"] is True, out


# -- stage scale-out (ISSUE 11): competing consumers + batched dispatch ---


def test_queuestore_competing_consumers_never_double_dispatch():
    """Two fetchers in ONE group over the durable queue store must
    split the backlog disjointly: fetch atomically moves rows to
    inflight, so a message can never be leased twice while a lease is
    live."""
    store = broker_mod._QueueStore(":memory:")
    store.bind(["k"], "g")
    for i in range(30):
        store.enqueue("k", "{}")
    a = store.fetch(["k"], "g", 16, 30.0)
    b = store.fetch(["k"], "g", 16, 30.0)
    ids_a = {r[0] for r in a}
    ids_b = {r[0] for r in b}
    assert not ids_a & ids_b
    assert len(ids_a | ids_b) == 30
    store.ack(sorted(ids_a | ids_b))
    assert store.counts() == {}
    store.close()


def test_queuestore_expired_lease_redelivers_exactly_once():
    store = broker_mod._QueueStore(":memory:")
    store.bind(["k"], "g")
    store.enqueue("k", "{}")
    (mid, _rk, _env, at0), = store.fetch(["k"], "g", 4, 0.01)
    assert store.fetch(["k"], "g", 4, 0.01) == []   # leased: invisible
    time.sleep(0.05)
    store.expire_leases()
    redelivered = store.fetch(["k"], "g", 4, 30.0)
    assert [r[0] for r in redelivered] == [mid]     # same row, once
    assert store.fetch(["k"], "g", 4, 30.0) == []
    store.ack([mid])
    assert store.dead_letters() == []
    store.close()


def test_queuestore_dlq_counts_exact_under_concurrent_nacks():
    """N worker threads nacking concurrently (half poison, half budget
    exhaustion) must leave EXACTLY one dead row per message, reasons
    and attempt counters intact — the competing-consumer quarantine
    contract."""
    store = broker_mod._QueueStore(":memory:")
    store.bind(["k"], "g")
    for _ in range(12):
        store.enqueue("k", "{}")
    rows = store.fetch(["k"], "g", 12, 30.0)
    assert len(rows) == 12
    ids = [r[0] for r in rows]

    def poison_nack(batch):
        store.nack(batch, max_redeliveries=1, poison=True,
                   reason="schema validation failed: x")

    def budget_nack(batch):
        store.nack(batch, max_redeliveries=1)

    threads = [threading.Thread(target=poison_nack, args=(ids[i::4],))
               for i in range(2)]
    threads += [threading.Thread(target=budget_nack, args=(ids[2 + i::4],))
                for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dead = store.dead_letters("k")
    assert len(dead) == 12
    assert sorted(d[0] for d in dead) == sorted(ids)   # no dup, no loss
    poisoned = [d for d in dead if d[4].startswith("schema validation")]
    budgeted = [d for d in dead if d[4] == "redelivery budget exhausted"]
    assert len(poisoned) == 6 and len(budgeted) == 6
    assert all(d[3] == 0 for d in poisoned)    # attempts untouched
    assert all(d[3] == 1 for d in budgeted)
    store.close()


def test_broker_subscriber_prefetch_config_knob():
    """`bus.prefetch` sizes the per-fetch lease batch (the old
    hardcoded 16); the legacy `batch` key stays as an alias and
    prefetch wins when both are set."""
    stub = StubClient()
    assert broker_mod.BrokerSubscriber(
        {"address": "tcp://stub"}, client=stub).batch == 16
    assert broker_mod.BrokerSubscriber(
        {"address": "tcp://stub", "prefetch": 48},
        client=stub).batch == 48
    assert broker_mod.BrokerSubscriber(
        {"address": "tcp://stub", "batch": 9}, client=stub).batch == 9
    assert broker_mod.BrokerSubscriber(
        {"address": "tcp://stub", "prefetch": 48, "batch": 9},
        client=stub).batch == 48


class StubWaveClient(StubClient):
    """StubClient whose fetches serve scripted message waves."""

    def __init__(self, waves):
        super().__init__()
        self.waves = list(waves)

    def request(self, req):
        if req.get("op") == "fetch":
            with self.lock:
                self.requests.append(dict(req))
            return {"ok": True,
                    "msgs": self.waves.pop(0) if self.waves else []}
        return super().request(req)


def _wave_msgs(rk, n, start=1):
    return [{"id": start + i, "rk": rk, "attempts": 0,
             "envelope": {"event_type": "JSONParsed", "event_id": f"e{i}",
                          "data": {"message_doc_id": f"m{i}"}}}
            for i in range(n)]


def test_broker_batch_dispatch_groups_verdicts_per_outcome():
    """A registered batch route dispatches one fetch wave as ONE
    callback call; per-envelope outcomes map to grouped verdicts —
    one ack for the successes, one transient nack, poison nacks with
    their structured reasons."""
    from copilot_for_consensus_tpu.core.retry import RetryableError

    stub = StubWaveClient([_wave_msgs("json.parsed", 4)])
    sub = broker_mod.BrokerSubscriber({"address": "tcp://stub"},
                                      client=stub)
    sub.metrics = InMemoryMetrics()
    waves = []

    def batch_cb(envelopes):
        waves.append(list(envelopes))
        return [None, RetryableError("store busy"), None,
                PoisonEnvelope("schema validation failed: nope")]

    sub.subscribe(["json.parsed"], lambda env: None)
    assert sub.subscribe_batch(["json.parsed"], batch_cb) is True
    assert sub.drain(4) == 4
    assert len(waves) == 1 and len(waves[0]) == 4
    verdicts = [r for r in stub.requests if r["op"] in ("ack", "nack")]
    acks = [v for v in verdicts if v["op"] == "ack"]
    nacks = [v for v in verdicts if v["op"] == "nack"]
    assert len(acks) == 1 and sorted(acks[0]["ids"]) == [1, 3]
    transient = [v for v in nacks if not v.get("poison")]
    poison = [v for v in nacks if v.get("poison")]
    assert len(transient) == 1 and transient[0]["ids"] == [2]
    assert len(poison) == 1 and poison[0]["ids"] == [4]
    assert "schema validation failed" in poison[0]["reason"]


def test_broker_batch_callback_raise_falls_back_to_single_dispatch():
    """A wave-level callback failure degrades to the exact per-envelope
    path: every message dispatched individually, individually acked."""
    stub = StubWaveClient([_wave_msgs("json.parsed", 3)])
    sub = broker_mod.BrokerSubscriber({"address": "tcp://stub"},
                                      client=stub)
    sub.metrics = InMemoryMetrics()
    singles = []
    sub.subscribe(["json.parsed"], lambda env: singles.append(env))

    def bad_batch(envelopes):
        raise RuntimeError("whole wave exploded")

    sub.subscribe_batch(["json.parsed"], bad_batch)
    assert sub.drain(3) == 3
    assert len(singles) == 3
    acks = [r for r in stub.requests if r["op"] == "ack"]
    assert sorted(i for a in acks for i in a["ids"]) == [1, 2, 3]
    assert not [r for r in stub.requests if r["op"] == "nack"]


def test_broker_batch_dispatch_only_groups_registered_keys():
    """Keys without a batch route keep per-envelope dispatch even when
    fetched in the same wave as batched keys."""
    wave = _wave_msgs("json.parsed", 2) + [
        {"id": 9, "rk": "source.deletion", "attempts": 0,
         "envelope": {"event_type": "SourceDeletionRequested",
                      "event_id": "d1", "data": {}}}]
    stub = StubWaveClient([wave])
    sub = broker_mod.BrokerSubscriber({"address": "tcp://stub"},
                                      client=stub)
    sub.metrics = InMemoryMetrics()
    singles, batches = [], []
    sub.subscribe(["json.parsed", "source.deletion"],
                  lambda env: singles.append(env))
    sub.subscribe_batch(["json.parsed"],
                        lambda envs: batches.append(list(envs)))
    assert sub.drain(3) == 3
    assert len(batches) == 1 and len(batches[0]) == 2
    assert len(singles) == 1
    assert singles[0]["event_type"] == "SourceDeletionRequested"


def test_validating_subscriber_batch_quarantines_invalid_per_envelope():
    """The validating wrapper's batch path must (a) exist explicitly —
    the base class's concrete `return False` default would otherwise
    shadow delegation and silently disable batching — and (b) validate
    per envelope: invalid ones become PoisonEnvelope outcomes without
    ever reaching the service wave."""
    captured = {}

    class FakeInner:
        def subscribe_batch(self, rks, cb):
            captured["cb"] = cb
            return True

    invalid_seen = []
    vsub = ValidatingSubscriber(FakeInner(),
                                on_invalid=lambda e, x:
                                invalid_seen.append(e))
    inner_waves = []

    def service_wave(envelopes):
        inner_waves.append(list(envelopes))
        return [None] * len(envelopes)

    assert vsub.subscribe_batch(["archive.ingested"],
                                service_wave) is True
    good = ArchiveIngested(archive_id="a1").to_envelope()
    bad = {"event_type": "ArchiveIngested", "nope": 1}
    outcomes = captured["cb"]([bad, good, dict(bad)])
    assert isinstance(outcomes[0], PoisonEnvelope)
    assert outcomes[1] is None
    assert isinstance(outcomes[2], PoisonEnvelope)
    assert len(inner_waves) == 1 and len(inner_waves[0]) == 1
    assert vsub.invalid_count == 2 and len(invalid_seen) == 2


@pytest.mark.slow
def test_competing_subscribers_on_durable_broker_split_work(tmp_path):
    """Two real subscribers in one group over the live broker: every
    message dispatched exactly once across the pool, nothing
    double-dispatched, nothing lost — the StageWorkerPool's delivery
    contract."""
    if not broker_mod.HAS_ZMQ:
        pytest.skip("pyzmq missing")
    b = broker_mod.Broker(port=0,
                          db_path=str(tmp_path / "q.sqlite3")).start()
    try:
        pub = broker_mod.BrokerPublisher({"address": b.address})
        seen: dict[str, list[str]] = {"a": [], "b": []}
        subs = {}
        for name in ("a", "b"):
            sub = broker_mod.BrokerSubscriber(
                {"address": b.address, "prefetch": 4}, group="svc")
            sub.subscribe(
                ["archive.ingested"],
                lambda env, n=name: seen[n].append(
                    env["data"]["archive_id"]))
            subs[name] = sub
        for i in range(40):
            pub.publish(ArchiveIngested(archive_id=f"m{i}"))
        threads = [threading.Thread(target=s.start_consuming)
                   for s in subs.values()]
        for t in threads:
            t.start()
        assert await_cond(
            lambda: len(seen["a"]) + len(seen["b"]) >= 40, timeout=20)
        time.sleep(0.3)          # would-be double dispatches land now
        for s in subs.values():
            s.stop()
        for t in threads:
            t.join(timeout=5)
        got = seen["a"] + seen["b"]
        assert sorted(got) == sorted({f"m{i}" for i in range(40)})
        assert len(got) == 40                      # exactly once
        counts = subs["a"].counts(timeout_ms=2000)
        assert counts.get("archive.ingested", {}).get("pending", 0) == 0
        for s in subs.values():
            s.close()
        pub.close()
    finally:
        b.stop()


def test_publish_window_groups_wave_publishes_into_one_request():
    """Grouped publishes: N publish() calls inside a window reach the
    broker as ONE pub_batch request, in order; depths ride along."""
    stub = StubClient()
    pub = make_publisher(stub)
    with pub.publish_window():
        for i in range(5):
            pub.publish(ArchiveIngested(archive_id=f"a{i}"))
        # nested window joins the outer one (no premature flush)
        with pub.publish_window():
            pub.publish(ArchiveIngested(archive_id="a5"))
    batches = [r for r in stub.requests if r["op"] == "pub_batch"]
    singles = [r for r in stub.requests if r["op"] == "pub"]
    assert len(batches) == 1 and not singles
    ids = [it["envelope"]["data"]["archive_id"]
           for it in batches[0]["items"]]
    assert ids == [f"a{i}" for i in range(6)]
    assert pub.outbox_stats()["confirmed"] == 6
    # outside the window, publishes go back to per-event confirms
    pub.publish(ArchiveIngested(archive_id="solo"))
    assert [r["op"] for r in stub.requests][-1] == "pub"
    pub.close()


def test_publish_window_outage_parks_whole_window_in_order():
    stub = StubClient()
    pub = make_publisher(stub)
    stub.down = True
    with pub.publish_window():
        for i in range(3):
            pub.publish(ArchiveIngested(archive_id=f"a{i}"))
    assert pub.outbox.depth() == 3
    stub.down = False
    assert await_cond(lambda: pub.outbox.depth() == 0)
    # replayed oldest-first as singles: order preserved
    ids = [env["data"]["archive_id"] for _rk, env in stub.published()]
    assert ids == ["a0", "a1", "a2"]
    assert pub.outbox_stats()["replayed"] == 3
    pub.close()


def test_queuestore_enqueue_many_one_transaction_depths():
    store = broker_mod._QueueStore(":memory:")
    store.bind(["k1"], "g")
    store.bind(["k2"], "g")
    depths = store.enqueue_many([("k1", "{}"), ("k2", "{}"),
                                 ("k1", "{}")])
    assert depths == {"k1": 2, "k2": 1}
    counts = store.counts()
    assert counts["k1"]["pending"] == 2
    assert counts["k2"]["pending"] == 1
    store.close()
