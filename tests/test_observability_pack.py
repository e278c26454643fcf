# Observability pack: alert rules + dashboards as code, bus gauges on the
# gateway /metrics, jax.profiler capture (VERDICT r1 item 9).
import json
import pathlib
import re
import urllib.request

import pytest

yaml = pytest.importorskip(
    "yaml", reason="pyyaml (dev extra) needed for alert-rule linting")

REPO = pathlib.Path(__file__).resolve().parent.parent
ALERTS = REPO / "infra" / "prometheus" / "alerts"
DASHBOARDS = REPO / "infra" / "grafana" / "dashboards"

# Metric families the code actually emits (services/base.py central
# counters + per-service counters + bus gauges + pushgateway self-metric
# + prometheus built-ins). The lint below keeps alert exprs honest.
KNOWN_SERIES = {
    "copilot_ingestion_events_total", "copilot_parsing_events_total",
    "copilot_chunking_events_total", "copilot_embedding_events_total",
    "copilot_orchestrator_events_total",
    "copilot_summarization_events_total",
    "copilot_reporting_events_total",
    # per-stage handle histograms (services/base.py:90)
    "copilot_ingestion_handle_seconds", "copilot_parsing_handle_seconds",
    "copilot_chunking_handle_seconds", "copilot_embedding_handle_seconds",
    "copilot_orchestrator_handle_seconds",
    "copilot_summarization_handle_seconds",
    "copilot_reporting_handle_seconds",
    "copilot_ingestion_archives_total", "copilot_ingestion_dedup_total",
    "copilot_parsing_messages_total", "copilot_chunking_chunks_total",
    "copilot_embedding_chunks_total", "copilot_embedding_batch_seconds",
    "copilot_orchestrator_requests_total",
    "copilot_orchestrator_dedup_total",
    "copilot_summarization_summaries_total",
    "copilot_summarization_latency_seconds",
    "copilot_reporting_reports_total",
    # stats exporter gauges (tools/exporters.py)
    "copilot_collection_documents", "copilot_documents_pending",
    "copilot_vectorstore_vectors", "copilot_vectorstore_dimension",
    "copilot_exporter_scrape_seconds",
    # retry-job pushed metrics (tools/retry_job.py)
    "copilot_retry_requeued_total", "copilot_retry_exhausted_documents",
    "copilot_retry_last_sweep_timestamp", "copilot_retry_sweep_seconds",
    # process/host resource gauges (obs/resources.py)
    "copilot_process_resident_bytes", "copilot_process_memory_limit_bytes",
    "copilot_process_cpu_seconds_total", "copilot_process_open_fds",
    "copilot_process_start_time_seconds",
    "copilot_disk_free_bytes", "copilot_disk_total_bytes",
    "up", "push_time_seconds", "time", "vector", "absent",
}

# Engine flight-recorder series come from the telemetry REGISTRY
# (engine/telemetry.py:METRICS), not a hand-copied list — the registry
# is what the telemetry layer actually emits, so dashboard/alert
# references can only reference what exists.
from copilot_for_consensus_tpu.engine.telemetry import (  # noqa: E402
    METRICS as ENGINE_METRICS,
    prometheus_series as _engine_series,
)

KNOWN_SERIES |= set(_engine_series())

# Bus series likewise come from the BUS_METRICS registry next to the
# emitter (services/bootstrap.py:_BusGaugeMetrics) — the PR-5 pattern
# extended to the pipeline fault plane (PR 8): alerts/dashboards can
# only reference bus series the gateway exposition actually carries.
from copilot_for_consensus_tpu.services.bootstrap import (  # noqa: E402
    BUS_METRICS,
)

KNOWN_SERIES |= set(BUS_METRICS)

# Pipeline-trace series come from the tracing registry
# (obs/trace.py:PIPELINE_METRICS) — stage span histograms emitted by
# services/base.py per dispatch, span-ledger counters refreshed on the
# gateway scrape — same contract discipline as the engine registry.
from copilot_for_consensus_tpu.obs.trace import (  # noqa: E402
    PIPELINE_METRICS,
    prometheus_series as _pipeline_series,
)

KNOWN_SERIES |= set(_pipeline_series())

# Process-lifecycle series (services/lifecycle.py) — the drain state
# machine's gauge, same registry-next-to-emitter discipline.
from copilot_for_consensus_tpu.services.lifecycle import (  # noqa: E402
    LIFECYCLE_METRICS,
)

KNOWN_SERIES |= set(LIFECYCLE_METRICS)

# Retrieval series (vectorstore/tpu.py) — query latency/route counters,
# ivf probe/spill gauges — same registry-next-to-emitter discipline.
from copilot_for_consensus_tpu.vectorstore.tpu import (  # noqa: E402
    VECTORSTORE_METRICS,
)

KNOWN_SERIES |= set(VECTORSTORE_METRICS)

# Telemetry-shipping self-metrics (obs/ship.py) — spool row counters,
# flush latency, spool depth — same registry-next-to-emitter
# discipline (ISSUE 20).
from copilot_for_consensus_tpu.obs.ship import (  # noqa: E402
    SHIP_METRICS,
)

KNOWN_SERIES |= set(SHIP_METRICS)
# [a-z0-9_]: engine series contain digits (engine_e2e_seconds)
_SERIES_RE = re.compile(r"\b(copilot_[a-z0-9_]+|up|push_time_seconds)\b")


def _alert_files():
    files = sorted(ALERTS.glob("*.yml"))
    assert len(files) >= 5, "alert pack incomplete"
    return files


def test_alert_rules_parse_and_have_required_fields():
    total = 0
    for f in _alert_files():
        doc = yaml.safe_load(f.read_text())
        for group in doc["groups"]:
            assert group["name"]
            for rule in group["rules"]:
                assert rule["alert"] and rule["expr"], (f.name, rule)
                assert "summary" in rule.get("annotations", {}), rule
                assert "severity" in rule.get("labels", {}), rule
                total += 1
    assert total >= 60, f"only {total} rules"


def test_alert_exprs_reference_real_series():
    """Every metric family an alert references must be one the code
    emits — an alert on a typo'd series never fires and rots silently."""
    for f in _alert_files():
        doc = yaml.safe_load(f.read_text())
        for group in doc["groups"]:
            for rule in group["rules"]:
                for name in _SERIES_RE.findall(rule["expr"]):
                    base = re.sub(r"_(bucket|sum|count)$", "", name)
                    assert base in KNOWN_SERIES, (f.name, rule["alert"],
                                                  name)


def test_dashboards_parse_and_reference_real_series():
    files = sorted(DASHBOARDS.glob("*.json"))
    assert len(files) >= 11, "dashboard pack incomplete"
    uids = set()
    for f in files:
        doc = json.loads(f.read_text())
        assert doc["title"] and doc["panels"], f.name
        assert doc["uid"] not in uids, f"duplicate uid {doc['uid']}"
        uids.add(doc["uid"])
        for panel in doc["panels"]:
            for target in panel.get("targets", []):
                for name in _SERIES_RE.findall(target["expr"]):
                    base = re.sub(r"_(bucket|sum|count)$", "", name)
                    assert base in KNOWN_SERIES, (f.name, panel["title"],
                                                  name)


# -- engine flight-recorder metric-name contract -------------------------
#
# The PR-1 bug class: an alert wrote deriv() where the series needed
# rate() (or referenced a series nobody emits) and rotted silently —
# the expression evaluates to empty/garbage and the alert can never
# fire. These tests catch both statically: every copilot_engine_*
# reference must exist in the telemetry registry, carry the right
# suffix for its type, and sit under a PromQL function legal for that
# type. A separate test drives a full EngineTelemetry lifecycle and
# asserts the registry matches what is ACTUALLY emitted, both ways.


def _serving_pack_exprs():
    exprs = []
    doc = json.loads((DASHBOARDS / "serving-engines.json").read_text())
    for panel in doc["panels"]:
        for target in panel.get("targets", []):
            exprs.append((f"dashboard:{panel['title']}", target["expr"]))
    doc = yaml.safe_load((ALERTS / "serving.yml").read_text())
    for group in doc["groups"]:
        for rule in group["rules"]:
            exprs.append((f"alert:{rule['alert']}", rule["expr"]))
    return exprs


_ENGINE_REF_RE = re.compile(r"\bcopilot_engine_[a-z0-9_]+\b")


def test_engine_series_references_are_emitted_by_registry():
    emitted = _engine_series()            # full name -> type
    refs = {}
    for where, expr in _serving_pack_exprs():
        for name in _ENGINE_REF_RE.findall(expr):
            refs.setdefault(name, where)
    assert refs, "serving pack references no engine telemetry series"
    for name, where in refs.items():
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert base in emitted, (
            f"{where} references {name}, which the telemetry registry "
            f"(engine/telemetry.py:METRICS) does not emit")
        if name != base:
            assert emitted[base] == "histogram", (
                f"{where}: {name} uses a histogram suffix but "
                f"{base} is a {emitted[base]}")


def test_engine_promql_functions_match_series_types():
    """rate()/increase() need counters (or histogram components);
    deriv()/ *_over_time need gauges — applied to the wrong type the
    expression silently evaluates to nonsense."""
    emitted = _engine_series()
    rate_re = re.compile(r"\b(?:rate|irate|increase)\(\s*"
                         r"(copilot_engine_[a-z0-9_]+)")
    gauge_fn_re = re.compile(
        r"\b(?:deriv|avg_over_time|min_over_time|max_over_time|"
        r"quantile_over_time|delta)\(\s*(copilot_engine_[a-z0-9_]+)")
    for where, expr in _serving_pack_exprs():
        for name in rate_re.findall(expr):
            base = re.sub(r"_(bucket|sum|count)$", "", name)
            typ = emitted.get(base)
            assert typ in ("counter", "histogram"), (
                f"{where}: rate() over {name} ({typ}) — gauges need "
                f"deriv()/…_over_time")
            if typ == "histogram":
                assert name != base, (
                    f"{where}: rate() over bare histogram {name}; use "
                    f"_bucket/_sum/_count")
        for name in gauge_fn_re.findall(expr):
            base = re.sub(r"_(bucket|sum|count)$", "", name)
            assert emitted.get(base) == "gauge", (
                f"{where}: gauge function over {name} "
                f"({emitted.get(base)}) — counters/histograms need "
                f"rate()")


def test_telemetry_registry_matches_actual_emission():
    """Drive one full lifecycle through EngineTelemetry and assert the
    set of series it lands in its collector EQUALS the registry — a
    metric added to the code but not the registry (or vice versa) fails
    here, keeping the contract tests above honest."""
    from copilot_for_consensus_tpu.engine.telemetry import (
        EngineTelemetry,
    )
    from copilot_for_consensus_tpu.obs.metrics import InMemoryMetrics

    m = InMemoryMetrics(namespace="copilot")
    tele = EngineTelemetry(engine="generation", num_slots=4, metrics=m)
    tele.on_submit(1, prompt_len=16, correlation_id="c-1")
    tele.on_admit(1, wave_start=0.0, admit_kind="seeded",
                  prefix_hit_tokens=8)
    tele.record_step("prefill_seeded", 0.01, rows=1, batch=2,
                     tokens=8, padded_tokens=32)
    tele.record_step("decode", 0.002, rows=1, batch=4, tokens=4,
                     padded_tokens=32)
    tele.record_step("verify", 0.002, rows=1, batch=4, tokens=3,
                     padded_tokens=16, draft_tokens=4,
                     accepted_tokens=2)
    tele.gauge_queue(3, active=1)
    # host phases of the serving loop (obs/profile.py:HOST_PHASES)
    with tele.host_span("plan", ahead=True):
        pass
    # scheduler series (engine/scheduler.py): per-tenant gauges, the
    # shed counter, and the chunked-prefill counter
    tele.sched_gauges({"tenant-a": 2, "": 1},
                      {"tenant-a": 128.0, "": 0.0})
    tele.on_shed("tenant-a", "batch")
    tele.on_prefill_chunks(3)
    tele.record_step("prefill_chunk", 0.004, rows=2, batch=4,
                     tokens=48, padded_tokens=256)
    # resilience series (engine/faults.py + engine/supervisor.py):
    # fault plane, watchdog, breakers, replay, audit, deadlines
    tele.on_fault_injected("decode", "error")
    tele.on_watchdog_trip("decode")
    tele.breaker_gauge("spec_verify", 1.0)
    tele.breaker_gauge("resource", 0.5)
    tele.on_replay()
    tele.on_replay_failed()
    tele.gauge_quarantined(1)
    tele.on_released_pins(2)
    tele.on_deadline_expired()
    # paged KV block pool (engine/kv_pool.py)
    tele.gauge_kv_pool(12, pinned_blocks=3, fragmentation_ratio=0.25)
    tele.on_zero_copy_admits(2)
    tele.gauge_kv_route("kernel")
    # disaggregated prefill/decode roles (engine/roles.py)
    tele.gauge_role_occupancy("prefill", 0.75)
    tele.on_handoff(blocks=6, wait_s=0.01)
    # durable request journal (engine/journal.py)
    tele.gauge_journal(2, checkpoint_lag=5)
    tele.on_journal_replayed()
    tele.on_retire(1, new_tokens=8, finish_reason="eos")
    tele.update_ledgers(
        prefix_stats={"enabled": True, "hit_rate": 0.5},
        spec_stats={"enabled": True, "acceptance_rate": 0.5,
                    "draft_hit_rate": 0.25,
                    "tokens_per_weight_pass": 2.0})
    tele.record_error(RuntimeError("boom"))
    emitted = (set(m.counters) | set(m.gauges) | set(m.histograms))
    assert emitted == set(ENGINE_METRICS), (
        f"registry drift: only-in-code {emitted - set(ENGINE_METRICS)}, "
        f"only-in-registry {set(ENGINE_METRICS) - emitted}")
    # and the TYPE of each emitted series matches its declaration
    for name, (typ, _labels, _help) in ENGINE_METRICS.items():
        store = {"counter": m.counters, "gauge": m.gauges,
                 "histogram": m.histograms}[typ]
        assert name in store, (name, typ)


def test_pipeline_trace_registry_matches_actual_emission():
    """Drive one traced dispatch through a BaseService and assert the
    set of pipeline_* series it lands EQUALS the registry's histogram
    families (the span-ledger counters are scrape-time, asserted in
    test_gateway_metrics_exposes_pipeline_span_counters) — with the
    declared types."""
    from copilot_for_consensus_tpu.bus.base import NoopPublisher
    from copilot_for_consensus_tpu.core.events import JSONParsed
    from copilot_for_consensus_tpu.obs.metrics import InMemoryMetrics
    from copilot_for_consensus_tpu.services.base import BaseService
    from copilot_for_consensus_tpu.storage.memory import (
        InMemoryDocumentStore,
    )

    class Svc(BaseService):
        name = "chunking"
        consumes = ("JSONParsed",)

        def on_JSONParsed(self, event):
            pass

    m = InMemoryMetrics(namespace="copilot")
    svc = Svc(NoopPublisher(), InMemoryDocumentStore(), metrics=m)
    svc.handle_envelope(JSONParsed(message_doc_id="m1").to_envelope())
    emitted = {n for n in (set(m.counters) | set(m.gauges)
                           | set(m.histograms))
               if n.startswith("pipeline_")}
    declared_hists = {n for n, (typ, _l, _h) in PIPELINE_METRICS.items()
                      if typ == "histogram"}
    assert emitted == declared_hists, (
        f"registry drift: only-in-code {emitted - declared_hists}, "
        f"only-in-registry {declared_hists - emitted}")
    for name in declared_hists:
        assert name in m.histograms, name
        assert m.histograms[name], name


def test_pipeline_alert_functions_match_series_types():
    """rate()/increase() need counters or histogram components;
    deriv()/delta() need gauges — the dead-alert bug class, applied to
    the copilot_pipeline_* pack."""
    emitted = _pipeline_series()
    fn_re = re.compile(r"\b(rate|irate|increase|deriv|delta|idelta)\s*"
                       r"\(\s*(copilot_pipeline_[a-z0-9_]+)")
    seen = 0
    for f in _alert_files():
        doc = yaml.safe_load(f.read_text())
        for group in doc["groups"]:
            for rule in group["rules"]:
                for fn, name in fn_re.findall(rule["expr"]):
                    seen += 1
                    base = re.sub(r"_(bucket|sum|count)$", "", name)
                    typ = emitted.get(base)
                    if fn in ("rate", "irate", "increase"):
                        assert typ in ("counter", "histogram"), (
                            f.name, rule["alert"], fn, name, typ)
                        if typ == "histogram":
                            assert name != base, (
                                f.name, rule["alert"], name)
                    else:
                        assert typ == "gauge", (f.name, rule["alert"],
                                                fn, name, typ)
    assert seen, "no alert references the pipeline-trace series"


def test_gateway_metrics_exposes_pipeline_span_counters():
    """The span-ledger counters are refreshed from the global collector
    on every scrape (services/bootstrap.py), so the
    PipelineTraceSpansDropped alert never watches an absent series."""
    from copilot_for_consensus_tpu.services.bootstrap import serve_pipeline

    server = serve_pipeline().start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics").read().decode()
        assert "copilot_pipeline_spans_open_total" in body
        assert "copilot_pipeline_spans_dropped_total" in body
        assert ("# TYPE copilot_pipeline_spans_open_total counter"
                in body)
    finally:
        server.stop()


def test_gateway_metrics_exposes_bus_gauges():
    from copilot_for_consensus_tpu.services.bootstrap import serve_pipeline

    server = serve_pipeline().start()
    try:
        # Park a message on a routing key nobody consumes → depth shows.
        server.pipeline.broker.publish(
            {"event_type": "report.delivery.failed"},
            "report.delivery.failed")
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics").read().decode()
        assert "copilot_bus_queue_depth" in body
        assert 'queue="report.delivery.failed"' in body
        # Registry ⇄ exposition honesty (the PR-5 equality pattern):
        # every BUS_METRICS family must be present on a live scrape —
        # gauges refreshed per scrape, counters declared at zero — so
        # the alert pack's rate()/deriv() expressions never evaluate
        # over an absent series. copilot_bus_dead_letters is the one
        # exception: its <rk>.dlq gauge only exists once something
        # dead-letters (covered by test_gauge_depths semantics).
        emitted = set(re.findall(r"^(copilot_bus_[a-z_]+)\{?",
                                 body, flags=re.M))
        expected = set(BUS_METRICS) - {"copilot_bus_dead_letters"}
        assert expected <= emitted, sorted(expected - emitted)
        assert emitted <= set(BUS_METRICS), sorted(
            emitted - set(BUS_METRICS))
    finally:
        server.stop()


def test_bus_alert_functions_match_series_types():
    """rate()/increase() need counters; deriv()/delta() need gauges —
    the PR-1 dead-alert bug class, applied to the copilot_bus_* pack."""
    counter_fns = {"rate", "irate", "increase"}
    gauge_fns = {"deriv", "delta", "idelta"}
    fn_re = re.compile(r"\b(rate|irate|increase|deriv|delta|idelta)\s*"
                       r"\(\s*(copilot_bus_[a-z_]+)")
    for f in _alert_files():
        doc = yaml.safe_load(f.read_text())
        for group in doc["groups"]:
            for rule in group["rules"]:
                for fn, series in fn_re.findall(rule["expr"]):
                    typ = BUS_METRICS[series][0]
                    if fn in counter_fns:
                        assert typ == "counter", (f.name, rule["alert"],
                                                  fn, series, typ)
                    if fn in gauge_fns:
                        assert typ == "gauge", (f.name, rule["alert"],
                                                fn, series, typ)


def test_profiler_flag_captures_trace(tmp_path):
    """maybe_profile writes an XLA trace; None is a strict no-op."""
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.obs.profile import maybe_profile

    with maybe_profile(None) as p:
        assert p is None
    trace_dir = tmp_path / "traces"
    with maybe_profile(str(trace_dir)) as p:
        assert p is not None
        (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    produced = list(trace_dir.rglob("*"))
    assert any(f.is_file() for f in produced), "no trace files written"


def test_engine_profile_dir_plumbing(tmp_path):
    import jax

    from copilot_for_consensus_tpu.engine.generation import GenerationEngine
    from copilot_for_consensus_tpu.models import decoder
    from copilot_for_consensus_tpu.models.configs import decoder_config

    cfg = decoder_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(cfg, params, num_slots=2, max_len=64,
                           profile_dir=str(tmp_path / "tr"))
    comps = eng.generate([[5, 6, 7]], max_new_tokens=4)
    assert comps[0].tokens
    assert any(f.is_file() for f in (tmp_path / "tr").rglob("*"))


def test_resource_gauges_on_metrics_exposition():
    """The resource_limits alert group fires on series every service's
    /metrics must actually expose (obs/resources.py gauges)."""
    from copilot_for_consensus_tpu.obs.metrics import InMemoryMetrics
    from copilot_for_consensus_tpu.obs.resources import resource_gauges

    m = InMemoryMetrics(namespace="copilot")
    resource_gauges(m)
    body = m.render_prometheus()
    for series in ("copilot_process_resident_bytes",
                   "copilot_process_memory_limit_bytes",
                   "copilot_process_cpu_seconds_total",
                   "copilot_process_open_fds",
                   "copilot_process_start_time_seconds",
                   "copilot_disk_free_bytes", "copilot_disk_total_bytes"):
        assert series in body, series
    # live values, not placeholders: this process HAS memory and fds
    import re as _re

    rss = float(_re.search(
        r"^copilot_process_resident_bytes (\S+)", body, _re.M).group(1))
    fds = float(_re.search(
        r"^copilot_process_open_fds (\S+)", body, _re.M).group(1))
    assert rss > 1e6 and fds >= 3
    # the ratio the memory alerts divide must be computable and sane
    limit = float(_re.search(
        r"^copilot_process_memory_limit_bytes (\S+)", body,
        _re.M).group(1))
    assert limit > rss


# -- cross-process telemetry plane (obs/ship.py, ISSUE 20) ---------------


def test_reserved_labels_collide_loudly_at_registration():
    """proc/role are stamped by the TelemetryAggregator on every merged
    series — a registry that declares them itself would silently alias
    across processes, so check_registry_labels refuses it."""
    from copilot_for_consensus_tpu.obs.metrics import (
        RESERVED_LABELS,
        check_registry_labels,
    )

    for reserved in RESERVED_LABELS:
        bad = {"copilot_x_total": ("counter", (reserved,), "h")}
        with pytest.raises(ValueError, match=reserved):
            check_registry_labels(bad, owner="test")
    # every shipped registry in the repo passes (the import-time call
    # in each module already enforces this; assert it stays true)
    for owner, registry in (
            ("ENGINE_METRICS", ENGINE_METRICS),
            ("BUS_METRICS", BUS_METRICS),
            ("PIPELINE_METRICS", PIPELINE_METRICS),
            ("LIFECYCLE_METRICS", LIFECYCLE_METRICS),
            ("VECTORSTORE_METRICS", VECTORSTORE_METRICS),
            ("SHIP_METRICS", SHIP_METRICS)):
        check_registry_labels(registry, owner=owner)


def test_merged_exposition_has_no_cross_process_type_conflicts():
    """Two processes shipping the SAME series as DIFFERENT types would
    render two contradictory # TYPE lines in the merged scrape — the
    aggregator must refuse; same-typed series from N procs merge into
    one family with proc/role labels."""
    from copilot_for_consensus_tpu.obs.metrics import InMemoryMetrics
    from copilot_for_consensus_tpu.obs.ship import TelemetryAggregator

    agg = TelemetryAggregator()
    m1 = InMemoryMetrics(namespace="copilot")
    m1.increment("jobs_total", 3.0, {"q": "a"})
    m2 = InMemoryMetrics(namespace="copilot")
    m2.increment("jobs_total", 2.0, {"q": "a"})
    agg.merge_registry(m1, proc="p1", role="engine")
    agg.merge_registry(m2, proc="p2", role="engine")
    body = agg.render_prometheus()
    assert body.count("# TYPE copilot_jobs_total counter") == 1
    assert 'copilot_jobs_total{proc="p1",q="a",role="engine"} 3' in body
    assert 'copilot_jobs_total{proc="p2",q="a",role="engine"} 2' in body
    # same series shipped as a gauge by a third process: refused loudly
    m3 = InMemoryMetrics(namespace="copilot")
    m3.gauge("jobs_total", 1.0, {"q": "a"})
    with pytest.raises(ValueError, match="type conflict"):
        agg.merge_registry(m3, proc="p3", role="engine")


def test_gateway_metrics_exposes_resource_gauges():
    from copilot_for_consensus_tpu.services.bootstrap import serve_pipeline

    server = serve_pipeline().start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics").read().decode()
        assert "copilot_process_resident_bytes" in body
        assert "copilot_disk_free_bytes" in body
    finally:
        server.stop()
