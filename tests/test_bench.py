# bench.py host-side plumbing: preset registry, artifact column
# contracts, and the outcome rule — a gate preset's verdict flag IS the
# run's outcome, so ok:false can never ride exit code 0. (What bench.py
# does without a chip is in tests/test_chip_bringup.py.)
import json

import pytest

import bench


@pytest.mark.parametrize("verdict,ok,rc", [(True, True, None),
                                            (False, False, 1)])
def test_gate_preset_verdict_is_the_exit_code(monkeypatch, capsys,
                                              verdict, ok, rc):
    """multichip_serving printed multichip_ok:false under ok:true and
    exit 0 until PR 21; every PRESET_GATES flag now decides both."""
    assert set(bench.PRESET_GATES) <= set(bench.PRESETS)
    monkeypatch.setenv("BENCH_PRESET", "multichip_serving")
    monkeypatch.setenv("BENCH_PREFLIGHT", "0")
    monkeypatch.setattr(bench, "headline", lambda: {
        "metric": "stub", "multichip_ok": verdict})
    if rc is None:
        bench.main()
    else:
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code == rc
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] is ok
    # the parent of this preset never holds a device
    assert out["platform"] == "none" and out["device_count"] == 0


def test_spec_decode_preset_registered():
    assert "spec_decode" in bench.PRESETS
    assert bench.PRESETS["spec_decode"]["BENCH_SPEC_DECODE"] == "1"
    # the shardcheck preflight must trace the engine whose _verify
    # entrypoint the preset exercises
    assert "copilot_for_consensus_tpu.engine.generation" in \
        bench.PRESET_CONTRACT_MODULES["spec_decode"]


def test_decode_heavy_preset_registered():
    """The telemetry-overhead gate's preset: decode-dominated shape,
    contract-traced like every other preset."""
    assert "decode_heavy" in bench.PRESETS
    p = bench.PRESETS["decode_heavy"]
    # decode-dominated: generated tokens dominate prompt tokens
    assert int(p["BENCH_NEW_TOKENS"]) >= 4 * int(p["BENCH_PROMPT_LEN"])
    assert "copilot_for_consensus_tpu.engine.generation" in \
        bench.PRESET_CONTRACT_MODULES["decode_heavy"]


def test_preset_artifact_columns_unchanged():
    """The artifact column sets are a cross-round contract: the
    telemetry tentpole must not rename the columns earlier rounds'
    presets established, and its own columns are now part of it."""
    ps0 = {"lookups": 0, "hits": 0, "prefill_tokens": 0,
           "prefill_tokens_saved": 0}
    ps1 = {"lookups": 10, "hits": 9, "prefill_tokens": 1280,
           "prefill_tokens_saved": 3840}
    cols = bench.prefix_columns(ps0, ps1)
    assert set(cols) == {"prefix_hit_rate", "prefill_tokens_saved",
                         "prefill_tokens"}
    assert cols["prefix_hit_rate"] == 0.9
    assert cols["prefill_tokens_saved"] == 3840

    ss0 = {"lookups": 0, "hits": 0, "accepted_tokens": 0,
           "verify_rows": 0, "weight_row_tokens": 0,
           "weight_row_passes": 0}
    ss1 = {"lookups": 8, "hits": 4, "accepted_tokens": 12,
           "verify_rows": 4, "weight_row_tokens": 40,
           "weight_row_passes": 10}
    cols = bench.spec_columns(ss0, ss1)
    assert set(cols) == {"draft_hit_rate", "mean_accepted_per_step",
                         "tokens_per_weight_pass"}
    assert cols["draft_hit_rate"] == 0.5
    assert cols["tokens_per_weight_pass"] == 4.0
    # zero-delta denominators must not divide by zero
    assert bench.prefix_columns(ps0, ps0)["prefix_hit_rate"] == 0.0
    assert bench.spec_columns(ss0, ss0)["tokens_per_weight_pass"] == 0.0


def test_paged_capacity_preset_registered():
    """ISSUE 14: the paged-KV capacity gate — paged engine ON, pool
    sized at the contiguous 128-slot HBM budget (1024 x 64-token
    blocks == 128 slots x max_len 512), slot count ABOVE the 128
    ceiling, and a shared prefix so the zero-copy hit path exercises.
    The shardcheck preflight must trace the paged dispatch family."""
    assert "paged_capacity" in bench.PRESETS
    p = bench.PRESETS["paged_capacity"]
    assert p["BENCH_PAGED"] == "1"
    assert int(p["BENCH_SLOTS"]) > 128
    assert int(p["BENCH_KV_POOL_BLOCKS"]) * 64 \
        == 128 * int(p["BENCH_MAX_LEN"])
    assert int(p["BENCH_SHARED_PREFIX"]) > 0
    assert int(p["BENCH_PREFIX_BLOCKS"]) > 0
    assert "copilot_for_consensus_tpu.engine.generation" in \
        bench.PRESET_CONTRACT_MODULES["paged_capacity"]


def test_paged_columns_contract():
    """paged_capacity's artifact columns are a cross-round contract:
    max_concurrent_streams / kv_pool_fragmentation /
    zero_copy_hit_rate (timed-run delta, zero-delta safe)."""
    kv0 = {"paged_admits": 4, "zero_copy_admits": 0,
           "peak_active": 3, "fragmentation_ratio": 0.5}
    kv1 = {"paged_admits": 14, "zero_copy_admits": 8,
           "peak_active": 170, "fragmentation_ratio": 0.12}
    cols = bench.paged_columns(kv0, kv1)
    assert set(cols) == {"max_concurrent_streams",
                         "kv_pool_fragmentation", "zero_copy_hit_rate"}
    assert cols["max_concurrent_streams"] == 170
    assert cols["kv_pool_fragmentation"] == 0.12
    assert cols["zero_copy_hit_rate"] == 0.8
    assert bench.paged_columns(kv0, kv0)["zero_copy_hit_rate"] == 0.0


def test_mixed_traffic_preset_registered():
    """The scheduler gate's preset (ISSUE 6): adversarial mix with at
    least two tenants, contract-traced through BOTH the generation
    engine and the scheduler module (the chunked-prefill dispatch)."""
    assert "mixed_traffic" in bench.PRESETS
    p = bench.PRESETS["mixed_traffic"]
    assert int(p["BENCH_MIX_CHAT"]) > 0 and int(p["BENCH_MIX_LONG"]) > 0
    # adversarial: the long prompts must actually be long enough to
    # need chunking at the preset's chunk size
    assert int(p["BENCH_MIX_LONG_LEN"]) > int(p["BENCH_CHUNK_TOKENS"])
    mods = bench.PRESET_CONTRACT_MODULES["mixed_traffic"]
    assert "copilot_for_consensus_tpu.engine.generation" in mods
    assert "copilot_for_consensus_tpu.engine.scheduler" in mods


def test_sched_columns_contract():
    """The mixed_traffic artifact columns are a cross-round contract:
    ttft_p99_s / itl_p95_s / shed_rate / fairness_jain_index."""
    summary = {"ttft_p99_s": 1.25, "itl_p95_s": 0.08,
               "ttft_p50_s": 0.2}
    stats = {"shed_rate": 0.125, "fairness_jain_index": 0.96,
             "chunk_dispatches": 7}
    cols = bench.sched_columns(summary, stats)
    assert set(cols) == {"ttft_p99_s", "itl_p95_s", "shed_rate",
                         "fairness_jain_index"}
    assert cols["ttft_p99_s"] == 1.25
    assert cols["shed_rate"] == 0.125
    assert cols["fairness_jain_index"] == 0.96
    # empty stats degrade to the no-scheduler defaults, not KeyErrors
    empty = bench.sched_columns({}, {})
    assert empty["shed_rate"] == 0.0
    assert empty["fairness_jain_index"] == 1.0


def test_chaos_preset_registered():
    """The resilience gate's preset (ISSUE 7): spec decode ON (the
    persistent verify fault needs a verify dispatch to hit), compute
    dtype pinned to float32 (the replay bit-identity requirement:
    prefill and decode logits only agree exactly at f32), a hang
    longer than the watchdog deadline, contract-traced through the
    generation engine."""
    assert "chaos" in bench.PRESETS
    p = bench.PRESETS["chaos"]
    assert p["BENCH_SPEC_DECODE"] == "1"
    assert p["BENCH_CHAOS_DTYPE"] == "float32"
    assert float(p["BENCH_CHAOS_HANG_S"]) > \
        float(p["BENCH_CHAOS_DECODE_DEADLINE_S"])
    assert int(p["BENCH_CHAOS_CHAT"]) > 0 and \
        int(p["BENCH_CHAOS_LONG"]) > 0
    assert "copilot_for_consensus_tpu.engine.generation" in \
        bench.PRESET_CONTRACT_MODULES["chaos"]


def test_chaos_columns_contract():
    """The chaos artifact columns are a cross-round contract:
    recovered / replayed / failed / breaker_trips / watchdog_trips
    (plus the chaos_ok verdict assembled in chaos_headline)."""
    rec = {"recovered": 5, "replayed": 7, "failed": 1,
           "breaker_trips": 2, "watchdog_trips": 1,
           "containments": 9, "suspect_failures": 3}
    cols = bench.chaos_columns(rec)
    assert set(cols) == {"recovered", "replayed", "failed",
                         "breaker_trips", "watchdog_trips"}
    assert cols["recovered"] == 5 and cols["failed"] == 1
    # empty stats degrade to zeros, not KeyErrors
    empty = bench.chaos_columns({})
    assert empty == {"recovered": 0, "replayed": 0, "failed": 0,
                     "breaker_trips": 0, "watchdog_trips": 0}


def test_pipeline_chaos_preset_registered():
    """The pipeline fault gate's preset (ISSUE 8): a host-only storm —
    no jitted entrypoints for the shardcheck preflight to trace — with
    a watermark strictly inside the scaled warn SLO (pacing must hold
    depth UNDER the SLO with headroom, not ride its edge), poison
    envelopes to quarantine, and an overload drag so the OFF arm
    reproduces the SCALE_BROKER flood deterministically."""
    assert "pipeline_chaos" in bench.PRESETS
    p = bench.PRESETS["pipeline_chaos"]
    assert int(p["BENCH_PIPE_MESSAGES"]) > 0
    assert int(p["BENCH_PIPE_POISON"]) > 0
    assert float(p["BENCH_PIPE_DRAG_S"]) > 0
    slo = int(p["BENCH_PIPE_WARN_SLO"])
    assert 0 < slo // 2 < slo          # the watermark the harness uses
    # host-only: the preflight must SKIP, not trace the default engine
    # set a pipeline storm never dispatches to
    assert bench.PRESET_CONTRACT_MODULES["pipeline_chaos"] == []


def test_pipeline_chaos_columns_contract():
    """The pipeline_chaos artifact columns are a cross-round contract:
    lost / duplicated / quarantined / replayed_publishes plus the
    redelivery, sweep-recovery and two-arm depth evidence (the
    pipeline_chaos_ok verdict is assembled in
    pipeline_chaos_headline)."""
    audit = {"lost": 0, "duplicated": 0, "quarantined": 5,
             "replayed_publishes": 104, "redelivered": 3,
             "recovered_by_sweep": 2, "max_depth_backpressure_on": 8,
             "max_depth_backpressure_off": 88, "final_depth_max": 0,
             "stage_p95_s": {"chunking": 0.4},
             "queue_wait_p95_s": {"chunking": 1.2},
             "bottleneck_stage": "chunking", "orphan_spans": 0,
             "journal_replayed": 7, "shutdown_redeliveries": 0,
             "telemetry_recovered_ok": True, "spool_rows": 30,
             "spool_lost": 0, "extra_key_ignored": 1}
    cols = bench.pipeline_chaos_columns(audit)
    assert set(cols) == {"lost", "duplicated", "quarantined",
                         "replayed_publishes", "redelivered",
                         "recovered_by_sweep",
                         "max_depth_backpressure_on",
                         "max_depth_backpressure_off",
                         "final_depth_max",
                         # distributed-tracing columns (obs/trace.py +
                         # tools/tracepath.py, PR-10 tentpole)
                         "stage_p95_s", "queue_wait_p95_s",
                         "bottleneck_stage", "orphan_spans",
                         # process-lifecycle columns (engine/journal
                         # + services/lifecycle, ISSUE 12): the kill
                         # phase's warm-restart replays and the
                         # graceful-drain arm's shutdown-caused
                         # redeliveries (zero is the gate)
                         "journal_replayed", "shutdown_redeliveries",
                         # cross-process telemetry columns (obs/ship,
                         # ISSUE 20): the SIGKILLed child's committed
                         # spool survived and merged with zero orphans
                         "telemetry_recovered_ok", "spool_rows",
                         "spool_lost"}
    assert cols["quarantined"] == 5
    assert cols["replayed_publishes"] == 104
    assert cols["max_depth_backpressure_off"] == 88
    assert cols["bottleneck_stage"] == "chunking"
    assert cols["stage_p95_s"] == {"chunking": 0.4}
    assert cols["orphan_spans"] == 0
    assert cols["journal_replayed"] == 7
    assert cols["shutdown_redeliveries"] == 0
    assert cols["telemetry_recovered_ok"] is True
    assert cols["spool_rows"] == 30 and cols["spool_lost"] == 0
    # empty audit degrades to zeros/empties, not KeyErrors — and the
    # telemetry verdict degrades to False / -1 lost (unknown), never a
    # vacuous pass
    empty = bench.pipeline_chaos_columns({})
    assert empty["bottleneck_stage"] == ""
    assert empty["stage_p95_s"] == {}
    assert empty["queue_wait_p95_s"] == {}
    assert empty["telemetry_recovered_ok"] is False
    assert empty["spool_lost"] == -1
    assert all(v == 0 for k, v in empty.items()
               if k not in ("bottleneck_stage", "stage_p95_s",
                            "queue_wait_p95_s", "spool_lost"))


def test_telemetry_columns_contract():
    """Flight-recorder columns come from the engine's own telemetry;
    a telemetry-disabled engine (BENCH_TELEMETRY=0 overhead arm)
    yields NO columns rather than zeros that would look like a
    regression."""
    from copilot_for_consensus_tpu.engine.telemetry import (
        EngineTelemetry,
    )

    class FakeEngine:
        telemetry = EngineTelemetry(engine="generation", num_slots=4)

    tele = FakeEngine.telemetry
    for rid in range(3):
        tele.on_submit(rid, prompt_len=8)
        tele.on_admit(rid, wave_start=0.0)
    tele.record_step("decode", 0.01, rows=3, batch=4, tokens=12,
                     padded_tokens=32)
    for rid in range(3):
        tele.on_retire(rid, new_tokens=4, finish_reason="length")
    cols = bench.telemetry_columns(FakeEngine(), last_n=3)
    assert set(cols) == {"ttft_p50_s", "ttft_p95_s", "ttft_p99_s",
                         "itl_mean_s", "itl_p95_s", "mean_occupancy"}
    assert cols["ttft_p50_s"] > 0
    assert cols["mean_occupancy"] == 0.75

    class Disabled:
        telemetry = None

    assert bench.telemetry_columns(Disabled()) == {}


def test_pipeline_chaos_preset_enables_worker_pools():
    """ISSUE 11: the chaos gate must prove its delivery contracts
    UNDER stage scale-out — competing consumer pools on the host-bound
    stages, not the old one-consumer-per-service wiring."""
    assert int(bench.PRESETS["pipeline_chaos"]["BENCH_PIPE_WORKERS"]) >= 2


def test_pipeline_chaos_preset_has_kill_and_drain_knobs():
    """ISSUE 12: the chaos gate grew a process-kill phase (journaled
    engine storm SIGKILLed in a child process, warm-restarted from the
    journal) and a graceful-drain arm — both must stay in the preset."""
    p = bench.PRESETS["pipeline_chaos"]
    assert int(p["BENCH_KILL_REQUESTS"]) > 0
    assert int(p["BENCH_KILL_STEP"]) > 0
    assert int(p["BENCH_KILL_NEW_TOKENS"]) > 0
    assert int(p["BENCH_PIPE_DRAIN_MESSAGES"]) > 0
    assert int(p["BENCH_PIPE_DRAIN_ARCHIVES"]) > 0


def _scale_bench():
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(bench.__file__).parent
                           / "scripts"))
    import scale_bench
    return scale_bench


def test_scale_bench_workers_spec_parsing():
    sb = _scale_bench()
    assert sb.parse_workers_spec("") == {}
    assert sb.parse_workers_spec("1") == {}      # 1 = pre-scale-out
    assert sb.parse_workers_spec("4") == {
        "parsing": 4, "chunking": 4, "embedding": 4}
    assert sb.parse_workers_spec("parsing=2,chunking=6") == {
        "parsing": 2, "chunking": 6}
    # prefetch rides the services config next to the pools
    cfg = sb.services_config({"chunking": 3}, prefetch=32)
    assert cfg["chunking"] == {"workers": 3, "prefetch": 32}
    assert cfg["parsing"]["prefetch"] == 32


def test_scale_bench_artifact_columns_contract():
    """The SCALE_BROKER.json columns are a cross-round contract; the
    scale-out round adds speedup_vs_baseline (vs the 59.6 msg/s
    single-consumer baseline), per-stage worker counts and the
    prefetch knob, without renaming the established columns."""
    sb = _scale_bench()
    out = sb.broker_artifact(
        messages=100_000, gen_s=5.0, run_s=167.8, events=337_600,
        max_depth={"json.parsed": 900}, workers={"chunking": 6},
        prefetch=64, failure_audit={"events": 0}, stats={"reports": 1},
        ok=True)
    assert {"stage", "messages", "generate_s", "pipeline_s",
            "messages_per_s", "baseline_messages_per_s",
            "speedup_vs_baseline", "workers", "prefetch",
            "broker_events", "broker_events_per_s", "max_queue_depth",
            "queue_depth_slo", "failure_audit", "stats",
            "ok"} <= set(out)
    assert out["messages_per_s"] == 595.9
    assert out["speedup_vs_baseline"] == 10.0
    assert out["baseline_messages_per_s"] == 59.6
    # every scalable stage reports a worker count, configured or not
    assert out["workers"] == {"parsing": 1, "chunking": 6,
                              "embedding": 1}
    assert out["prefetch"] == 64
    assert out["queue_depth_slo"]["worst"] == 900
    # unconfigured knobs degrade to the pre-scale-out shape
    base = sb.broker_artifact(
        messages=10, gen_s=0.0, run_s=1.0, events=30, max_depth={},
        workers={}, prefetch=0, failure_audit={}, stats={}, ok=False)
    assert base["workers"] == {"parsing": 1, "chunking": 1,
                               "embedding": 1}
    assert base["prefetch"] == 16
    assert base["queue_depth_slo"]["worst"] == 0


@pytest.mark.slow
def test_scale_bench_smoke_arm_runs_green():
    """The CI-runnable small-N arm: broker mode, pools + batching on,
    toy corpus — asserts the artifact contract end-to-end without
    touching SCALE_BROKER.json."""
    import json
    import pathlib
    import subprocess
    import sys

    pytest.importorskip("zmq")
    root = pathlib.Path(bench.__file__).parent
    out = subprocess.run(
        [sys.executable, str(root / "scripts" / "scale_bench.py"),
         "--smoke", "--messages", "240"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    artifact = json.loads(out.stdout.strip().splitlines()[-1])
    assert artifact["ok"] is True
    assert artifact["workers"]["chunking"] >= 2
    assert artifact["speedup_vs_baseline"] > 0
    assert artifact["stats"]["messages"] == 240


def test_multichip_serving_preset_registered():
    """ISSUE 15: the multi-chip sharded-paged serving gate — paged
    pool sized so every dp degree in the chip sweep gets equal shards
    with per-slot headroom, and the preflight traces the MESH-sharded
    dispatch family plus the serving mesh/rules contracts."""
    assert "multichip_serving" in bench.PRESETS
    p = bench.PRESETS["multichip_serving"]
    chips = [int(c) for c in p["BENCH_MC_CHIPS"].split(",")]
    assert chips[0] == 1 and chips[-1] == 8
    tp = int(p["BENCH_MC_TP"])
    blocks = int(p["BENCH_KV_POOL_BLOCKS"])
    slots = int(p["BENCH_SLOTS"])
    max_blocks = int(p["BENCH_MAX_LEN"]) // int(p["BENCH_PREFILL_CHUNK"])
    for c in chips:
        dp = c // tp if c > tp else 1
        assert blocks % dp == 0
        assert slots % dp == 0
        assert blocks // dp >= max_blocks + 1
    assert float(p["BENCH_MC_ITL_TOL"]) >= 1.0
    mods = bench.PRESET_CONTRACT_MODULES["multichip_serving"]
    assert "copilot_for_consensus_tpu.engine.generation" in mods
    assert "copilot_for_consensus_tpu.parallel.mesh" in mods
    assert "copilot_for_consensus_tpu.parallel.sharding" in mods


def test_multichip_columns_contract():
    """multichip_serving's artifact columns are a cross-round
    contract: chips / tok_s_per_chip / scaling_efficiency /
    ttft_p99_s / handoff_ms plus the two-arm ITL comparison."""
    scaling = {1: {"tok_s": 100.0, "ttft_p99_s": 0.01},
               2: {"tok_s": 180.0, "ttft_p99_s": 0.012},
               4: {"tok_s": 320.0, "ttft_p99_s": 0.015},
               8: {"tok_s": 560.0, "ttft_p99_s": 0.02}}
    disagg = {"itl_p95_coloc_s": 0.3, "itl_p95_disagg_s": 0.05,
              "handoff_ms": 12.5, "handoffs": 9}
    cols = bench.multichip_columns(scaling, disagg)
    assert cols["chips"] == 8
    assert cols["tok_s_per_chip"] == 70.0
    assert cols["scaling_efficiency"] == 0.7
    assert cols["ttft_p99_s"] == 0.02
    assert cols["handoff_ms"] == 12.5
    assert cols["itl_p95_disagg_s"] == 0.05
    assert set(cols["scaling"]) == {"1", "2", "4", "8"}
    # no spool merge: the spool columns degrade to unknown, never to a
    # vacuous pass
    assert cols["slo_ok"] is None
    assert cols["spool_rows"] == 0 and cols["spool_lost"] == -1
    assert all(row["ttft_p99_spool_s"] is None
               for row in cols["scaling"].values())
    # degenerate single-chip sweep stays well-formed
    one = bench.multichip_columns({1: {"tok_s": 0.0}}, {})
    assert one["scaling_efficiency"] == 0.0


def test_multichip_columns_spool_merge():
    """ISSUE 20: the parent merges every child's telemetry spool and
    publishes spool-derived TTFT per chip count, fleet ITL p95, row
    accounting and the declarative SLO verdict next to the measured
    columns."""
    scaling = {1: {"tok_s": 100.0, "ttft_p99_s": 0.01},
               2: {"tok_s": 180.0, "ttft_p99_s": 0.012}}
    spool = {"ttft_p99_by_chips": {"1": 0.011, "2": 0.013},
             "itl_p95_s": 0.04, "spool_rows": 21, "spool_lost": 0,
             "slo_ok": True,
             "slo": {"interactive-ttft-p99": True}}
    cols = bench.multichip_columns(scaling, {}, spool)
    assert cols["scaling"]["1"]["ttft_p99_spool_s"] == 0.011
    assert cols["scaling"]["2"]["ttft_p99_spool_s"] == 0.013
    assert cols["itl_p95_s"] == 0.04
    assert cols["spool_rows"] == 21 and cols["spool_lost"] == 0
    assert cols["slo_ok"] is True
    assert cols["slo"] == {"interactive-ttft-p99": True}


def test_kv_kernel_route_preset_keys():
    """ISSUE 16: the paged presets carry the dispatch-route knob —
    paged_capacity auto-selects its headline arm and pins a Pallas
    kernel-route arm next to it; multichip_serving auto-selects its
    scale children (the parent adds the pinned kernel child itself)."""
    p = bench.PRESETS["paged_capacity"]
    assert p["BENCH_KV_KERNEL"] == "auto"
    assert p["BENCH_KV_KERNEL_ARM"] == "1"
    assert bench.PRESETS["multichip_serving"]["BENCH_KV_KERNEL"] \
        == "auto"


def test_kernel_route_columns_contract():
    """The kernel-route arm's artifact columns are a cross-round
    contract: the RESOLVED route (kernel proves the Pallas path
    compiled), its tok/s, and the zero-safe ratio against the
    headline arm."""
    cols = bench.kernel_route_columns("kernel", 100.0, 117.0)
    assert set(cols) == {"kv_route", "kernel_tok_s",
                         "kernel_tok_s_delta"}
    assert cols["kv_route"] == "kernel"
    assert cols["kernel_tok_s"] == 117.0
    assert cols["kernel_tok_s_delta"] == 1.17
    # a failed headline arm must not divide by zero
    assert bench.kernel_route_columns("kernel", 0.0,
                                      50.0)["kernel_tok_s_delta"] == 0.0


def test_unknown_kv_kernel_fails_loudly():
    """ISSUE 16: a typo'd BENCH_KV_KERNEL must fail rc-2/ok:false the
    same way a typo'd BENCH_PRESET does — silently running (and
    mislabeling) the default route would poison the next round's
    artifact comparison. The check runs before the jax import, so the
    subprocess exits fast."""
    import json
    import os
    import subprocess
    import sys

    env = {**os.environ, "BENCH_KV_KERNEL": "pallass",
           "BENCH_PRESET": "", "BENCH_MC_CHILD": ""}
    out = subprocess.run(
        [sys.executable, bench.__file__],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stdout + out.stderr
    artifact = json.loads(out.stdout.strip().splitlines()[-1])
    assert artifact["ok"] is False
    assert "BENCH_KV_KERNEL" in artifact["reason"]
    assert "pallass" in artifact["reason"]


def test_ann_retrieval_preset_registered():
    """ISSUE 19: the ANN retrieval gate — million-vector default
    corpus, auto-sized index (nlist=0), a probe budget that keeps
    lists_scanned_frac well under the 0.15 ceiling, and preflights
    that trace + compile the vectorstore contract family (the fused
    search dispatch carries an hlo peak/collective budget)."""
    assert "ann_retrieval" in bench.PRESETS
    p = bench.PRESETS["ann_retrieval"]
    assert int(p["BENCH_ANN_N"]) == 1_000_000
    assert int(p["BENCH_ANN_TOPK"]) == 10
    assert int(p["BENCH_ANN_NLIST"]) == 0        # auto: ~sqrt(n)
    # at auto nlist for 1M (1024 lists), the preset's nprobe must sit
    # under the 15% scanned-lists ceiling the artifact gates on
    assert int(p["BENCH_ANN_NPROBE"]) / 1024 <= 0.15
    mods = bench.PRESET_CONTRACT_MODULES["ann_retrieval"]
    assert "copilot_for_consensus_tpu.vectorstore.tpu" in mods
    # the ivf search dispatch declares compiled-artifact budgets, so
    # the preset must run the hlocheck preflight, not just shardcheck
    assert "ann_retrieval" in bench.HLO_PREFLIGHT_PRESETS
    from copilot_for_consensus_tpu.analysis.contracts import (
        HLO_CONTRACT_MODULES,
    )
    assert "copilot_for_consensus_tpu.vectorstore.tpu" in (
        HLO_CONTRACT_MODULES)


def test_ann_columns_contract():
    """The ann_retrieval artifact columns are a cross-round contract:
    recall/QPS/latency per route plus the scanned-lists fraction, and
    the ann_ok gate = recall >= 0.95 AND frac <= 0.15 AND ivf faster."""
    flat = {"qps": 120.0, "p50_ms": 8.0, "p95_ms": 11.0}
    ivf = {"qps": 900.0, "p50_ms": 1.1, "p95_ms": 1.9,
           "lists_scanned_frac": 0.0156, "spill_fraction": 0.01,
           "nlist": 1024, "nprobe": 16}
    cols = bench.ann_columns(1_000_000, 0.973, flat, ivf)
    assert set(cols) >= {"corpus_size", "recall_at_10", "flat_qps",
                         "ivf_qps", "flat_query_p50_ms",
                         "flat_query_p95_ms", "ivf_query_p50_ms",
                         "ivf_query_p95_ms", "lists_scanned_frac",
                         "spill_fraction", "nlist", "nprobe", "ann_ok"}
    assert cols["recall_at_10"] == 0.973
    assert cols["ivf_qps"] == 900.0
    assert cols["lists_scanned_frac"] == 0.0156
    assert cols["ann_ok"] is True
    # each gate leg flips it independently
    assert not bench.ann_columns(10, 0.90, flat, ivf)["ann_ok"]
    assert not bench.ann_columns(
        10, 0.99, flat, {**ivf, "lists_scanned_frac": 0.5})["ann_ok"]
    assert not bench.ann_columns(
        10, 0.99, flat, {**ivf, "qps": 50.0})["ann_ok"]
    # degenerate empty dicts stay well-formed (failed arm)
    empty = bench.ann_columns(0, 0.0, {}, {})
    assert empty["ann_ok"] is False and empty["nlist"] == 0
