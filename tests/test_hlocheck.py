# The post-lowering static-analysis lane (hlocheck) must stay green
# AND keep catching what it claims to catch: every rule is proven
# against a fixture corpus (one true positive + one clean negative),
# the worker/CLI/baseline routes are exercised, the bench preflight
# gates on it with the same rc-2/ok:false artifact contract as the
# shard and dura gates, and the committed HLO_BUDGETS.json snapshot
# stays internally consistent. Same spirit as test_shardcheck.py for
# the trace-level semantic group. The engine-mutation tripwires live
# in test_static_analysis.py (donation drop, bucket-table widening)
# and test_engine_kernel_route.py (re-introduced pool gather).
import json
import pathlib
import subprocess
import sys

import pytest

from copilot_for_consensus_tpu.analysis import (
    RULES as CLI_RULES,
    SEMANTIC_GROUPS,
    main as jaxlint_main,
)
from copilot_for_consensus_tpu.analysis import hlocheck
from copilot_for_consensus_tpu.analysis.contracts import (
    HLO_CONTRACT_MODULES,
)

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "tests" / "fixtures" / "hlocheck"


def _findings(fixture: str, rule: str):
    findings, _, skips = hlocheck.check_modules([str(FIXTURES / fixture)])
    assert skips == [], skips       # conftest provides 8 virtual devices
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------------
# fixture corpus: one true positive + one clean negative per rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture,rule,bad_marker,good_marker", [
    ("donation_alias.py", "hlo-donation-alias", "bad_alias",
     "good_alias"),
    ("materialize.py", "hlo-materialize", "bad_materialize",
     "good_materialize"),
    ("collective_budget.py", "hlo-collective-budget", "bad_budget",
     "good_budget"),
    ("peak_memory.py", "hlo-peak-memory", "bad_peak", "good_peak"),
    ("program_cache.py", "hlo-program-cache", "bad_cache",
     "good_cache"),
])
def test_rule_true_positive_and_clean_negative(fixture, rule,
                                               bad_marker, good_marker):
    found = _findings(fixture, rule)
    assert any(bad_marker in f.context for f in found), (rule, found)
    assert not any(good_marker in f.context for f in found), (rule, found)


def test_materialize_finding_names_the_tensor():
    found = _findings("materialize.py", "hlo-materialize")
    assert any("2048" in f.message for f in found), found


def test_collective_finding_names_op_and_counts():
    found = _findings("collective_budget.py", "hlo-collective-budget")
    assert any("'all-reduce'" in f.message and "declares 0" in f.message
               for f in found), found


def test_peak_finding_carries_the_byte_breakdown():
    found = _findings("peak_memory.py", "hlo-peak-memory")
    assert any("argument" in f.message and "temp" in f.message
               for f in found), found


def test_program_cache_duplicate_variants_share_a_digest():
    """good_cache declares 4 variants / 3 programs (width 8 twice):
    passing proves the digest identifies programs, not labels."""
    found = _findings("program_cache.py", "hlo-program-cache")
    assert all("good_cache" not in f.context for f in found), found


def test_broken_module_is_a_contract_finding(tmp_path):
    boom = tmp_path / "boom.py"
    boom.write_text("raise RuntimeError('import bomb')\n")
    findings, _, _ = hlocheck.check_modules([str(boom)])
    assert any(f.rule == "hlo-contract" and "failed to import"
               in f.message for f in findings), findings
    empty = tmp_path / "empty.py"
    empty.write_text("X = 1\n")
    findings, _, _ = hlocheck.check_modules([str(empty)])
    assert any(f.rule == "hlo-contract"
               and "no SHARDCHECK_CONTRACTS" in f.message
               for f in findings), findings


def test_module_without_hlo_specs_is_registry_rot(tmp_path):
    """A contract module whose cases all lost their HloSpec has rotted
    out of the post-lowering pass — full (unfiltered) runs must say so
    instead of silently passing."""
    mod = tmp_path / "nospec.py"
    mod.write_text(
        "from copilot_for_consensus_tpu.analysis.contracts import (\n"
        "    ContractCase, contract)\n\n\n"
        "def no_spec():\n"
        "    return ContractCase(label='x')\n\n\n"
        "SHARDCHECK_CONTRACTS = [contract('no_spec', no_spec)]\n")
    findings, _, _ = hlocheck.check_modules([str(mod)])
    assert any(f.rule == "hlo-contract" and "no HloSpec" in f.message
               for f in findings), findings
    # ...but a labels-narrowed tripwire run must not trip it
    findings, _, _ = hlocheck.check_modules(
        [str(mod)], labels={"absent"})
    assert findings == [], findings


# ---------------------------------------------------------------------------
# registry + CLI integration
# ---------------------------------------------------------------------------


def test_hlo_is_a_semantic_group_and_rules_in_sync():
    assert "hlo" in SEMANTIC_GROUPS
    hlo_rules = {r for r, g in CLI_RULES.items() if g == "hlo"}
    assert hlo_rules == set(hlocheck.RULES)


@pytest.mark.slow
def test_cli_hlo_group_subprocess_clean():
    """The worker subprocess route (what CI's hlo matrix arm and bench
    preflight use) comes up with the virtual device platform, lowers +
    compiles the whole registry, and reports clean."""
    proc = subprocess.run(
        [sys.executable, "-m",
         "copilot_for_consensus_tpu.analysis.hlocheck", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert data["findings"] == [] and data["skips"] == []
    assert len(data["checked"]) == len(HLO_CONTRACT_MODULES)
    # the --budgets report rides the same run: every compiled case
    # with a declared budget must sit under it
    assert data["report"]
    for ctx, stats in data["report"].items():
        if stats.get("budget_bytes") is not None:
            assert stats["peak_bytes"] <= stats["budget_bytes"], ctx


def test_worker_baseline_silences_finding(tmp_path, capsys):
    """A justified baseline entry matching an hlo finding silences it
    through the worker's --baseline route (what bench preflight
    passes)."""
    findings, _, _ = hlocheck.check_modules(
        [str(FIXTURES / "peak_memory.py")])
    bad = [f for f in findings if f.rule == "hlo-peak-memory"]
    assert bad
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([
        {"rule": f.rule, "path": f.path, "context": f.context,
         "message": f.message,
         "justification": "fixture: deliberately starved budget"}
        for f in bad]))
    rc = hlocheck.main(["--modules", str(FIXTURES / "peak_memory.py"),
                        "--baseline", str(bl), "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["findings"] == []


def test_fast_run_skips_hlo_without_judging_its_baseline(tmp_path,
                                                         capsys):
    """--fast skips the hlo group the way it skips shard — and a
    skipped group must not judge hlo baseline entries stale."""
    ok = tmp_path / "ok.py"
    ok.write_text("import os\nprint(os.name)\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([
        {"rule": "hlo-peak-memory", "path": "tests/x.py",
         "context": "some-contract", "message": "m",
         "justification": "entry only the full lowering run can judge"}]))
    rc = jaxlint_main(["--fast", "--strict", "--baseline", str(bl),
                       str(ok)])
    out = capsys.readouterr().out
    assert rc == 0 and "stale" not in out, out


# ---------------------------------------------------------------------------
# bench preflight: the rc-2/ok:false artifact contract
# ---------------------------------------------------------------------------


def test_bench_hlo_preflight_blocks_on_violation():
    """pipeline_chaos maps to no jitted entrypoints (shardcheck skips)
    so the pinned fixture reaches the hlo gate directly: the bench
    must exit 2 with the same rc-2/ok:false artifact contract before
    any timed run starts."""
    import os

    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ,
             "BENCH_PREFLIGHT": "1",
             "BENCH_PRESET": "pipeline_chaos",
             "BENCH_HLOCHECK_MODULES":
                 str(FIXTURES / "donation_alias.py")})
    assert proc.returncode == 2, proc.stdout + proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["ok"] is False
    assert "hlocheck preflight failed" in line["reason"]
    assert any("hlo-donation-alias" in f for f in line["findings"])


def test_hlo_preflight_opt_out_and_preset_map(monkeypatch):
    """BENCH_HLOCHECK=0 (and BENCH_PREFLIGHT=0) skip even with
    violating modules pinned; ungated presets resolve to no modules;
    every gated preset intersects the hlo registry non-trivially."""
    sys.path.insert(0, str(ROOT))
    try:
        import bench
    finally:
        sys.path.remove(str(ROOT))
    monkeypatch.setenv("BENCH_HLOCHECK_MODULES",
                       str(FIXTURES / "donation_alias.py"))
    monkeypatch.setenv("BENCH_PREFLIGHT", "0")
    assert bench.hlocheck_preflight() is None
    monkeypatch.setenv("BENCH_PREFLIGHT", "1")
    monkeypatch.setenv("BENCH_HLOCHECK", "0")
    assert bench.hlocheck_preflight() is None
    monkeypatch.delenv("BENCH_HLOCHECK")
    monkeypatch.delenv("BENCH_HLOCHECK_MODULES")
    monkeypatch.setenv("BENCH_PRESET", "rag2k")     # ungated preset
    assert bench.hlocheck_preflight() is None
    assert bench.HLO_PREFLIGHT_PRESETS <= set(bench.PRESETS)
    for preset in bench.HLO_PREFLIGHT_PRESETS:
        mods = [m for m in bench.PRESET_CONTRACT_MODULES[preset]
                if m in HLO_CONTRACT_MODULES]
        assert mods, f"{preset} gates on hlo but maps to no modules"


# ---------------------------------------------------------------------------
# the committed budget snapshot stays honest
# ---------------------------------------------------------------------------


def test_hlo_budgets_snapshot_consistent():
    """docs/artifacts/HLO_BUDGETS.json (regenerated with --budgets)
    must carry every declared budget at/above its recorded peak and
    cover the kernel-route dispatch family the lane exists to pin."""
    data = json.loads(
        (ROOT / "docs" / "artifacts" / "HLO_BUDGETS.json").read_text())
    assert data["device_count"] == 8
    cases = data["cases"]
    assert "generation-engine:decode-paged-kernel" in cases
    assert "generation-engine:decode-paged-mesh-kernel" in cases
    for ctx, stats in cases.items():
        assert stats["peak_bytes"] == (
            stats["argument_bytes"] + stats["output_bytes"]
            + stats["temp_bytes"] - stats["alias_bytes"]), ctx
        assert stats["budget_bytes"] is not None, ctx
        assert stats["peak_bytes"] <= stats["budget_bytes"], ctx
    # the kernel route's whole point: its decode peak stays well under
    # the reference route's materializing decode
    ref = cases["generation-engine:decode-paged"]["peak_bytes"]
    ker = cases["generation-engine:decode-paged-kernel"]["peak_bytes"]
    assert ker < ref
