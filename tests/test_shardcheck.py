# The semantic static-analysis lane (shardcheck) must stay green AND
# keep catching what it claims to catch: every rule is proven against a
# fixture corpus (one true positive + one clean negative), and the
# tripwire tests prove the canonical engine mutations — a mesh-axis
# typo, a KV-cache dtype mismatch, a shape-mismatched donated arg —
# turn the lane red. Same spirit as test_static_analysis.py for the
# syntactic groups.
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

from copilot_for_consensus_tpu.analysis import (
    RULES as CLI_RULES,
    main as jaxlint_main,
)
from copilot_for_consensus_tpu.analysis import shardcheck
from copilot_for_consensus_tpu.analysis.base import rel

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "tests" / "fixtures" / "shardcheck"


def _findings(fixture: str, rule: str):
    findings, _, skips = shardcheck.check_modules([str(FIXTURES / fixture)])
    assert skips == [], skips       # conftest provides 8 virtual devices
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------------
# fixture corpus: one true positive + one clean negative per rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture,rule,bad_marker,good_marker", [
    ("rule_axis.py", "shard-rule-axis", "bad_rule_axis",
     "good_rule_axis"),
    ("divisibility.py", "shard-divisibility", "bad_divisibility",
     "good_divisibility"),
    ("collective.py", "shard-collective", "bad_collective",
     "good_collective"),
    ("donation.py", "shard-donation", "bad_donation", "good_donation"),
    ("kv_layout.py", "shard-kv-layout", "bad_kv_layout",
     "good_kv_layout"),
    ("bucket.py", "shard-bucket", "bad_bucket", "good_bucket"),
])
def test_rule_true_positive_and_clean_negative(fixture, rule,
                                               bad_marker, good_marker):
    found = _findings(fixture, rule)
    assert any(bad_marker in f.context for f in found), (rule, found)
    assert not any(good_marker in f.context for f in found), (rule, found)


def test_collective_finding_names_the_bad_axis():
    found = _findings("collective.py", "shard-collective")
    assert any("model" in f.message for f in found), found


def test_divisibility_finding_names_dim_and_mesh_size():
    (f,) = _findings("divisibility.py", "shard-divisibility")
    assert "dim 1 (6)" in f.message and "size 4" in f.message


def test_inline_suppression_honored(tmp_path):
    """A `# jaxlint: disable=<rule>` comment above the factory def
    covers every finding the contract emits."""
    mod = tmp_path / "suppressed.py"
    mod.write_text(textwrap.dedent("""\
        from copilot_for_consensus_tpu.analysis.contracts import (
            ContractCase, contract,
        )


        # deliberate: fixture proving inline suppression
        # jaxlint: disable=shard-bucket
        def bad_bucket():
            return ContractCase(buckets=(64,), bucket_covers=(256,))


        SHARDCHECK_CONTRACTS = [contract("bad_bucket", bad_bucket)]
        """))
    findings, _, _ = shardcheck.check_modules([str(mod)])
    assert findings == [], findings


def test_broken_factory_is_a_contract_finding(tmp_path):
    """The registry must not rot silently: a factory that raises (or a
    module with no table) is itself a finding."""
    mod = tmp_path / "broken.py"
    mod.write_text(textwrap.dedent("""\
        from copilot_for_consensus_tpu.analysis.contracts import contract


        def boom():
            raise RuntimeError("factory exploded")


        SHARDCHECK_CONTRACTS = [contract("boom", boom)]
        """))
    findings, _, _ = shardcheck.check_modules([str(mod)])
    assert any(f.rule == "shard-contract" and "factory exploded"
               in f.message for f in findings), findings
    empty = tmp_path / "empty.py"
    empty.write_text("X = 1\n")
    findings, _, _ = shardcheck.check_modules([str(empty)])
    assert any(f.rule == "shard-contract"
               and "no SHARDCHECK_CONTRACTS" in f.message
               for f in findings), findings


# ---------------------------------------------------------------------------
# regression tripwires on the REAL modules: the three mutations the
# acceptance criteria name must turn the lane red.
# ---------------------------------------------------------------------------

_GEN = ROOT / "copilot_for_consensus_tpu" / "engine" / "generation.py"
_ULY = ROOT / "copilot_for_consensus_tpu" / "parallel" / "ulysses.py"


def _mutated_findings(tmp_path, src_path, needle, replacement, stem):
    src = src_path.read_text()
    assert needle in src, f"{src_path.name} moved; update the test"
    mutated = tmp_path / f"{stem}.py"
    mutated.write_text(src.replace(needle, replacement, 1))
    findings, _, skips = shardcheck.check_modules([str(mutated)])
    assert skips == [], skips
    return findings


def test_mesh_axis_typo_in_ulysses_fails_the_lane(tmp_path):
    """Typo the module's default sequence axis: the shard_map specs and
    all_to_all collectives bind an axis no mesh has."""
    findings = _mutated_findings(
        tmp_path, _ULY, 'axis: str = "sp",', 'axis: str = "sq",',
        "ulysses_mutated")
    assert any(f.rule == "shard-collective" and "sq" in f.message
               for f in findings), findings


def test_kv_dtype_mismatch_in_generation_fails_the_lane(tmp_path):
    """Build the slot cache in a different dtype than the prefix pool:
    the five engine programs no longer share one KV-cache layout."""
    needle = ("            cache = decoder.init_cache(cfg, num_slots, "
              "self.max_len,\n"
              "                                       "
              "dtype=self.kv_dtype)")
    findings = _mutated_findings(
        tmp_path, _GEN, needle,
        needle.replace("dtype=self.kv_dtype", "dtype=jnp.float32"),
        "generation_kvdtype_mutated")
    assert any(f.rule == "shard-kv-layout" for f in findings), findings


def test_block_table_dtype_flip_fails_the_lane(tmp_path):
    """Flip the paged dispatches' declared block-table dtype: the
    ``engine.generation-kv-table`` layout group no longer agrees with
    the canonical ``kv_pool.BLOCK_TABLE_DTYPE`` anchor — the drift
    class where host-built tables and the kernel's scalar-prefetch
    spec stop describing the same indirection."""
    needle = ("    table_dtype = jnp.int32       "
              "# dispatch-side block-table dtype")
    findings = _mutated_findings(
        tmp_path, _GEN, needle,
        needle.replace("jnp.int32", "jnp.int16"),
        "generation_tabledtype_mutated")
    assert any(f.rule == "shard-kv-layout"
               and "engine.generation-kv-table" in f.message
               for f in findings), findings


def test_kernel_block_pack_flip_fails_the_lane(tmp_path):
    """Flip the dispatch side's declared lane packing: the
    ``engine.generation-kv-pack`` layout group no longer agrees with
    the kernel's ``KERNEL_BLOCK_PACK`` anchor (and the pool's
    ``POOL_BLOCK_PACK``) — the drift class where the engine's
    128-aligned kv buckets and the kernel's BlockSpec packing stop
    describing the same block layout."""
    needle = ("    block_pack = 128              "
              "# dispatch-side kernel lane packing")
    findings = _mutated_findings(
        tmp_path, _GEN, needle,
        needle.replace("= 128", "= 64"),
        "generation_blockpack_mutated")
    assert any(f.rule == "shard-kv-layout"
               and "engine.generation-kv-pack" in f.message
               for f in findings), findings


def test_shape_mismatched_donated_arg_fails_the_lane(tmp_path):
    """Cast the admit program's cache output: the donated cache buffer
    no longer has a matching output, so XLA would drop the alias."""
    findings = _mutated_findings(
        tmp_path, _GEN, '            return {"k": k, "v": v}',
        '            return {"k": k.astype(jnp.float32), '
        '"v": v.astype(jnp.float32)}',
        "generation_donation_mutated")
    assert any(f.rule == "shard-donation" for f in findings), findings


def test_cast_verify_cache_output_fails_the_lane(tmp_path):
    """Cast the speculative verify dispatch's cache output: its donated
    slot cache loses the aliasable output and the verify program would
    double-allocate the cache every dispatch."""
    needle = "            return out, n_accept, cache"
    findings = _mutated_findings(
        tmp_path, _GEN, needle,
        "            return out, n_accept, jax.tree.map("
        "lambda x: x.astype(jnp.float32), cache)",
        "generation_verify_mutated")
    assert any(f.rule == "shard-donation"
               and "generation-engine:verify" in f.context
               for f in findings), findings


def test_generation_contract_declares_verify_entrypoint():
    """The acceptance contract for speculative decoding: the _verify
    program is registered with the cache donation, rides the one
    engine KV-layout group, and its token-width bucket table covers
    every declared draft length (so the shardcheck preflight guards
    the spec_decode bench preset)."""
    from copilot_for_consensus_tpu.engine import generation

    con = next(c for c in generation.SHARDCHECK_CONTRACTS
               if c.name == "generation-engine")
    cases = {c.label: c for c in con.factory()}
    assert "verify" in cases, sorted(cases)
    vc = cases["verify"]
    assert tuple(vc.donate_argnums) == (4,)
    assert vc.kv_group == "engine.generation-kv"
    assert vc.buckets and max(vc.bucket_covers) <= max(vc.buckets)


# ---------------------------------------------------------------------------
# the real registry is clean, and the CLI glue holds
# ---------------------------------------------------------------------------


def test_registry_contracts_clean():
    """Every registered contract module traces clean — the in-process
    equivalent of `python -m copilot_for_consensus_tpu.analysis` running
    the shard group green under JAX_PLATFORMS=cpu."""
    findings, checked, skips = shardcheck.check_modules()
    assert findings == [], [f.render() for f in findings]
    assert len(checked) == len(
        __import__("copilot_for_consensus_tpu.analysis.contracts",
                   fromlist=["CONTRACT_MODULES"]).CONTRACT_MODULES)
    assert skips == [], skips


def test_cli_shard_group_subprocess_clean():
    """The worker subprocess route (what CI and bench preflight use)
    comes up with the virtual device platform and reports clean."""
    proc = subprocess.run(
        [sys.executable, "-m",
         "copilot_for_consensus_tpu.analysis.shardcheck", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert data["findings"] == [] and data["skips"] == []
    assert len(data["checked"]) >= 9


def test_cli_rules_table_in_sync():
    shard_rules = {r for r, g in CLI_RULES.items() if g == "shard"}
    assert shard_rules == set(shardcheck.RULES)


def test_worker_baseline_silences_finding(tmp_path, capsys):
    """A justified baseline entry matching a shard finding silences it
    through the worker's --baseline route (what bench preflight
    passes)."""
    findings, _, _ = shardcheck.check_modules(
        [str(FIXTURES / "bucket.py")])
    bad = [f for f in findings if f.rule == "shard-bucket"]
    assert bad
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([
        {"rule": f.rule, "path": f.path, "context": f.context,
         "message": f.message,
         "justification": "fixture: deliberately uncovered bucket"}
        for f in bad]))
    rc = shardcheck.main(["--modules", str(FIXTURES / "bucket.py"),
                          "--baseline", str(bl), "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["findings"] == []


# ---------------------------------------------------------------------------
# CLI satellites: --format=github, --strict
# ---------------------------------------------------------------------------


def test_format_github_annotations(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import json\nimport os\nprint(os.name)\n")
    rc = jaxlint_main(["--rules", "policy", "--no-baseline",
                       "--format=github", str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=" in out and "policy-unused-import" in out


def test_output_json_artifact(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import json\nimport os\nprint(os.name)\n")
    artifact = tmp_path / "findings.json"
    rc = jaxlint_main(["--rules", "policy", "--no-baseline",
                       "--output-json", str(artifact), str(bad)])
    capsys.readouterr()
    assert rc == 1
    data = json.loads(artifact.read_text())
    assert any(f["rule"] == "policy-unused-import"
               for f in data["findings"])


def test_skipped_shard_group_does_not_judge_shard_baseline(tmp_path,
                                                           capsys):
    """A run that SKIPS the semantic pass (--fast / explicit paths)
    produces no shard findings, so it must not judge shard baseline
    entries — a still-valid entry would otherwise be reported stale
    (and fail under --strict)."""
    ok = tmp_path / "ok.py"
    ok.write_text("import os\nprint(os.name)\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([
        {"rule": "shard-kv-layout", "path": rel(ok),
         "context": "some-contract", "message": "m",
         "justification": "entry only the full semantic run can judge"}]))
    rc = jaxlint_main(["--fast", "--strict", "--baseline", str(bl),
                       str(ok)])
    out = capsys.readouterr().out
    assert rc == 0 and "stale" not in out, out


def test_strict_turns_stale_baseline_into_failure(tmp_path, capsys):
    ok = tmp_path / "ok.py"
    ok.write_text("import os\nprint(os.name)\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([
        {"rule": "policy-unused-import", "path": rel(ok), "context": "",
         "message": "unused import 'gone'",
         "justification": "entry that matches nothing any more"}]))
    rc = jaxlint_main(["--rules", "policy", "--baseline", str(bl),
                       str(ok)])
    capsys.readouterr()
    assert rc == 0                      # stale only warns by default
    rc = jaxlint_main(["--rules", "policy", "--baseline", str(bl),
                       "--strict", str(ok)])
    out = capsys.readouterr().out
    assert rc == 1 and "stale baseline entry" in out


# ---------------------------------------------------------------------------
# bench preflight: contract violations fail fast with the rc-2/ok:false
# artifact (matching the unknown-BENCH_PRESET behavior)
# ---------------------------------------------------------------------------


def test_bench_preset_contract_modules_cover_every_preset():
    """Every bench preset must have an explicit contract-module list —
    a new preset silently falling back to the generation-only default
    would lose e.g. prefix-cache preflight coverage."""
    sys.path.insert(0, str(ROOT))
    try:
        import bench
    finally:
        sys.path.remove(str(ROOT))
    assert set(bench.PRESET_CONTRACT_MODULES) == \
        set(bench.PRESETS) | {""}


def test_bench_preflight_blocks_on_contract_violation():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ,
             "BENCH_PREFLIGHT": "1",
             "BENCH_PRESET": "",
             "BENCH_SHARDCHECK_MODULES":
                 str(FIXTURES / "donation.py")})
    assert proc.returncode == 2, proc.stdout + proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["ok"] is False
    assert "shardcheck preflight failed" in line["reason"]
    assert any("shard-donation" in f for f in line["findings"])


DURA_FIXTURES = ROOT / "tests" / "fixtures" / "duracheck"


def test_bench_dura_preflight_blocks_on_violation():
    """pipeline_chaos maps to no jitted entrypoints (shardcheck
    skips), so the dura family is its gate: pointed at the violating
    fixture corpus, the bench must exit 2 with the same rc-2/ok:false
    artifact contract before the storm starts."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ,
             "BENCH_PREFLIGHT": "1",
             "BENCH_PRESET": "pipeline_chaos",
             "BENCH_DURACHECK_PATHS": "tests/fixtures/duracheck"})
    assert proc.returncode == 2, proc.stdout + proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["ok"] is False
    assert "duracheck preflight failed" in line["reason"]
    assert any("dura-" in f for f in line["findings"])


def test_scale_bench_gates_on_dura_preflight():
    """The host-pipeline driver (scripts/scale_bench.py) runs the same
    gate over bus/ + services/ before building the pipeline."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "scale_bench.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ,
             "BENCH_PREFLIGHT": "1",
             "BENCH_DURACHECK_PATHS": "tests/fixtures/duracheck"})
    assert proc.returncode == 2, proc.stdout + proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["ok"] is False
    assert "duracheck preflight failed" in line["reason"]


def test_dura_preflight_opt_out_and_preset_map(monkeypatch):
    """BENCH_PREFLIGHT=0 skips even with violating paths pinned; the
    pipeline_chaos preset map resolves to the live bus/services planes
    (which must pass their own gate); engine presets map to no dura
    paths and skip."""
    sys.path.insert(0, str(ROOT))
    try:
        import bench
    finally:
        sys.path.remove(str(ROOT))
    monkeypatch.setenv("BENCH_PREFLIGHT", "0")
    monkeypatch.setenv("BENCH_DURACHECK_PATHS",
                       "tests/fixtures/duracheck")
    assert bench.duracheck_preflight() is None
    monkeypatch.setenv("BENCH_PREFLIGHT", "1")
    monkeypatch.delenv("BENCH_DURACHECK_PATHS")
    monkeypatch.setenv("BENCH_PRESET", "rag2k")
    assert bench.duracheck_preflight() is None
    monkeypatch.setenv("BENCH_PRESET", "pipeline_chaos")
    assert bench.duracheck_preflight() is None   # live planes CLEAN


def test_mesh_scatter_out_spec_flip_fails_the_lane(tmp_path):
    """Flip the mesh scatter's pool out_specs to replicated: the
    shard_map returns a shard-local-shaped pool as the global result,
    so the donated pool halves lose their shape-matching outputs —
    exactly the pool-PartitionSpec drift the sharded dispatches must
    never ship (ISSUE 15 tripwire)."""
    needle = ("                    in_specs=(POOL, POOL, VIEW, VIEW, "
              "ROW2, ROW2),\n"
              "                    out_specs=(POOL, POOL),")
    findings = _mutated_findings(
        tmp_path, _GEN, needle,
        needle.replace(
            "out_specs=(POOL, POOL),",
            "out_specs=(P(None, None, None, None, None),\n"
            "                               "
            "P(None, None, None, None, None)),"),
        "generation_mesh_poolspec_mutated")
    assert any(f.rule == "shard-donation"
               and "paged-mesh" in f.context
               for f in findings), findings


def test_mesh_handoff_import_dropped_donation_fails_the_lane(tmp_path):
    """Cast the KV-handoff import's pool outputs: the donated pool
    halves no longer alias and every handoff would double-buffer the
    whole decode pool."""
    needle = "                return pk, pv\n\n            " \
             "self._import_fn = jax.jit(_import_kv,"
    findings = _mutated_findings(
        tmp_path, _GEN, needle,
        needle.replace(
            "                return pk, pv",
            "                return pk.astype(jnp.float16), "
            "pv.astype(jnp.float16)"),
        "generation_handoff_donation_mutated")
    assert any(f.rule == "shard-donation"
               and "kv-handoff-import" in f.context
               for f in findings), findings
