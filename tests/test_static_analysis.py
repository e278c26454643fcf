# The first-party static-analysis lane must stay green AND keep
# catching what it claims to catch (a policy that can't fail is not a
# policy — same spirit as the fuzzer's seeded-bug effectiveness proof).
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "scripts" / "validate_python.py"
FIXTURES = ROOT / "tests" / "fixtures" / "jaxlint"

sys.path.insert(0, str(ROOT / "scripts"))
import validate_python as vp  # noqa: E402

from copilot_for_consensus_tpu.analysis import (  # noqa: E402
    analyze_files,
    main as jaxlint_main,
)


def test_repo_is_clean_fast():
    """Syntax + AST policies hold over the whole source tree (the
    import-smoke stage runs in CI's dedicated lint job; the suite
    itself already imports everything)."""
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--fast"], cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.parametrize("snippet,expect", [
    ("def f(x=[]):\n    return x\n", "mutable default"),
    ("def f(x={'a': 1}):\n    return x\n", "mutable default"),
    ("try:\n    pass\nexcept:\n    pass\n", "bare 'except:'"),
    ("import json\nimport os\nprint(os.name)\n", "unused import 'json'"),
    ("def f(:\n    pass\n", "syntax"),
])
def test_lane_catches_seeded_bugs(tmp_path, snippet, expect):
    bad = tmp_path / "seeded.py"
    bad.write_text(textwrap.dedent(snippet))
    errs = (vp.check_syntax([bad]) if expect == "syntax" else
            vp.check_syntax([bad])
            + vp.check_mutable_defaults([bad])
            + vp.check_bare_except([bad])
            + vp.check_unused_imports([bad]))
    assert any(expect in e for e in errs), errs


def test_lane_exemptions_hold(tmp_path):
    """noqa lines, __all__ strings, and used imports must NOT flag."""
    ok = tmp_path / "ok.py"
    ok.write_text(
        "import json  # noqa: used by doctest\n"
        "import os\n"
        "__all__ = ['os']\n"
        "print(os.name)\n")
    assert vp.check_unused_imports([ok]) == []


def test_syntax_error_reported_not_crashing(tmp_path):
    """A file with a syntax error must yield ONE syntax finding from
    the whole lane, never an unhandled SyntaxError out of main()."""
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n    pass\n")
    errs = (vp.check_syntax([bad]) + vp.check_mutable_defaults([bad])
            + vp.check_bare_except([bad])
            + vp.check_unused_imports([bad]))
    assert len(errs) == 1 and "syntax" in errs[0]


def test_constructor_call_defaults_flagged(tmp_path):
    bad = tmp_path / "ctor.py"
    bad.write_text("def f(x=list(), y=dict()):\n    return x, y\n")
    errs = vp.check_mutable_defaults([bad])
    assert len(errs) == 2
    # frozen-config style defaults (arbitrary constructor calls) pass:
    # only the builtin mutable containers are the documented class
    ok = tmp_path / "cfg.py"
    ok.write_text("def f(x=Config()):\n    return x\n")
    assert vp.check_mutable_defaults([ok]) == []


# ---------------------------------------------------------------------------
# jaxlint rule groups (copilot_for_consensus_tpu/analysis): each rule is
# proven against the fixture corpus — one true positive AND one clean
# negative per rule — so a checker that silently stops firing (or starts
# flagging the blessed idiom) fails here, not in review.
# ---------------------------------------------------------------------------


def _findings(fixture: str, rule: str):
    out = analyze_files([FIXTURES / fixture])
    return [f for f in out if f.rule == rule]


@pytest.mark.parametrize("fixture,rule,bad_marker,good_marker", [
    ("host_sync.py", "host-sync-in-jit", "bad_sync", "good_sync"),
    ("retrace.py", "retrace-hazard", "bad_branch", "good_branch"),
    ("donation.py", "donation", "_step_bad", "_step_good"),
    ("prng.py", "prng-reuse", "bad_double_use", "good_split"),
    ("blocking.py", "blocking-call", "BadConsumer", "GoodConsumer"),
    ("collective.py", "collective-axis", "bad_body", "good_body"),
])
def test_rule_true_positive_and_clean_negative(fixture, rule,
                                               bad_marker, good_marker):
    found = _findings(fixture, rule)
    assert any(bad_marker in f.context or bad_marker in f.message
               for f in found), (rule, found)
    assert not any(good_marker in f.context for f in found), (rule, found)


def test_host_sync_catches_every_surface():
    msgs = "\n".join(f.message for f in
                     _findings("host_sync.py", "host-sync-in-jit"))
    for surface in (".item()", "np.asarray", "jax.device_get",
                    ".block_until_ready()", "`float()`"):
        assert surface in msgs, (surface, msgs)


def test_retrace_unhashable_static_default_flagged():
    found = _findings("retrace.py", "retrace-hazard")
    assert any("unhashable" in f.message for f in found)


def test_prng_all_three_reuse_shapes_flagged():
    ctxs = {f.context for f in _findings("prng.py", "prng-reuse")}
    assert {"bad_double_use", "bad_use_after_split",
            "bad_loop_reuse"} <= ctxs
    assert "good_exclusive_branches" not in ctxs


def test_blocking_flags_publish_under_lock():
    found = _findings("blocking.py", "blocking-call")
    assert any("lock" in f.message for f in found)


def test_inline_suppression_honored():
    """`# jaxlint: disable=<rule>` on (or right above) the line wins."""
    found = _findings("blocking.py", "blocking-call")
    assert not any(f.context.endswith("run_suppressed") for f in found)


def test_collective_axis_literal_vs_mesh():
    found = _findings("collective.py", "collective-axis")
    assert any("'tp'" in f.message for f in found)
    assert any("'model'" in f.message for f in found)


# ---------------------------------------------------------------------------
# regression tripwires on the REAL engine: the two mutations the
# acceptance criteria name must turn the lane red.
# ---------------------------------------------------------------------------

_GEN = ROOT / "copilot_for_consensus_tpu" / "engine" / "generation.py"


def test_deleting_decode_donation_fails_the_lane(tmp_path):
    src = _GEN.read_text()
    needle = "jax.jit(_decode, donate_argnums=(3,),"
    assert needle in src, "decode jit signature moved; update the test"
    mutated = tmp_path / "generation_mutated.py"
    mutated.write_text(src.replace(needle, "jax.jit(_decode,"))
    found = [f for f in analyze_files([mutated]) if f.rule == "donation"]
    assert any("_decode" in f.context and "'cache'" in f.message
               for f in found), found


def test_item_inside_decode_jit_fails_the_lane(tmp_path):
    src = _GEN.read_text()
    needle = "            w_sz = self.decode_window\n"
    assert needle in src, "decode body moved; update the test"
    mutated = tmp_path / "generation_mutated.py"
    mutated.write_text(src.replace(
        needle, needle + "            _dbg = tokens.sum().item()\n", 1))
    found = [f for f in analyze_files([mutated])
             if f.rule == "host-sync-in-jit"]
    assert any("_decode" in f.context for f in found), found


def test_deleting_decode_donation_fails_the_hlo_lane(tmp_path):
    """The post-lowering view of the same mutation: with
    donate_argnums gone from the decode jit, the COMPILED artifact
    carries no input_output_alias for the cache the contract still
    declares donated — hlo-donation-alias must flag (the ast donation
    rule sees the jit call; this sees what XLA actually kept)."""
    from copilot_for_consensus_tpu.analysis import hlocheck

    src = _GEN.read_text()
    needle = "jax.jit(_decode, donate_argnums=(3,),"
    assert needle in src, "decode jit signature moved; update the test"
    mutated = tmp_path / "generation_hlo_donation_mutated.py"
    mutated.write_text(src.replace(needle, "jax.jit(_decode,", 1))
    findings, _, skips = hlocheck.check_modules(
        [str(mutated)], labels={"decode"},
        only_rules={"hlo-donation-alias"})
    assert skips == [], skips
    assert any(f.rule == "hlo-donation-alias" and ":decode" in f.context
               for f in findings), [f.render() for f in findings]


def test_widening_draft_buckets_fails_the_hlo_lane(tmp_path):
    """Widen spec_draft_lens without touching the program-cache
    contract's declared cardinality: the bucket cross-product lowers
    to one more distinct program than declared — hlo-program-cache
    must flag the drift before it ships as a retrace/program-cache
    explosion."""
    from copilot_for_consensus_tpu.analysis import hlocheck

    src = _GEN.read_text()
    needle = "spec_draft_lens=(0, 2, 4)"
    assert src.count(needle) >= 1, "draft buckets moved; update the test"
    mutated = tmp_path / "generation_hlo_buckets_mutated.py"
    mutated.write_text(src.replace(needle, "spec_draft_lens=(0, 2, 4, 6)"))
    findings, _, skips = hlocheck.check_modules(
        [str(mutated)], labels={"program-cache"},
        only_rules={"hlo-program-cache"})
    assert skips == [], skips
    assert any(f.rule == "hlo-program-cache"
               and "7 declared" in f.message
               for f in findings), [f.render() for f in findings]


# ---------------------------------------------------------------------------
# baseline workflow: grandfathered findings must carry a justification;
# matching entries silence findings; the e2e repo run is clean.
# ---------------------------------------------------------------------------


def test_baseline_requires_justification(tmp_path):
    entry = {"rule": "donation", "path": "x.py", "context": "f",
             "message": "m"}                    # no justification
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([entry]))
    rc = jaxlint_main(["--rules", "donation", "--baseline", str(bl),
                       str(FIXTURES / "donation.py")])
    assert rc == 1


def test_baseline_silences_matching_finding(tmp_path, capsys):
    found = [f for f in analyze_files([FIXTURES / "donation.py"])
             if f.rule == "donation"]
    assert found
    entries = [{"rule": f.rule, "path": f.path, "context": f.context,
                "message": f.message,
                "justification": "fixture: deliberately undonated"}
               for f in found]
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(entries))
    rc = jaxlint_main(["--rules", "donation", "--baseline", str(bl),
                       str(FIXTURES / "donation.py")])
    assert rc == 0, capsys.readouterr().out


def test_strict_rejects_todo_justification(tmp_path, capsys):
    """A justification still starting with the --write-baseline TODO
    placeholder warns on a normal run but fails under --strict
    (finding id baseline-unjustified) — the placeholder must not
    calcify into the record."""
    found = [f for f in analyze_files([FIXTURES / "donation.py"])
             if f.rule == "donation"]
    assert found
    entries = [{"rule": f.rule, "path": f.path, "context": f.context,
                "message": f.message,
                "justification": "TODO: explain why this is deliberate"}
               for f in found]
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(entries))
    args = ["--rules", "donation", "--baseline", str(bl),
            str(FIXTURES / "donation.py")]
    assert jaxlint_main(args) == 0          # non-strict: warn only
    assert "baseline-unjustified" in capsys.readouterr().err
    rc = jaxlint_main(["--strict"] + args)
    out = capsys.readouterr()
    assert rc == 1
    assert "baseline-unjustified" in out.out


def test_repo_baseline_entries_all_justified():
    from copilot_for_consensus_tpu.analysis.base import (
        DEFAULT_BASELINE,
        load_baseline,
    )

    from copilot_for_consensus_tpu.analysis.base import unjustified_entries

    entries, errors = load_baseline(DEFAULT_BASELINE)
    assert errors == []
    assert all(len(e["justification"]) > 40 for e in entries), (
        "baseline justifications must actually explain the decision")
    assert unjustified_entries(entries) == [], (
        "committed baseline entries must not carry the TODO placeholder")


# ---------------------------------------------------------------------------
# racecheck (the `race` group): each rule proven against its fixture —
# one true positive AND one clean negative — plus tripwires that
# re-introduce the REAL shipped bugs (PR-7 callback-under-lock, PR-8
# wrapper-shadow, broker stats lock-consistency) and assert the lane
# turns red.
# ---------------------------------------------------------------------------

RACE_FIXTURES = ROOT / "tests" / "fixtures" / "racecheck"

from copilot_for_consensus_tpu.analysis import racecheck  # noqa: E402


def _race_findings(fixture: str, rule: str):
    out = analyze_files([RACE_FIXTURES / fixture])
    return [f for f in out if f.rule == rule]


@pytest.mark.parametrize("fixture,rule,bad_marker,good_marker", [
    ("lock_order.py", "race-lock-order", "BadOrder", "GoodOrder"),
    ("callback_under_lock.py", "race-callback-under-lock",
     "BadNotifier", "GoodNotifier"),
    ("unlocked_field.py", "race-unlocked-field", "BadLedger",
     "GoodLedger"),
    ("thread_lifecycle.py", "race-thread-lifecycle", "BadPump",
     "GoodPump"),
    # pool-shutdown tripwire (ISSUE 11): a worker pool whose consume
    # threads have no stop path must flag; the StageWorkerPool shape
    # (stop-aware loops + owner join over the list) must stay clean
    ("pool_shutdown.py", "race-thread-lifecycle", "BadPool",
     "GoodPool"),
    ("wrapper_shadow.py", "race-wrapper-shadow", "BadWrapper",
     "GoodWrapper"),
    # telemetry-shipper pump (ISSUE 20): a fire-and-forget flush
    # thread must flag; the TelemetryShipper shape (stop-aware wait
    # loop + owner-joined stop before the spool closes) stays clean
    ("ship_pump.py", "race-thread-lifecycle", "BadShipPump",
     "GoodShipPump"),
])
def test_race_rule_true_positive_and_clean_negative(fixture, rule,
                                                    bad_marker,
                                                    good_marker):
    found = _race_findings(fixture, rule)
    assert any(bad_marker in f.context or bad_marker in f.message
               for f in found), (rule, found)
    assert not any(good_marker in f.context or good_marker in f.message
                   for f in found), (rule, found)


def test_lock_order_cycle_names_both_locks():
    """The ABBA report must name both locks so the reader can pick an
    order; the single-lock self-deadlock is reported as guaranteed."""
    found = _race_findings("lock_order.py", "race-lock-order")
    cycle = [f for f in found if "cycle" in f.message]
    assert cycle and "_alpha" in cycle[0].message \
        and "_beta" in cycle[0].message, found
    assert any("self-deadlock" in f.message
               and "BadSelfDeadlock" in f.context for f in found), found
    assert not any("GoodReentrant" in f.context for f in found), found


def test_callback_under_lock_propagates_through_call_graph():
    """``complete()`` never touches a callback directly — it calls
    ``_finish()``, which does. The call site must still flag."""
    found = _race_findings("callback_under_lock.py",
                           "race-callback-under-lock")
    assert any(f.context == "BadIndirect.complete"
               and "_finish" in f.message for f in found), found


def test_unlocked_field_requires_a_common_lock():
    """Accesses under two DIFFERENT locks race just like a bare one:
    the lockset intersection must be non-empty (RacerD's invariant)."""
    found = _race_findings("unlocked_field.py", "race-unlocked-field")
    assert any("BadTwoGuards" in f.context
               and "NO common lock" in f.message for f in found), found


def test_callback_under_lock_catches_subscript_invocation():
    """``self._handlers[key](env)`` under the lock — the element call
    form must flag just like the bound-local form."""
    found = _race_findings("callback_under_lock.py",
                           "race-callback-under-lock")
    assert any(f.context == "BadSubscriptDispatch.dispatch"
               for f in found), found


def test_wrapper_shadow_cross_pass_resolves_relative_imports(tmp_path):
    """``from .base import Base`` must resolve against the importing
    module's own directory — never some other base.py in the tree."""
    pkg = tmp_path / "pkg"
    decoy = tmp_path / "other"
    pkg.mkdir()
    decoy.mkdir()
    # decoy base.py with NO trivial defaults: wrong resolution = miss
    (decoy / "base.py").write_text(
        "class Base:\n    def saturation(self):\n"
        "        raise NotImplementedError\n")
    (pkg / "base.py").write_text(
        "class Base:\n    def saturation(self):\n        return {}\n")
    (pkg / "wrap.py").write_text(
        "from .base import Base\n\n\n"
        "class Wrapper(Base):\n"
        "    def __init__(self, inner):\n"
        "        self.inner = inner\n\n"
        "    def __getattr__(self, name):\n"
        "        return getattr(self.inner, name)\n")
    # and an `as`-aliased import: lookup in the defining module must
    # use the ORIGINAL name, not the local alias
    (pkg / "wrap2.py").write_text(
        "from .base import Base as RenamedBase\n\n\n"
        "class AliasWrapper(RenamedBase):\n"
        "    def __init__(self, inner):\n"
        "        self.inner = inner\n\n"
        "    def __getattr__(self, name):\n"
        "        return getattr(self.inner, name)\n")
    found = [f for f in racecheck.check_cross(
                 [decoy / "base.py", pkg / "base.py", pkg / "wrap.py",
                  pkg / "wrap2.py"])
             if f.rule == "race-wrapper-shadow"]
    assert any("'saturation'" in f.message and f.context == "Wrapper"
               for f in found), found
    assert any("'saturation'" in f.message
               and f.context == "AliasWrapper" for f in found), found


def test_cli_contradictory_rules_group_fails_loudly():
    """--rules blocking-call --group race selects nothing: that must
    be a usage error (rc 2), not a 0-file CLEAN run."""
    with pytest.raises(SystemExit) as exc:
        jaxlint_main(["--rules", "blocking-call", "--group", "race"])
    assert exc.value.code == 2


def test_unlocked_field_counts_container_element_writes():
    """``self._stats[key] += 1`` is a write OF ``_stats`` (the broker
    ledger shape) — bare element mutation must flag."""
    found = _race_findings("unlocked_field.py", "race-unlocked-field")
    assert any(f.context == "BadContainer.bump"
               and "'_stats'" in f.message for f in found), found
    # the verified "# caller holds the lock" idiom must NOT flag
    assert not any("GoodPrivateHelper" in f.context for f in found), found


def test_inferred_held_defeated_by_cross_class_call_site():
    """'caller holds the lock' inference must count EVERY resolvable
    call site: a lock-free cross-class call into ``_mark_done`` makes
    its bare write a real race, not an inherited-lock access."""
    found = _race_findings("unlocked_field.py", "race-unlocked-field")
    assert any(f.context == "_CrossHandle._mark_done"
               and "'_state'" in f.message for f in found), found


def test_module_level_thread_joined_in_sibling_function_is_clean():
    found = _race_findings("thread_lifecycle.py",
                           "race-thread-lifecycle")
    assert not any("_module_loop" in f.message
                   or "good_module" in f.context for f in found), found


def test_thread_lifecycle_join_only_owner_is_clean():
    found = _race_findings("thread_lifecycle.py",
                           "race-thread-lifecycle")
    assert not any("GoodJoinOnly" in f.context for f in found), found


def test_thread_lifecycle_tracked_join_excuses_nothing_else():
    """Joining thread _a must not excuse the forgotten _b; only a
    provenance-free join (the list-loop idiom) excuses untracked
    threads."""
    found = _race_findings("thread_lifecycle.py",
                           "race-thread-lifecycle")
    assert any(f.context == "BadSecondThread.__init__"
               and "_pump" in f.message for f in found), found


def test_lock_model_alias_declared_before_source():
    """``Condition(self._lock)`` textually before ``self._lock =
    threading.Lock()`` still aliases to ONE identity — holding the
    condition while taking the lock is a guaranteed self-deadlock."""
    found = _race_findings("lock_order.py", "race-lock-order")
    assert any("BadAliasBeforeSource" in f.context
               and "self-deadlock" in f.message for f in found), found


def test_lock_field_reassignable_from_parameter_stays_a_lock():
    """A lock field also assignable from a parameter (test injection)
    must neither crash the scan nor be misread as a callback field."""
    found = analyze_files([RACE_FIXTURES / "unlocked_field.py"])
    assert not any("GoodInjectedLock" in f.context
                   for f in found if f.rule.startswith("race-")), found


def test_blocking_call_sees_condition_members():
    """Satellite: the shared assignment-provenance lock model makes
    blocking-call recognize Condition-typed members whose names never
    say 'lock' (``self._work``, the async_runner dispatcher shape)."""
    found = _findings("blocking.py", "blocking-call")
    assert any(f.context == "BadConditionConsumer.run"
               for f in found), found
    assert not any("GoodConditionConsumer" in f.context
                   for f in found), found


# -- tripwires on the REAL runtime files: re-introduce each shipped bug

_RUNNER = ROOT / "copilot_for_consensus_tpu" / "engine" / "async_runner.py"
_VALIDATING = ROOT / "copilot_for_consensus_tpu" / "bus" / "validating.py"
_BUS_BASE = ROOT / "copilot_for_consensus_tpu" / "bus" / "base.py"
_BROKER = ROOT / "copilot_for_consensus_tpu" / "bus" / "broker.py"


def test_done_callback_under_runner_lock_fails_the_lane(tmp_path):
    """PR-7 regression: resolving a Handle inside the dispatcher's
    ``_work`` lock (shared with the watchdog; done-callbacks may
    re-enter submit()) must flag race-callback-under-lock."""
    src = _RUNNER.read_text()
    needle = (
        "                    with self._work:\n"
        "                        h = self._handles.pop(c.request_id, None)\n"
        "                        meta = self._replays.pop(c.request_id, None)\n")
    assert needle in src, "dispatcher harvest block moved; update the test"
    mutated = tmp_path / "async_runner_mutated.py"
    mutated.write_text(src.replace(
        needle,
        needle + "                        if h is not None:\n"
                 "                            h._resolve(c)\n", 1))
    found = [f for f in analyze_files([mutated])
             if f.rule == "race-callback-under-lock"]
    assert any("_resolve" in f.message for f in found), found
    # the unmutated file is part of the clean e2e run (no findings)


def test_wrapper_shadow_catches_inert_saturation(tmp_path):
    """PR-8 regression: drop ValidatingPublisher's explicit
    ``saturation()`` delegation and the cross-module pass must flag the
    base class's concrete ``{}`` default shadowing ``__getattr__`` —
    the bug that silently disabled the throttle/pacer in the assembled
    pipeline."""
    src = _VALIDATING.read_text()
    start = src.index("    def saturation(self)")
    end = src.index("    def pending_depths(self)")
    assert 0 < start < end, "ValidatingPublisher moved; update the test"
    pkg = tmp_path / "copilot_for_consensus_tpu" / "bus"
    pkg.mkdir(parents=True)
    (pkg / "base.py").write_text(_BUS_BASE.read_text())
    (pkg / "validating.py").write_text(src[:start] + src[end:])
    found = [f for f in racecheck.check_cross(
                 [pkg / "base.py", pkg / "validating.py"])
             if f.rule == "race-wrapper-shadow"]
    assert any("'saturation'" in f.message
               and f.context == "ValidatingPublisher"
               for f in found), found
    # the unmutated pair is clean (the explicit delegation overrides)
    clean = [f for f in racecheck.check_cross([_BUS_BASE, _VALIDATING])
             if f.rule == "race-wrapper-shadow"]
    assert clean == [], clean


def test_unlocked_broker_stats_fails_the_lane(tmp_path):
    """Dropping ``_stats_lock`` from the publisher's stats mutation
    must flag race-unlocked-field (the ledger is read under the lock
    elsewhere)."""
    src = _BROKER.read_text()
    needle = ("        with self._stats_lock:\n"
              "            self._stats[key] += n\n")
    assert needle in src, "_bump moved; update the test"
    mutated = tmp_path / "broker_mutated.py"
    mutated.write_text(src.replace(
        needle, "        self._stats[key] += n\n", 1))
    found = [f for f in analyze_files([mutated])
             if f.rule == "race-unlocked-field"]
    assert any("'_stats'" in f.message and "_bump" in f.context
               for f in found), found


_SHIP = ROOT / "copilot_for_consensus_tpu" / "obs" / "ship.py"


def test_fire_and_forget_ship_pump_fails_the_lane(tmp_path):
    """ISSUE-20 tripwire on the REAL shipper: replace the pump's
    stop-aware wait loop with a bare sleep loop AND drop the owner
    join — race-thread-lifecycle must flag the now-unstoppable pump
    thread."""
    src = _SHIP.read_text()
    loop_needle = ("        while not self._stop.is_set():\n"
                   "            self._stop.wait(self.interval_s)\n")
    join_needle = ("        if thread is not None:\n"
                   "            thread.join(timeout=5.0)\n")
    assert loop_needle in src and join_needle in src, \
        "TelemetryShipper pump/stop moved; update the test"
    mutated = tmp_path / "ship_mutated.py"
    mutated.write_text(
        src.replace(loop_needle,
                    "        while True:\n"
                    "            time.sleep(self.interval_s)\n", 1)
        .replace(join_needle, "", 1))
    found = [f for f in analyze_files([mutated])
             if f.rule == "race-thread-lifecycle"]
    assert any("TelemetryShipper" in f.context or "_pump" in f.message
               for f in found), found
    # the unmutated file is part of the clean e2e run (no findings)


def test_torn_spool_flush_fails_the_lane(tmp_path):
    """ISSUE-20 tripwire on the REAL spool: drop the one-transaction
    wrapper around the append loop (per-row autocommit — a SIGKILL
    mid-flush would commit a torn batch) — dura-sqlite-ledger must
    flag the unscoped mutating loop."""
    src = _SHIP.read_text()
    needle = ("                with self._db:\n"
              "                    for kind, payload in batch:\n"
              "                        self._db.execute(\n")
    assert needle in src, "TelemetrySpool.append moved; update the test"
    mutated = tmp_path / "spool_mutated.py"
    mutated.write_text(src.replace(
        needle,
        "                for kind, payload in batch:\n"
        "                    self._db.execute(\n", 1).replace(
        "                            \"INSERT INTO rows (kind, payload) \"\n"
        "                            \"VALUES (?, ?)\", (kind, payload))\n",
        "                        \"INSERT INTO rows (kind, payload) \"\n"
        "                        \"VALUES (?, ?)\", (kind, payload))\n", 1))
    found = [f for f in analyze_files([mutated], {"dura"})
             if f.rule == "dura-sqlite-ledger"]
    assert any("transaction" in f.message for f in found), found


# -- baseline round trip + CLI group filter for the race family


def test_race_baseline_round_trip(tmp_path, capsys):
    """racecheck findings ride the existing baseline machinery: a
    justified entry silences the finding; a TODO placeholder warns on a
    normal run and fails under --strict (the PR-4 rejection rule)."""
    fixture = RACE_FIXTURES / "unlocked_field.py"
    found = [f for f in analyze_files([fixture])
             if f.rule == "race-unlocked-field"]
    assert found
    entries = [{"rule": f.rule, "path": f.path, "context": f.context,
                "message": f.message,
                "justification": "fixture: deliberate bare access kept "
                                 "to prove the baseline round trip"}
               for f in found]
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(entries))
    args = ["--group", "race", "--baseline", str(bl), str(fixture)]
    assert jaxlint_main(args) == 0, capsys.readouterr().out
    for e in entries:
        e["justification"] = "TODO: explain why this is deliberate"
    bl.write_text(json.dumps(entries))
    assert jaxlint_main(args) == 0          # non-strict: warn only
    assert "baseline-unjustified" in capsys.readouterr().err
    rc = jaxlint_main(["--strict"] + args)
    out = capsys.readouterr()
    assert rc == 1
    assert "baseline-unjustified" in out.out


def test_cli_group_filter(capsys):
    """--group runs one rule family: the race fixture fails under
    --group race and passes under --group jax (whose rules don't fire
    on it) — the dev-loop filter the CI matrix uses."""
    fixture = str(RACE_FIXTURES / "callback_under_lock.py")
    rc = jaxlint_main(["--group", "race", "--no-baseline", fixture])
    out = capsys.readouterr()
    assert rc == 1
    assert "race-callback-under-lock" in out.out
    rc = jaxlint_main(["--group", "jax", "--no-baseline", fixture])
    capsys.readouterr()
    assert rc == 0


def test_repo_race_group_clean_with_cross_pass():
    """The full-repo race run (including the cross-module
    wrapper-shadow pass that --fast skips) is clean — the acceptance
    bar for dogfooding the analyzer over its own thread plane."""
    proc = subprocess.run(
        [sys.executable, "-m", "copilot_for_consensus_tpu.analysis",
         "--group", "race", "--strict"], cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[race]" in proc.stderr, proc.stderr


# ---------------------------------------------------------------------------
# duracheck (the `dura` group): the crash-safety / exactly-once
# contracts from docs/RESILIENCE.md. Each rule proven against its
# fixture — one true positive AND one clean negative — plus tripwires
# that re-introduce the REAL shipped bug classes (PR-11 commit/publish
# window, PR-12 journal ordering, the finisher's transient re-raise)
# and assert the lane turns red.
# ---------------------------------------------------------------------------

DURA_FIXTURES = ROOT / "tests" / "fixtures" / "duracheck"

from copilot_for_consensus_tpu.analysis import duracheck  # noqa: E402


def _dura_findings(fixture: str, rule: str):
    out = analyze_files([DURA_FIXTURES / fixture], {"dura"})
    return [f for f in out if f.rule == rule]


@pytest.mark.parametrize("fixture,rule,bad_marker,good_marker", [
    ("commit_publish_window.py", "dura-commit-publish-window",
     "BadFreshOnlyPublisher", "GoodRepublishStored"),
    ("raw_publish.py", "dura-raw-publish", "BadRawEnvelopePublisher",
     "GoodTypedPublisher"),
    ("ack_swallow.py", "dura-ack-swallow", "BadSwallowingHandler",
     "GoodClassifyingHandler"),
    ("journal_order.py", "dura-journal-order", "BadSubmitAfterEnqueue",
     "GoodJournalOrder"),
    ("idempotent_write.py", "dura-idempotent-write", "BadBlindInsert",
     "GoodDupTolerantInsert"),
    ("sqlite_ledger.py", "dura-sqlite-ledger", "BadLedger",
     "GoodLedger"),
    # telemetry spool (ISSUE 20): a spool without WAL + one-transaction
    # flushes must flag; the TelemetrySpool shape stays clean
    ("ship_spool.py", "dura-sqlite-ledger", "BadSpool", "GoodSpool"),
])
def test_dura_rule_true_positive_and_clean_negative(fixture, rule,
                                                    bad_marker,
                                                    good_marker):
    found = _dura_findings(fixture, rule)
    assert any(bad_marker in f.context or bad_marker in f.message
               for f in found), (rule, found)
    assert not any(good_marker in f.context or good_marker in f.message
                   for f in found), (rule, found)


def test_dura_rules_registered_under_dura_group():
    """duracheck.RULES and the CLI's RULES map must stay in sync (the
    group-scoped baseline judgment keys off this mapping)."""
    from copilot_for_consensus_tpu.analysis import RULES
    for rule in duracheck.RULES:
        assert RULES.get(rule) == "dura", rule


def test_journal_order_flags_both_halves():
    """Submit-before-enqueue AND retire-after-harvest are one
    contract; each half must flag independently."""
    ctxs = {f.context for f in
            _dura_findings("journal_order.py", "dura-journal-order")}
    assert "BadSubmitAfterEnqueue.submit" in ctxs, ctxs
    assert "BadRetireBeforeHarvest.harvest" in ctxs, ctxs
    assert not any("GoodJournalOrder" in c for c in ctxs), ctxs


def test_sqlite_ledger_flags_all_three_disciplines():
    msgs = "\n".join(f.message for f in
                     _dura_findings("sqlite_ledger.py",
                                    "dura-sqlite-ledger"))
    assert "journal_mode=WAL" in msgs, msgs
    assert "transaction" in msgs, msgs
    assert "owner-joined close" in msgs, msgs


def test_ack_swallow_accepts_all_three_classifying_exits():
    """re-raise, `return exc`, and a *Failed-event publish are the
    legitimate exits — none of GoodClassifyingHandler's three handlers
    may flag, and the swallowing handler is the only finding."""
    found = _dura_findings("ack_swallow.py", "dura-ack-swallow")
    assert {f.context for f in found} == \
        {"BadSwallowingHandler.on_JobReady"}, found


def test_raw_publish_flags_wire_protocol_op():
    """A raw broker `pub` op is the sneakier outbox bypass — it must
    flag alongside the publish_envelope form."""
    found = _dura_findings("raw_publish.py", "dura-raw-publish")
    assert any(f.context == "BadRawBrokerOp.on_FlushRequested"
               for f in found), found


def test_effect_provenance_not_name_tokens(tmp_path):
    """Receivers resolve by PROVENANCE: a renamed field bound from an
    `EventPublisher`-annotated param is a publisher; an unrelated
    object whose method merely shares a name is not."""
    mod = tmp_path / "renamed.py"
    mod.write_text(
        "class RenamedFieldHandler:\n"
        "    def __init__(self, bus: EventPublisher):\n"
        "        self.bus = bus\n\n"
        "    def on_ThingHappened(self, event):\n"
        "        self.bus.publish_envelope(event.to_envelope(), 'x')\n\n\n"
        "class NotAPublisher:\n"
        "    def __init__(self, codec):\n"
        "        self.codec = codec\n\n"
        "    def on_ThingHappened(self, event):\n"
        "        self.codec.publish_envelope(event)\n")
    found = [f for f in analyze_files([mod], {"dura"})
             if f.rule == "dura-raw-publish"]
    assert any("RenamedFieldHandler" in f.context for f in found), found
    assert not any("NotAPublisher" in f.context for f in found), found


# -- tripwires on the REAL runtime files: re-introduce each shipped
#    durability bug class

_PARSING = ROOT / "copilot_for_consensus_tpu" / "services" / "parsing.py"
_SERVICES_BASE = ROOT / "copilot_for_consensus_tpu" / "services" / "base.py"


def test_dropping_redelivery_republish_fails_the_lane(tmp_path):
    """PR-11 regression: publish only the fresh rows (drop
    `stored_unchunked` from the republish) and the commit/publish
    crash window is back — dura-commit-publish-window must flag."""
    src = _PARSING.read_text()
    needle = 'to_publish[b["archive_id"]] = fresh + stored_unchunked'
    assert needle in src, "_store_parsed moved; update the test"
    mutated = tmp_path / "parsing_mutated.py"
    mutated.write_text(src.replace(
        needle, 'to_publish[b["archive_id"]] = fresh', 1))
    found = [f for f in analyze_files([mutated], {"dura"})
             if f.rule == "dura-commit-publish-window"]
    assert any("_store_parsed" in f.context for f in found), found
    # the unmutated file is clean under the dura group
    assert analyze_files([_PARSING], {"dura"}) == []


def test_submit_after_scheduler_insert_fails_the_lane(tmp_path):
    """PR-12 regression: a scheduler insertion before `record_submit`
    re-opens the crash window where admitted work is invisible to
    restart replay — dura-journal-order must flag."""
    src = _GEN.read_text()
    needle = "                ids = _trace.current_ids()\n"
    assert src.count(needle) == 1, "submit block moved; update the test"
    mutated = tmp_path / "generation_mutated.py"
    mutated.write_text(src.replace(
        needle, needle + "                self._sched.enqueue(prompt)\n",
        1))
    found = [f for f in analyze_files([mutated], {"dura"})
             if f.rule == "dura-journal-order"]
    assert any(f.context == "GenerationEngine.submit"
               for f in found), found
    assert analyze_files([_GEN], {"dura"}) == []


def test_swallowed_retryable_in_wave_finisher_fails_the_lane(tmp_path):
    """Contract regression: remove the finisher's re-raise after the
    transient (PublishError/RetryableError) metrics bump and the nack/
    redeliver path is silently gone — dura-ack-swallow must flag."""
    src = _SERVICES_BASE.read_text()
    needle = ('                        labels={"event": etype, '
              '"ok": "false"})\n'
              '                    raise\n')
    assert src.count(needle) == 1, "finisher catch moved; update the test"
    mutated = tmp_path / "base_mutated.py"
    mutated.write_text(src.replace(
        needle,
        '                        labels={"event": etype, '
        '"ok": "false"})\n', 1))
    found = [f for f in analyze_files([mutated], {"dura"})
             if f.rule == "dura-ack-swallow"]
    assert any("_finish_wave_envelope" in f.context for f in found), found


# -- baseline round trip + full-repo cleanliness for the dura family


def test_dura_baseline_round_trip(tmp_path, capsys):
    """dura findings ride the existing baseline machinery: a justified
    entry silences the finding; a TODO placeholder warns on a normal
    run and fails under --strict."""
    fixture = DURA_FIXTURES / "ack_swallow.py"
    found = [f for f in analyze_files([fixture], {"dura"})
             if f.rule == "dura-ack-swallow"]
    assert found
    entries = [{"rule": f.rule, "path": f.path, "context": f.context,
                "message": f.message,
                "justification": "fixture: deliberate swallow kept to "
                                 "prove the baseline round trip"}
               for f in found]
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(entries))
    args = ["--group", "dura", "--baseline", str(bl), str(fixture)]
    assert jaxlint_main(args) == 0, capsys.readouterr().out
    for e in entries:
        e["justification"] = "TODO: explain why this is deliberate"
    bl.write_text(json.dumps(entries))
    assert jaxlint_main(args) == 0          # non-strict: warn only
    assert "baseline-unjustified" in capsys.readouterr().err
    rc = jaxlint_main(["--strict"] + args)
    out = capsys.readouterr()
    assert rc == 1
    assert "baseline-unjustified" in out.out


def test_repo_dura_group_clean():
    """The full-repo dura run is clean under --strict — the acceptance
    bar for dogfooding the durability contracts over the live
    pipeline and serving planes."""
    proc = subprocess.run(
        [sys.executable, "-m", "copilot_for_consensus_tpu.analysis",
         "--group", "dura", "--strict"], cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[dura]" in proc.stderr, proc.stderr


def test_repo_is_clean_end_to_end():
    """The whole tree passes every jaxlint group (modulo the committed,
    justified baseline). --fast skips import smoke, which the suite
    itself already proves by importing everything."""
    proc = subprocess.run(
        [sys.executable, "-m", "copilot_for_consensus_tpu.analysis",
         "--fast"], cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
