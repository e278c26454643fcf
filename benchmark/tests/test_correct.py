"""How ``correct`` is decided, at a size a test run can hold (the
``tiny`` rehearsal sizes, on the CPU), for every configuration that
``BENCHMARK.json`` lists: one added later is held to these tests by
being listed, in one cell of its own (the last it has).

1. The control: the configuration's reference computed in each
   precision below the stated one that it offers (``LOWERS``; for
   ``decoder`` int4 weights for int8, an fp8 cache for bfloat16) reads
   gaps above a sound run's, through the same comparison the benchmark
   makes, and each limit is broken three times over by one of them.
2. The rest of a run, driven past the harness's look for a chip, with
   the timed path broken underneath (every decoded token altered where
   the engine fetches it): ``correct`` comes out false. The same run
   unbroken comes out true.
"""
import json

import pytest

from benchmark import run as bench_run
from benchmark.harness import spec

_BENCH = json.loads((spec.REPO / "BENCHMARK.json").read_text())
#: configuration -> the cell it is tested in
CELLS = {w["config"]: w["name"] for w in _BENCH["workloads"]}
CONFIGS = [c["name"] for c in _BENCH["configs"]]


def args(workload, seed=11):
    return bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", "4", "--trace", "0",
                            "--rehearse"])


@pytest.fixture(scope="module", params=CONFIGS)
def sound(request):
    """(result, gaps with every control, rehearsal limits) of one
    sound run of the configuration's cell."""
    captured = {}
    from benchmark.harness import correct
    real = correct.logit_gaps

    def spy(ref, weights, dims, sample, lowers=()):
        out = real(ref, weights, dims, sample, ref.LOWERS)
        captured.update(out, lowers=ref.LOWERS)
        return out

    correct.logit_gaps = spy
    try:
        result = bench_run.execute(args(CELLS[request.param]))
    finally:
        correct.logit_gaps = real
    cfg = spec.load_cell(CELLS[request.param])["config_data"]
    limits = dict(cfg["correct"], **cfg["rehearsal"].get("correct", {}))
    return result, captured, limits


def test_sound_run_is_correct(sound):
    result, gaps, _limits = sound
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 5
    assert gaps["tokens"] >= 10


def test_control_in_lower_precision_reads_far_above_a_sound_run(sound):
    """The limits of the rehearsal sizes are set, like the chip's,
    between the sound runs' largest and a control's smallest
    (configs/*.json, ``rehearsal.correct``). Every control reads above
    the sound run (for ``decoder`` an fp8 cache, which moves a 2-layer
    model little, does no more than that), and each limit is passed
    three times over by some control (for ``decoder`` int4 weights
    break both)."""
    _result, gaps, limits = sound
    controls = [gaps[f"control_{m}"] for m in gaps["lowers"]]
    assert controls
    for name in ("logit_gap_max", "logit_gap_mean"):
        assert gaps[name] <= limits[name]
        assert max(c[name] for c in controls) > 3 * limits[name]
    for c in controls:
        assert c["logit_gap_mean"] > gaps["logit_gap_mean"]


@pytest.mark.parametrize("config", CONFIGS)
def test_broken_timed_path_is_not_correct(monkeypatch, config):
    from copilot_for_consensus_tpu.engine import generation

    real = generation._host_fetch

    def altered(x):
        out = real(x)
        if out.ndim == 2:                 # [steps, slots] decoded tokens
            out = (out + 1) % 400 + 3
        return out

    monkeypatch.setattr(generation, "_host_fetch", altered)
    result = bench_run.execute(args(CELLS[config], seed=12))
    assert result["correct"] is False


def test_the_programs_own_fp8_cache_is_not_correct(monkeypatch):
    """The control that the program has a path for: the same run with
    the engine's cache in float8_e4m3fn, where the configuration states
    bfloat16, reads ``logit_gap_mean`` over its limit."""
    real = spec.load_cell

    def with_fp8_cache(workload):
        cell = real(workload)
        cell["config_data"]["engine"]["kv_dtype"] = "float8_e4m3fn"
        return cell

    monkeypatch.setattr(spec, "load_cell", with_fp8_cache)
    result = bench_run.execute(args("mistral-7b-int8.qa-steady", seed=13))
    assert result["correct"] is False and result["failed"] == 0
