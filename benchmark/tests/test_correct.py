"""How ``correct`` is decided, at a size a test run can hold (the
``tiny`` rehearsal sizes, on the CPU).

1. The control: the reference computed in the precision below the one
   the configuration states (int4 weights for int8, an fp8 cache for
   bfloat16) reads gaps far above a sound run's, through the same
   comparison the benchmark makes.
2. The rest of a run, driven past the harness's look for a chip, with
   the timed path broken underneath (every decoded token altered where
   the engine fetches it): ``correct`` comes out false. The same run
   unbroken comes out true.
"""
import pytest

from benchmark import run as bench_run


def args(workload, seed=11):
    return bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", "4", "--trace", "0",
                            "--rehearse"])


@pytest.fixture(scope="module")
def sound():
    captured = {}
    from benchmark.harness import correct
    real = correct.logit_gaps

    def spy(weights, dims, sample, lowers=()):
        out = real(weights, dims, sample, ("int4", "fp8kv"))
        captured.update(out)
        return out

    correct.logit_gaps = spy
    try:
        result = bench_run.execute(args("mistral-7b-int8.qa-steady"))
    finally:
        correct.logit_gaps = real
    return result, captured


def test_sound_run_is_correct(sound):
    result, gaps = sound
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 5
    assert gaps["tokens"] >= 10


def test_control_in_lower_precision_reads_far_above_a_sound_run(sound):
    """int4 weights break both limits of the rehearsal sizes (set, like
    the chip's, between the sound runs' largest and the control's
    smallest: configs/*.json, ``rehearsal.correct``); an fp8 cache,
    which moves a 2-layer model little, still reads above the sound
    run."""
    import json
    import pathlib

    _result, gaps = sound
    cfg = json.loads((pathlib.Path(bench_run.__file__).parent / "configs"
                      / "mistral-7b-int8.json").read_text())
    limits = cfg["rehearsal"]["correct"]
    int4, fp8 = gaps["control_int4"], gaps["control_fp8kv"]
    assert gaps["logit_gap_max"] <= limits["logit_gap_max"]
    assert gaps["logit_gap_mean"] <= limits["logit_gap_mean"]
    assert int4["logit_gap_max"] > 3 * limits["logit_gap_max"]
    assert int4["logit_gap_mean"] > 3 * limits["logit_gap_mean"]
    assert fp8["logit_gap_mean"] > gaps["logit_gap_mean"]


def test_broken_timed_path_is_not_correct(monkeypatch):
    from copilot_for_consensus_tpu.engine import generation

    real = generation._host_fetch

    def altered(x):
        out = real(x)
        if out.ndim == 2:                 # [steps, slots] decoded tokens
            out = (out + 1) % 400 + 3
        return out

    monkeypatch.setattr(generation, "_host_fetch", altered)
    result = bench_run.execute(args("mistral-7b-int8.qa-steady", seed=12))
    assert result["correct"] is False


def test_the_programs_own_fp8_cache_is_not_correct(monkeypatch):
    """The control that the program has a path for: the same run with
    the engine's cache in float8_e4m3fn, where the configuration states
    bfloat16, reads ``logit_gap_mean`` over its limit."""
    from benchmark.harness import spec

    real = spec.load_cell

    def with_fp8_cache(workload):
        cell = real(workload)
        cell["config_data"]["engine"]["kv_dtype"] = "float8_e4m3fn"
        return cell

    monkeypatch.setattr(spec, "load_cell", with_fp8_cache)
    result = bench_run.execute(args("mistral-7b-int8.qa-steady", seed=13))
    assert result["correct"] is False and result["failed"] == 0
