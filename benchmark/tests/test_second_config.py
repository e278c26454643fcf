"""A second architecture arrives as files alone. ``data/second/`` is a
whole benchmark root of the test's own: a ``BENCHMARK.json`` with one
configuration and one cell, the configuration's file (its rotary base
is scaled by a nested ``rope_scaling``), a plain reference of that
architecture, a builder that is a subclass of the engine builder, a
traffic file and metric files with a tag of their own. ``spec`` is
pointed at that root for the length of a test; no harness file knows
of it."""
import importlib
import pathlib

import pytest

from benchmark import run as bench_run
from benchmark.harness import spec

ROOT = pathlib.Path(__file__).resolve().parent / "data" / "second"
CELL = "tiny-ntk.trickle"


@pytest.fixture()
def second_root(monkeypatch):
    monkeypatch.setattr(spec, "REPO", ROOT)
    monkeypatch.setattr(spec, "BENCH", ROOT / "benchmark")
    # its modules are found under the names its files give, beside the
    # benchmark's own (generators and readers are the benchmark's)
    for group in ("builders", "reference"):
        package = importlib.import_module(f"benchmark.{group}")
        monkeypatch.setattr(package, "__path__", list(package.__path__)
                            + [str(ROOT / "benchmark" / group)])


def rehearse(seed, trace=0):
    return bench_run.execute(bench_run.parse(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "4",
         "--trace", str(trace), "--rehearse"]))


def test_its_own_reference_finds_it_correct(second_root):
    result = rehearse(21)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 5
    assert set(result["metrics"]) == {"e2e_mean_s", "setup_s"}
    compared = result["compared"]
    assert list(result)[-1] == "compared"
    assert compared["served_tokens_compared"]["value"] >= 10
    assert compared["logit_gap_max"]["value"] <= 0.25


def test_its_per_layer_metrics_carry_its_own_tag(second_root):
    result = rehearse(22, trace=1)
    assert set(result["metrics"]) == {"requests_in_window.ntk",
                                      "decode_tok_s.ntk"}


def test_the_other_architectures_reference_finds_it_not_correct(
        second_root, monkeypatch):
    """``decoder`` reads the same weights and leaves the scaling of the
    rotary base out: the served tokens are not its tokens."""
    real = spec.load_cell

    def held_to_mistral(workload):
        cell = real(workload)
        cell["config_data"]["reference"] = "decoder"
        return cell

    monkeypatch.setattr(spec, "load_cell", held_to_mistral)
    result = rehearse(21)
    assert result["correct"] is False and result["failed"] == 0
    assert result["compared"]["logit_gap_max"]["value"] > 3 * 0.25


def test_the_builder_is_a_subclass_that_copies_nothing(second_root):
    from benchmark.builders import engine

    builder = spec.module("builders", "ntk_engine")
    assert issubclass(builder.System, engine.System)
    own = {k for k in vars(builder.System) if not k.startswith("__")}
    assert own <= {"make_dims", "make_weights", "program_config"}
    source = pathlib.Path(builder.__file__).read_text().splitlines()
    assert len(source) < 60


@pytest.mark.parametrize("key", ["builder", "reference"])
def test_a_configuration_that_does_not_name_it_is_refused(
        second_root, monkeypatch, key):
    real = spec._json

    def without(path):
        data = real(path)
        if pathlib.Path(path).name == "tiny-ntk.json":
            del data[key]
        return data

    monkeypatch.setattr(spec, "_json", without)
    with pytest.raises(SystemExit, match=key):
        spec.load_cell(CELL)
