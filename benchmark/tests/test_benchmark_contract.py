"""Every entry of ``BENCHMARK.json`` has the files its name points to,
one test case an entry: a configuration or a metric that a PR has half
added fails here. No JAX and nothing of the program is imported: a
module is looked for as a file."""
import json
import pathlib

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = {w["name"] for w in MANIFEST["workloads"]}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def names(entries):
    return [e["name"] for e in entries]


def is_module(group: str, name: str) -> bool:
    return bool(name) and (BENCH / group / f"{name}.py").is_file()


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=names(MANIFEST["configs"]))
def test_configuration_has_its_file_builder_and_reference(config):
    path = BENCH.parent / config["file"]
    assert path.is_file() and BENCH in path.parents
    data = json.loads(path.read_text())
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert is_module("builders", data.get("builder"))
    assert is_module("reference", data.get("reference"))
    for section in ("engine", "correct", "rehearsal"):
        assert isinstance(data[section], dict)
    assert any(w["config"] == config["name"]
               for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=names(MANIFEST["workloads"]))
def test_cell_has_its_configuration_and_a_traffic_file_with_a_generator(
        cell):
    assert cell["config"] in names(MANIFEST["configs"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert is_module("generators", traffic.get("generator"))


@pytest.mark.parametrize("metric", METRICS, ids=names(METRICS))
def test_metric_has_its_file_its_reader_and_real_cells(metric):
    how = json.loads(
        (BENCH / "metrics" / f"{metric['name']}.json").read_text())
    assert set(how) <= {"reader", "args"}
    assert is_module("readers", how.get("reader"))
    assert set(metric.get("workloads", ())) <= CELLS
    if "moves" in metric:
        moved = {m["name"]: m for m in MANIFEST["end_to_end"]}
        assert metric["moves"] in moved
        # every cell that reads it reports the metric it should move
        target = moved[metric["moves"]].get("workloads", CELLS)
        assert set(metric.get("workloads", target)) <= set(target)
