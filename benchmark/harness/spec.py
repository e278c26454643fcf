"""Finds a cell's files by the names in BENCHMARK.json. A later PR adds
a configuration, its builder and its plain reference, a traffic mix, a
metric or a reader as new files; no file here lists them."""

from __future__ import annotations

import importlib
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    bench = _json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in bench["configs"]}
    cell["config_file"] = configs[cell["config"]]["file"]
    cell["config_data"] = _json(REPO / cell["config_file"])
    # no default for either: a cell is never built by, or held
    # against, a module that its configuration did not name
    for key, group in (("builder", "builders"), ("reference", "reference")):
        if not cell["config_data"].get(key):
            raise SystemExit(f"{cell['config_file']} has no {key!r}: it "
                             f"names a module of benchmark/{group}/")
    cell["traffic_data"] = _json(
        BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell


def metric_files(workload: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind (``end_to_end`` for --trace 0,
    ``per_layer`` for --trace 1) as BENCHMARK.json lists them: name,
    unit and cells come from there, reader and arguments from
    ``benchmark/metrics/<name>.json``. A metric without ``workloads``
    belongs to every cell (end-to-end), or to every cell that reports
    the end-to-end metric it moves (per-layer)."""
    bench = _json(REPO / "BENCHMARK.json")

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    mine = {m["name"] for m in bench["end_to_end"] if listed(m)}
    out = []
    for m in bench[kind]:
        if listed(m) and (kind == "end_to_end" or m["moves"] in mine):
            how = _json(BENCH / "metrics" / f"{m['name']}.json")
            out.append({"name": m["name"], "unit": m["unit"],
                        "reader": how["reader"],
                        "args": how.get("args", {})})
    return out


def module(group: str, name: str):
    """benchmark/<group>/<name>.py, found by the name a data file gives."""
    return importlib.import_module(f"benchmark.{group}.{name}")
