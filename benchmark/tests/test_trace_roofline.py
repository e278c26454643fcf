"""The trace reducer, on hand-made planes and on a small trace recorded
on the chip and kept with the benchmark; the roofline functions and
the table of peaks."""
import json
import pathlib

import pytest

from benchmark.harness import roofline, trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def planes():
    ops = [("fusion.1", 1.0, 2.0), ("while.2", 1.5, 3.0),
           ("fusion.1", 5.0, 6.0)]
    mods = [("jit__decode", 1.0, 3.0), ("jit__admit_fused", 5.0, 6.0)]
    notes = [{"name": "decode", "step_num": 7, "start": 0.9, "end": 3.6},
             {"name": "bench:submit", "step_num": None, "start": 3.7,
              "end": 4.4},
             {"name": "prefill", "step_num": 8, "start": 4.9, "end": 6.5}]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}],
            "annotations": notes, "compiles": 0, "lo": 0.0, "hi": 8.0}


def test_union_and_gaps():
    assert tr.union_length([(1, 2), (1.5, 3), (5, 6)]) == pytest.approx(3)
    assert tr.union_length([]) == 0
    assert tr.gaps_of([(1, 2), (1.5, 3), (5, 6)], 0, 8) == \
        [(0, 1), (3, 5), (6, 8)]


def test_reduce_busy_idle_op_sums_and_gap_attribution():
    r = tr.reduce_planes(planes())
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["window_s"] == pytest.approx(8.0)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(2.0)
    assert ops["while.2"] == pytest.approx(1.5)
    gaps = dict(r["idle_gaps"])
    # (0,1) and (6,8): nobody's; (3,5): its middle lies in bench:submit
    assert gaps["_no_annotation_"] == pytest.approx(3.0)
    assert gaps["bench:submit"] == pytest.approx(2.0)
    assert sum(gaps.values()) == pytest.approx(8.0 - 3.0)
    steps = {s["step_num"]: s for s in r["steps"]}
    assert steps[7]["modules"] == [("jit__decode", pytest.approx(2.0))]
    assert steps[8]["modules"] == [("jit__admit_fused",
                                    pytest.approx(1.0))]


def test_short_names():
    assert tr.short_name("%fusion.16 = (u32[1]) fusion(...)") == "fusion.16"
    assert tr.short_name("jit__decode(15645617801993290285)") == \
        "jit__decode"


def test_recorded_trace_from_the_chip():
    files = sorted(DATA.glob("*.xplane.pb"))
    assert files, "the recorded trace is kept under tests/data"
    r = tr.reduce_file(str(files[0]))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    names = {m for s in r["steps"] for m, _ in s["modules"]}
    assert "jit__decode" in names and "jit__admit_fused" in names
    kinds = {s["name"] for s in r["steps"]}
    assert {"decode", "prefill"} <= kinds
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    total_gap = sum(v for _k, v in r["idle_gaps"])
    assert total_gap <= r["window_s"] - r["busy_s"] + 1e-6


def mistral():
    d = json.loads((CONFIGS / "mistral-7b-int8.json").read_text())
    return {k: v for k, v in d.items() if isinstance(v, (int, float))}


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("_source")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_parameter_count_is_the_published_one():
    layer, head = roofline.matmul_params(mistral())
    total = layer * 32 + head + 32000 * 4096        # + the embedding
    assert total == pytest.approx(7.24e9, rel=0.005)


def test_no_share_can_pass_100_percent_at_the_cells_shapes():
    """A device that ran the program's PADDED shapes exactly at the
    chip's peaks would read under 100%, because only real tokens and
    live cache lengths are counted."""
    dims, peak = mistral(), roofline.peaks("TPU v5 lite")
    for real, bucket, rows in ((1500, 2048, 4), (256, 256, 8),
                               (2048, 2048, 1), (600, 1024, 3)):
        need = roofline.prefill_flops(dims, [real] * rows)
        padded_rows = 1 << (rows - 1).bit_length()
        done = roofline.prefill_flops(dims, [bucket] * padded_rows)
        fastest = done / peak["bf16_flops_per_s"]
        assert 100.0 * need / peak["bf16_flops_per_s"] / fastest <= 100.0 + 1e-9
    for live in ([300] * 8, [2300] * 8, [2048], []):
        need = roofline.decode_bytes(dims, live, 1.0, 2.0)
        slots_full = roofline.decode_bytes(dims, [2304] * 8, 1.0, 2.0)
        assert need <= slots_full
        fastest = slots_full / peak["hbm_bytes_per_s"]
        assert 100.0 * need / peak["hbm_bytes_per_s"] / fastest <= 100.0 + 1e-9
    # the sliding window bounds what attention reads and computes
    assert roofline.decode_bytes(dims, [10_000], 1.0, 2.0) == \
        roofline.decode_bytes(dims, [4096], 1.0, 2.0)
    assert roofline.prefill_flops(dims, [8192]) < \
        2 * roofline.prefill_flops(dims, [4096]) * 1.2
