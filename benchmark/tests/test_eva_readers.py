"""The bytes an EVA decode step has to read (``harness/eva_roofline``)
and the three readers that came with the EvaByte cell, on hand-made
runs: each gives the number its arithmetic says, and gives nothing,
without raising, for a program that lacks what it reads."""
import json
import pathlib

import pytest

from benchmark.harness import eva_roofline
from benchmark.readers import eva_decode_hbm, scope_share_of, step_field_rate

CONFIG = json.loads((pathlib.Path(__file__).resolve().parents[1]
                     / "configs" / "evabyte-6.5b-int8.json").read_text())
DIMS = dict({k: v for k, v in CONFIG.items() if not isinstance(v, dict)},
            head_dim=128)


def test_bytes_of_a_decode_step_at_the_published_widths():
    # 4 x 4096^2 + 3 x 4096 x 11008 a layer, 32 layers, and the output
    # matrix of 8 heads x 320 ids: ISSUE 29's 6.48e9 + 10.5e6
    assert eva_roofline.weight_params(DIMS) == pytest.approx(
        32 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 2560)
    # a key and a value of 32 heads x 128 in 32 layers, bfloat16
    assert eva_roofline.state_bytes_per_column(DIMS, 2.0) == 524288
    assert eva_roofline.decode_bytes(DIMS, 1000, 1.0, 2.0) == \
        eva_roofline.weight_params(DIMS) + 1000 * 524288
    assert eva_roofline.decode_bytes(DIMS, 1000, 0.0, 2.0) == 524288000


def run_with(steps, trace_steps, planes=None):
    return {"records": {"steps": steps,
                        "engine": {"steps_per_dispatch": 8}},
            "trace": {"steps": trace_steps, "device_planes": planes},
            "dims": DIMS, "device": {"kind": "TPU v5 lite"},
            "window": (10.0, 20.0)}


def test_decode_hbm_share_reads_the_programs_own_counts():
    step = {"seq": 5, "kind": "decode", "t_end": 12.0,
            "window_tokens": 3000, "summary_tokens": 1000,
            "windows_compacted": 1}
    trace = [{"name": "decode", "step_num": 5,
              "modules": [("jit__decode_eva", 0.16)]}]
    args = {"step": "decode", "module": "jit__decode_eva",
            "weight_bytes": 1.0, "state_bytes": 2.0}
    need = 8 * (eva_roofline.weight_params(DIMS) + 4000 * 524288)
    assert eva_decode_hbm.read(run_with([step], trace), args) == \
        pytest.approx(100 * need / 819e9 / 0.16)
    # a program that writes no such counts, or no trace: no value
    bare = {k: v for k, v in step.items() if "tokens" not in k}
    assert eva_decode_hbm.read(run_with([bare], trace), args) is None
    assert eva_decode_hbm.read(run_with([step], []), args) is None
    # the attention alone needs a scope table; without planes, none
    assert eva_decode_hbm.read(run_with([step], trace),
                               dict(args, scope="attn")) is None


def test_compactions_per_second_of_the_counted_interval():
    steps = [{"t_end": t, "windows_compacted": n}
             for t, n in ((9.0, 5), (11.0, 2), (15.0, 0), (19.5, 3),
                          (20.5, 7))]
    args = {"field": "windows_compacted"}
    assert step_field_rate.read(run_with(steps, []), args) == 0.5
    assert step_field_rate.read(
        run_with([{"t_end": 11.0}], []), args) is None


def test_scope_share_of_needs_a_trace_and_the_programs_tuple():
    args = {"module": "jit__decode_eva", "scope": "kv_compact",
            "declared": "EVA_SCOPES"}
    assert scope_share_of.read(run_with([], []), args) is None
    assert scope_share_of.read(
        run_with([], []), dict(args, declared="NO_SUCH_TUPLE")) is None
    # ops are (start ps, end ps, name, op_name path)
    planes = [{"modules": [(0, 100, "jit__decode_eva", "")],
               "ops": [(0, 60, "fusion.1", "jit(_decode_eva)/attn/dot"),
                       (60, 100, "fusion.2",
                        "jit(_decode_eva)/while/cond/kv_compact/reduce")]}]
    assert scope_share_of.read(run_with([], [], planes), args) == \
        pytest.approx(40.0)
