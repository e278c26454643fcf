"""Self time by scope: on hand-made lines, and on a small trace
recorded on the chip at the ``tiny`` sizes with the program's scopes
and host phases in it (``data/tiny_scopes.xplane.pb``)."""
import pathlib

import pytest

from benchmark.harness import scope_reduce as sr, trace_reduce as tr
from copilot_for_consensus_tpu.obs.profile import HOST_PHASES, SCOPES

TRACE = pathlib.Path(__file__).resolve().parent / "data" / \
    "tiny_scopes.xplane.pb"


def test_self_time_counts_nested_events_once():
    #  0        10: while          (self 10 - 6 - 2 = 2)
    #    1    7:    while inside   (self 6 - 2 - 3 = 1)
    #      2 4:       fusion a     (self 2)
    #      4  7:      fusion b     (self 3)
    #        8 10:  slice          (self 2)
    #            12 15: copy, on its own
    events = [(4, 7), (0, 10), (12, 15), (1, 7), (8, 10), (2, 4)]
    assert sr.self_times(events) == [3, 2, 3, 1, 2, 2]
    assert sum(sr.self_times(events)) == 10 + 3     # what the line covers
    assert sr.self_times([]) == []


def test_innermost_scope_wins_and_no_path_is_unscoped():
    scopes = ("unembed", "norm_rope", "ffn")
    assert sr.scope_of("jit(_decode)/while/body/unembed/norm_rope/mul:",
                       scopes) == "norm_rope"
    assert sr.scope_of("jit(_decode)/while/body/ffn/jit(silu)/logistic:",
                       scopes) == "ffn"
    assert sr.scope_of("jit(_decode)/while:", scopes) == sr.UNSCOPED
    assert sr.scope_of(None, scopes) == sr.UNSCOPED
    assert sr.scope_of("cache['v']:", scopes) == sr.UNSCOPED


def test_events_go_to_the_program_that_holds_them():
    ps = 10 ** 12
    plane = {"name": "/device:TPU:0",
             "modules": [(0, 10 * ps, "jit__decode", None),
                         (20 * ps, 24 * ps, "jit__admit_fused", None)],
             "ops": [(0, 10 * ps, "while.1", "jit(_decode)/while:"),
                     (1 * ps, 7 * ps, "fusion.2",
                      "jit(_decode)/while/body/ffn/dot_general:"),
                     (20 * ps, 23 * ps, "fusion.9",
                      "jit(_admit_fused)/attn/exp:"),
                     (30 * ps, 31 * ps, "copy.1", None)]}
    table = sr.reduce_planes([plane], ("ffn", "attn"))
    assert table["jit__decode"]["device_s"] == pytest.approx(10)
    assert table["jit__decode"]["self_s"] == {
        sr.UNSCOPED: pytest.approx(4), "ffn": pytest.approx(6)}
    assert table["jit__admit_fused"]["self_s"] == {
        "attn": pytest.approx(3)}
    assert table[sr.OUTSIDE]["self_s"] == {sr.UNSCOPED: pytest.approx(1)}


def test_recorded_trace_has_every_scope_and_adds_up():
    assert TRACE.is_file(), "the recorded trace is kept under tests/data"
    assert TRACE.stat().st_size < 1_000_000
    planes = sr.read_device_planes(str(TRACE))
    assert len(planes) == 1
    table = sr.reduce_planes(planes, SCOPES)
    decode, admit = table["jit__decode"], table["jit__admit_fused"]
    # nested whiles counted once: the self times of a program's ops add
    # up to the device time of the program (the rest is the gaps
    # between ops, larger at these tiny sizes than at real ones)
    for prog in (decode, admit):
        covered = sum(prog["self_s"].values())
        assert 0.85 * prog["device_s"] <= covered <= prog["device_s"]
    plain = sum(e - s for s, e, _n, _p in planes[0]["ops"]) * 1e-12
    assert plain > 1.5 * sum(sum(p["self_s"].values())
                             for p in table.values())
    # every scope is found; greedy sampling's argmax is fused into the
    # unembed matmul's fusion by the chip's compiler in the decode
    # program, and an admission reads no cache prefix
    assert set(SCOPES) - {"sample"} <= set(decode["self_s"])
    assert set(SCOPES) - {"kv_prefix"} <= set(admit["self_s"])
    # at these sizes copies and loop control weigh most: 29% here
    assert decode["self_s"][sr.UNSCOPED] < 0.35 * sum(
        decode["self_s"].values())


def test_recorded_trace_names_the_idle_gaps():
    reduced = tr.reduce_file(str(TRACE))
    owners = dict(reduced["idle_gaps"])
    assert set(owners) & set(HOST_PHASES)
    assert set(owners) <= set(HOST_PHASES) | {"decode", "prefill",
                                              "_no_annotation_"}
    assert owners.get("_no_annotation_", 0.0) < \
        0.10 * sum(owners.values())


def test_device_ops_are_named_by_scope_with_totals_and_order_kept():
    """``breakdown.device_ops`` is what the writer of the next issue
    reads of a trace: an op is ``<scope>/<HLO name>``, and the seconds
    and their order are those of the bare names."""
    bare = tr.reduce_planes(tr.read_planes(str(TRACE)))["device_ops"]
    named = tr.reduce_file(str(TRACE))["device_ops"]
    assert [v for _k, v in named] == [v for _k, v in bare]
    assert [k.split("/", 1)[1] for k, _v in named] == [k for k, _v in bare]
    scopes = {k.split("/", 1)[0] for k, _v in named}
    assert scopes <= set(SCOPES) | {sr.UNSCOPED}
    assert scopes & set(SCOPES)
    # the layer scan's own loop has no scope; the matmul fusions have
    assert named[0][0].startswith(f"{sr.UNSCOPED}/while.")
    assert any(k.startswith("ffn/fusion.") for k, _v in named)


def test_an_op_name_takes_the_scope_it_spent_most_time_under():
    plane = {"ops": [(0, 10, "fusion.7", "jit(_decode)/ffn/dot_general:"),
                     (20, 23, "fusion.7", "jit(_admit_fused)/attn/exp:"),
                     (30, 31, "copy.1", None)]}
    assert sr.scope_of_ops([plane], ("ffn", "attn")) == {
        "fusion.7": "ffn", "copy.1": sr.UNSCOPED}
    reduced = tr.reduce_planes(
        {"devices": [{"name": "/device:TPU:0", "modules": [],
                      "ops": [("fusion.7", 0.0, 1.0), ("copy.1", 2.0, 2.5)]}],
         "annotations": [], "compiles": 0, "lo": 0.0, "hi": 3.0},
        scope_of_op={"fusion.7": "ffn"})
    assert reduced["device_ops"] == [["ffn/fusion.7", 1.0], ["copy.1", 0.5]]
