"""What a step of the mixed-layer configuration has to do
(``harness/cmda_roofline``), against hand arithmetic at the tiny and at
the published widths, and the readers that came with its cell, on
hand-made runs: each gives the number its arithmetic says, and gives
nothing, without raising, for a program that lacks what it reads."""
import json
import pathlib

import pytest

from benchmark.harness import cmda_roofline
from benchmark.readers import (
    cmda_decode_hbm,
    cmda_prefill_mxu,
    scope_shares_of,
    step_field_ratio,
)

HERE = pathlib.Path(__file__).resolve().parent
CONFIG = json.loads((HERE.parent / "configs"
                     / "command-a-plus-218b-a25b-int8.json").read_text())
DIMS = {k: v for k, v in CONFIG.items()
        if not isinstance(v, dict) or k == "held"}
#: tests/test_mixed_engine.py's sizes: d 64, 8 query heads on 2
#: key/value heads of 16, two periods of 3 window layers (16 positions)
#: and a global one, 8 experts of 32 (2 a token; 4 held here) beside 2
#: shared ones, 512 ids
TINY = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
            head_dim=16, intermediate_size=32, num_hidden_layers=8,
            layer_types=["sliding_attention"] * 3 + ["full_attention"]
            + ["sliding_attention"] * 3 + ["full_attention"] + ["x"] * 4,
            sliding_window=16, num_experts=4,
            held={"first_expert": 2, "router_experts": 8},
            num_experts_per_tok=2, num_shared_experts=2, vocab_size=512)


def test_the_tiny_size_by_hand():
    # wq and wo 64 x 128 each, wk and wv 64 x 32 each
    assert cmda_roofline.attention_params(TINY) == 2 * 8192 + 2 * 2048
    assert cmda_roofline.expert_params(TINY) == 3 * 64 * 32
    assert cmda_roofline.layer_counts(TINY) == (6, 2)
    assert cmda_roofline.position_bytes(TINY, 2.0) == 2 * 2 * 16 * 2
    fixed = (8 * (20480 + 2 * 6144 + 4 * 64 * 8)   # shared, routers of 8
             + 2 * 64 * 512)                        # the tied matrix
    assert cmda_roofline.fixed_decode_bytes(TINY) == fixed
    # a dispatch of 8 steps that touched 11 held experts, whose tokens
    # attended to 5,000 positions in a full layer and 900 in a window
    # layer
    assert cmda_roofline.decode_bytes(TINY, 8, 11, 5000, 900, 2.0) == \
        8 * fixed + 11 * 6144 + 2 * 5000 * 128 + 6 * 900 * 128
    for part, want in (("experts", 11 * 6144), ("full", 2 * 5000 * 128),
                       ("window", 6 * 900 * 128)):
        assert cmda_roofline.decode_bytes(TINY, 8, 11, 5000, 900, 2.0,
                                          part) == want
    # 100 tokens, 3,000 causal pairs of which 1,400 lie inside the
    # window, 37 token-expert pairs of held experts, 2 rows that yield a
    # token: 4 x 8 heads x 16 a pair and layer
    per_token = 8 * (20480 + 2 * 6144 + 64 * 8)
    assert cmda_roofline.prefill_flops(TINY, 100, 3000, 1400, 37, 2) == \
        2 * per_token * 100 + 2 * 6144 * 37 + 2 * 64 * 512 * 2 \
        + 512 * (6 * 1400 + 2 * 3000)


def test_the_published_widths_against_the_issues_arithmetic():
    """ISSUE 43: attention 142.6M a layer, an expert 50.33M, a cached
    position 4,096 B a layer; 16 experts of the router's 128 and 32,768
    rows of the vocabulary held; two periods of the 3:1 pattern."""
    assert round(cmda_roofline.attention_params(DIMS) / 1e6, 1) == 142.6
    assert cmda_roofline.expert_params(DIMS) == 3 * 4096 * 4096
    assert cmda_roofline.position_bytes(DIMS, 2.0) == 4096
    assert cmda_roofline.layer_counts(DIMS) == (6, 2)
    assert DIMS["held"]["router_experts"] == 128
    assert DIMS["num_experts"] == 16 and DIMS["vocab_size"] == 32768
    assert DIMS["sliding_window"] == 4096
    assert len(DIMS["layer_types"]) == 32          # published, whole
    # what the chip holds outside the cache, at the served bytes: the
    # issue's 9.47 GB, of which a step reads all but the experts nobody
    # chose: 5.4 GB whatever it chooses
    weights = 8 * (142.6e6 + 4 * 50.33e6 + 4 * 4096 * 128
                   + 16 * 50.33e6) + 2 * 32768 * 4096
    assert 9.4e9 < weights < 9.5e9
    fixed = cmda_roofline.fixed_decode_bytes(DIMS)
    assert fixed == pytest.approx(weights - 8 * 16 * 50.33e6, rel=1e-3)
    # eight sequences of 24,576 positions: the full layers' keys and
    # values 1.6e9 B a step, the window layers' 0.8e9 whatever the
    # length
    step = cmda_roofline.decode_bytes(DIMS, 1, 0, 8 * 24576, 8 * 4096,
                                      2.0)
    assert step - fixed == 2 * 8 * 24576 * 4096 + 6 * 8 * 4096 * 4096
    # an admission token: 6.3 GFLOP in matrices (ISSUE 43), with its
    # pair of held experts
    per_token = cmda_roofline.prefill_flops(DIMS, 1, 0, 0, 8, 0)
    assert 6.0e9 < per_token < 6.6e9


def run_with(steps, trace_steps, planes=None, dims=TINY):
    return {"records": {"steps": steps,
                        "engine": {"steps_per_dispatch": 8}},
            "trace": {"steps": trace_steps, "device_planes": planes},
            "dims": dims, "device": {"kind": "TPU v5 lite"},
            "window": (10.0, 20.0)}


DEC, ADM = "jit__decode_mixed", "jit__admit_mixed"


def test_decode_hbm_shares_read_the_programs_own_counts():
    step = {"seq": 5, "kind": "decode", "t_end": 12.0, "rows": 3,
            "experts_touched": 11, "live_tokens": 5000,
            "window_live_tokens": 900}
    trace = [{"name": "decode", "step_num": 5, "modules": [(DEC, 0.002)]}]
    args = {"step": "decode", "module": DEC, "state_bytes": 2.0}
    need = cmda_roofline.decode_bytes(TINY, 8, 11, 5000, 900, 2.0)
    assert cmda_decode_hbm.read(run_with([step], trace), args) == \
        pytest.approx(100 * need / 819e9 / 0.002)
    # a program that writes no such counts, or no trace: no value
    bare = {k: v for k, v in step.items() if k != "window_live_tokens"}
    assert cmda_decode_hbm.read(run_with([bare], trace), args) is None
    assert cmda_decode_hbm.read(run_with([step], []), args) is None
    # one scope against its own bytes needs the device's planes
    scoped = dict(args, scope="attn_window", part="window",
                  declared="MIXED_SCOPES")
    assert cmda_decode_hbm.read(run_with([step], trace), scoped) is None
    planes = [{"modules": [(0, 2_000_000_000, DEC, "")],
               "ops": [(0, 400_000_000, "dense_decode_attention.1",
                        "jit(_decode_mixed)/while/body/attn_window/"
                        "dense_decode_attention"),
                       (400_000_000, 500_000_000,
                        "dense_decode_attention.2",
                        "jit(_decode_mixed)/while/body/attn_full/"
                        "dense_decode_attention"),
                       (500_000_000, 1_000_000_000, "fusion.1",
                        "jit(_decode_mixed)/while/body/shared_experts/"
                        "dot_general"),
                       (1_000_000_000, 2_000_000_000, "grouped_qmatmul.1",
                        "jit(_decode_mixed)/while/body/moe_experts/"
                        "grouped_qmatmul")]}]
    run = run_with([step], trace, planes)
    assert cmda_decode_hbm.read(run, scoped) == \
        pytest.approx(100 * 6 * 900 * 128 / 819e9 / 0.0004)
    assert cmda_decode_hbm.read(
        run, dict(scoped, scope="attn_full", part="full")) == \
        pytest.approx(100 * 2 * 5000 * 128 / 819e9 / 0.0001)
    assert cmda_decode_hbm.read(
        run, dict(scoped, declared="NO_SUCH_TUPLE")) is None
    # the device shares of a step, from the same planes
    for scope, want in (("attn_window", 20.0), ("attn_full", 5.0),
                        ("shared_experts", 25.0),
                        (["moe_route", "moe_experts"], 50.0)):
        assert scope_shares_of.read(
            run, {"module": DEC, "scope": scope,
                  "declared": "MIXED_SCOPES"}) == pytest.approx(want)


def test_prefill_mxu_share_reads_the_pairs_the_program_counted():
    step = {"seq": 7, "kind": "prefill", "t_end": 12.0, "tokens": 100,
            "attn_pairs": 3000, "window_attn_pairs": 1400,
            "expert_rows": 37, "new_tokens": 2}
    trace = [{"name": "prefill", "step_num": 7, "modules": [(ADM, 0.001)]}]
    args = {"step": "prefill", "module": ADM}
    need = cmda_roofline.prefill_flops(TINY, 100, 3000, 1400, 37, 2)
    assert cmda_prefill_mxu.read(run_with([step], trace), args) == \
        pytest.approx(100 * need / 197e12 / 0.001)
    bare = {k: v for k, v in step.items() if k != "window_attn_pairs"}
    assert cmda_prefill_mxu.read(run_with([bare], trace), args) is None
    assert cmda_prefill_mxu.read(run_with([step], []), args) is None


def test_what_the_window_layers_read_of_what_is_live():
    steps = [{"t_end": t, "kind": k, "window_tokens_read": s,
              "live_tokens": n}
             for t, k, s, n in ((9.0, "decode", 9, 9),         # before it
                                (11.0, "decode", 36864, 100000),
                                (12.0, "prefill", 0, 0),
                                (15.0, "decode", 36864, 160000),
                                (20.5, "decode", 9, 9))]       # after it
    args = {"num": "window_tokens_read", "den": "live_tokens",
            "kind": "decode", "scale": 100.0}
    assert step_field_ratio.read(run_with(steps, []), args) == \
        pytest.approx(100 * 73728 / 260000)
    assert step_field_ratio.read(
        run_with([{"t_end": 11.0, "kind": "decode"}], []), args) is None


def test_every_metric_of_the_cell_names_a_reader_that_takes_its_arguments():
    """Each ``.cmda`` metric file's reader, handed a run with no trace
    and no counts (what the parent's program gives), returns nothing
    and does not raise; none can be above 100% by what it divides."""
    from benchmark.harness import spec

    cell = "command-a-plus-218b-a25b-int8.summarize-mixed-24k"
    names = [m["name"] for m in spec.metric_files(cell, "per_layer")]
    assert len(names) == 14 and all(n.endswith(".cmda") for n in names)
    run = dict(run_with([], []), trace=None, drive={"late_s": []},
               compile_times=[], seconds=51.0, setup_s=1.0,
               device={"kind": "TPU v5 lite", "memory_peak_bytes": 0})
    run["records"]["requests"] = []
    run["records"]["engine_requests"] = []
    for m in spec.metric_files(cell, "per_layer"):
        if m["reader"] == "compiles":
            continue            # a host count: it reads without a trace
        assert spec.module("readers", m["reader"]).read(
            run, m["args"]) is None, m["name"]
    ends = [m["name"] for m in spec.metric_files(cell, "end_to_end")]
    assert ends == ["out_tok_s", "setup_s"]


# ---------------------------------------------------------------------------
# a trace recorded on the chip: ``--rehearse --trace 1 --seconds 5`` of
# the cell on a TPU v5 lite (PR 43, seed 11), trimmed by
# tools/trim_trace.py and cut by tools/cut_trace.py to its first 45 ms
# ---------------------------------------------------------------------------

# (named to sort after tiny_qa.xplane.pb: test_trace_roofline.py reads
# the first trace of the directory)
TRACE = HERE / "data" / "tiny_window.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    from benchmark.harness import trace_reduce

    return trace_reduce.reduce_file(str(TRACE))


def test_the_recorded_trace_holds_both_programs_and_their_scopes(recorded):
    from benchmark.harness import scope_reduce
    from copilot_for_consensus_tpu.obs import profile

    names = {n for st in recorded["steps"] for n, _d in st["modules"]}
    assert {DEC, ADM} <= names
    table = scope_reduce.reduce_planes(
        recorded["device_planes"],
        tuple(profile.SCOPES) + tuple(profile.MIXED_SCOPES))
    for prog in (DEC, ADM):
        for scope in ("attn_window", "attn_full", "ring_write", "kv_write",
                      "shared_experts", "moe_route", "moe_experts", "qkv",
                      "unembed"):
            assert table[prog]["self_s"].get(scope, 0) > 0, (prog, scope)
        # the experts lie under no ``ffn`` here, and nothing of the
        # latent-attention programs' scopes is in these
        assert not set(table[prog]["self_s"]) & {"ffn", "latent_expand",
                                                  "mhc", "indexer"}
    # the dispatch's own columns and the fold stay under ``attn``
    assert table[DEC]["self_s"].get("attn", 0) > 0
    ops = {op[2] for plane in recorded["device_planes"]
           for op in plane["ops"]}
    assert any("grouped_qmatmul" in o for o in ops)
    assert any("flash_attention" in o for o in ops)


def test_the_scope_readers_on_the_recorded_trace(recorded):
    run = run_with([], recorded["steps"], recorded["device_planes"])
    shares = {}
    for prog in (DEC, ADM):
        for scope in ("attn_window", "attn_full", "shared_experts",
                      ["moe_route", "moe_experts"]):
            shares[prog, str(scope)] = scope_shares_of.read(
                run, {"module": prog, "scope": scope,
                      "declared": "MIXED_SCOPES"})
    assert all(0 < v < 100 for v in shares.values()), shares
    for prog in (DEC, ADM):
        assert sum(v for (p, _s), v in shares.items() if p == prog) < 100
