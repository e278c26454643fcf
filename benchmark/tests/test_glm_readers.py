"""What a step of the selected-latent-attention configuration has to do
(``harness/glm_roofline``), against hand arithmetic at the tiny and at
the published widths, and the readers that came with its cell, on
hand-made runs and on a trace recorded on the chip: each gives the
number its arithmetic says, and gives nothing, without raising, for a
program that lacks what it reads."""
import json
import pathlib

import pytest

from benchmark.harness import glm_roofline, scope_reduce, trace_reduce
from benchmark.readers import (
    glm_decode_hbm,
    glm_prefill_mxu,
    scope_shares_of,
    step_field_ratio,
)

HERE = pathlib.Path(__file__).resolve().parent
CONFIG = json.loads((HERE.parent / "configs"
                     / "glm5-744b-a40b-int8.json").read_text())
DIMS = {k: v for k, v in CONFIG.items()
        if not isinstance(v, (dict, list)) or k == "held"}
#: tests/test_glm_dsa_engine.py's sizes: d 64, 4 heads, latent 32 + 8,
#: queries through 48, 2 index heads of 16, values of 24, 8 experts of
#: 32 (2 a token; 4 held here) beside a shared one, one dense layer of
#: 160 and two expert layers, 512 ids
TINY = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=24, index_n_heads=2, index_head_dim=16,
            index_topk=24, n_routed_experts=4,
            held={"first_expert": 2, "router_experts": 8},
            num_experts_per_tok=2, n_shared_experts=1,
            moe_intermediate_size=32, intermediate_size=160,
            first_k_dense_replace=1, num_hidden_layers=3, vocab_size=512)


def test_the_tiny_size_by_hand():
    # attention 64 x 48 + 48 x 4 x 24 + 64 x 40 + 4 x 24 x 64 and the
    # indexer's 48 x 2 x 16 + 64 x 16 in int8; wkv_b 32 x 4 x 40 and
    # the head weights 64 x 2 in bfloat16
    assert glm_roofline.attention_params(TINY) == (
        3072 + 4608 + 2560 + 6144 + 1536 + 1024, 5120 + 128)
    assert glm_roofline.expert_params(TINY) == 3 * 64 * 32
    assert glm_roofline.state_bytes_per_row(TINY, 2.0) == (3 * 40 * 2,
                                                           3 * 16 * 2)
    fixed = (3 * (18944 + 2 * 5248)
             + 3 * 64 * 160                         # the dense SwiGLU
             + 2 * (3 * 64 * 32 + 4 * 64 * 8)       # shared, routers of 8
             + 64 * 512)                            # the head
    assert glm_roofline.fixed_decode_bytes(TINY) == fixed
    # a dispatch of 8 steps that touched 11 held experts, whose tokens
    # could read 5,000 positions and read 900
    assert glm_roofline.decode_bytes(TINY, 8, 11, 5000, 900, 2.0) == \
        8 * fixed + 11 * 6144 + 5000 * 96 + 900 * 240
    for part, want in (("experts", 11 * 6144), ("index", 5000 * 96),
                       ("latents", 900 * 240)):
        assert glm_roofline.decode_bytes(TINY, 8, 11, 5000, 900, 2.0,
                                         part) == want
    # 100 tokens, 3,000 pairs scored by the indexer of which 1,800 are
    # kept, 37 token-expert pairs of held experts, 2 rows that yield a
    # token: 2 x 2 heads x 16 an index pair, 2 x 4 heads x (16 + 8 + 24)
    # an attended pair, 3 layers
    per_token = (3 * (18944 + 5248) + 3 * 64 * 160
                 + 2 * (3 * 64 * 32 + 64 * 8))
    assert glm_roofline.prefill_flops(TINY, 100, 3000, 1800, 37, 2) == \
        2 * per_token * 100 + 2 * 6144 * 37 + 2 * 64 * 512 * 2 \
        + 3 * (64 * 3000 + 384 * 1800)


def test_the_published_widths_against_the_issues_arithmetic():
    """ISSUE 37: attention 165.02M and the indexer 9.37M a layer, an
    expert 37.75M, a cached position 1,408 B a layer; 32 experts of the
    router's 256 and 19,360 rows of the vocabulary held."""
    att8, att16 = glm_roofline.attention_params(DIMS)
    assert att8 + att16 == (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576
                            + 512 * 64 * 448 + 64 * 256 * 6144
                            + 2048 * 32 * 128 + 6144 * 128 + 6144 * 32)
    assert round((att8 + att16) / 1e6, 2) == round(165.02 + 9.37, 2)
    assert glm_roofline.expert_params(DIMS) == 3 * 6144 * 2048
    assert glm_roofline.state_bytes_per_row(DIMS, 2.0) == (6 * 1152,
                                                           6 * 256)
    assert glm_roofline.layer_counts(DIMS) == (1, 5)
    assert DIMS["held"]["router_experts"] == 256
    assert DIMS["n_routed_experts"] == 32 and DIMS["vocab_size"] == 19360
    # what the chip holds outside the cache, at the served bytes: the
    # issue's 7.98 GB
    weights = (6 * (att8 + 2 * att16) + 3 * 6144 * 12288
               + 5 * (3 * 6144 * 2048 + 4 * 6144 * 256
                      + 32 * 3 * 6144 * 2048)
               + 3 * 19360 * 6144)
    assert 7.9e9 < weights < 8.05e9


def run_with(steps, trace_steps, planes=None, dims=TINY):
    return {"records": {"steps": steps,
                        "engine": {"steps_per_dispatch": 8}},
            "trace": {"steps": trace_steps, "device_planes": planes},
            "dims": dims, "device": {"kind": "TPU v5 lite"},
            "window": (10.0, 20.0)}


DEC, ADM = "jit__decode_mla", "jit__admit_mla"


def test_decode_hbm_shares_read_the_programs_own_counts():
    step = {"seq": 5, "kind": "decode", "t_end": 12.0, "rows": 3,
            "experts_touched": 11, "live_tokens": 5000,
            "selected_tokens": 900}
    trace = [{"name": "decode", "step_num": 5, "modules": [(DEC, 0.002)]}]
    args = {"step": "decode", "module": DEC, "state_bytes": 2.0}
    need = glm_roofline.decode_bytes(TINY, 8, 11, 5000, 900, 2.0)
    assert glm_decode_hbm.read(run_with([step], trace), args) == \
        pytest.approx(100 * need / 819e9 / 0.002)
    # a program that writes no such counts, or no trace: no value
    bare = {k: v for k, v in step.items() if k != "live_tokens"}
    assert glm_decode_hbm.read(run_with([bare], trace), args) is None
    assert glm_decode_hbm.read(
        run_with([dict(step, live_tokens=0)], trace), args) is None
    assert glm_decode_hbm.read(run_with([step], []), args) is None
    # one scope against its own bytes needs the device's planes
    scoped = dict(args, scope="indexer", part="index",
                  declared="GLM_SCOPES")
    assert glm_decode_hbm.read(run_with([step], trace), scoped) is None
    planes = [{"modules": [(0, 2_000_000_000, DEC, "")],
               "ops": [(0, 400_000_000, "fusion.1",
                        "jit(_decode_mla)/while/body/indexer/dot"),
                       (400_000_000, 500_000_000, "fusion.2",
                        "jit(_decode_mla)/while/body/select/while/body/"
                        "reduce_sum"),
                       (500_000_000, 1_000_000_000,
                        "mla_decode_attention.1",
                        "jit(_decode_mla)/while/body/attn/"
                        "mla_decode_attention"),
                       (1_000_000_000, 2_000_000_000, "grouped_qmatmul.1",
                        "jit(_decode_mla)/while/body/moe_experts/"
                        "grouped_qmatmul")]}]
    run = run_with([step], trace, planes)
    assert glm_decode_hbm.read(run, scoped) == \
        pytest.approx(100 * 5000 * 96 / 819e9 / 0.0004)
    assert glm_decode_hbm.read(
        run, dict(scoped, scope="attn", part="latents")) == \
        pytest.approx(100 * 900 * 240 / 819e9 / 0.0005)
    assert glm_decode_hbm.read(
        run, dict(scoped, scope="moe_experts", part="experts")) == \
        pytest.approx(100 * 11 * 6144 / 819e9 / 0.001)
    assert glm_decode_hbm.read(
        run, dict(scoped, declared="NO_SUCH_TUPLE")) is None
    # the two new device shares of a step, from the same planes
    for scope, want in (("indexer", 20.0), ("select", 5.0)):
        assert scope_shares_of.read(
            run, {"module": DEC, "scope": scope,
                  "declared": "GLM_SCOPES"}) == pytest.approx(want)


def test_prefill_mxu_share_reads_the_pairs_the_program_counted():
    step = {"seq": 7, "kind": "prefill", "t_end": 12.0, "tokens": 100,
            "live_tokens": 3000, "selected_tokens": 1800,
            "expert_rows": 37, "new_tokens": 2}
    trace = [{"name": "prefill", "step_num": 7, "modules": [(ADM, 0.001)]}]
    args = {"step": "prefill", "module": ADM}
    need = glm_roofline.prefill_flops(TINY, 100, 3000, 1800, 37, 2)
    assert glm_prefill_mxu.read(run_with([step], trace), args) == \
        pytest.approx(100 * need / 197e12 / 0.001)
    bare = {k: v for k, v in step.items() if k != "live_tokens"}
    assert glm_prefill_mxu.read(run_with([bare], trace), args) is None
    assert glm_prefill_mxu.read(run_with([step], []), args) is None


def test_how_sparse_the_read_was_counts_decode_dispatches_alone():
    steps = [{"t_end": t, "kind": k, "selected_tokens": s,
              "live_tokens": n}
             for t, k, s, n in ((9.0, "decode", 9, 9),         # before it
                                (11.0, "decode", 16384, 100000),
                                (12.0, "prefill", 4000, 4000),
                                (15.0, "decode", 16384, 160000),
                                (20.5, "decode", 9, 9))]       # after it
    args = {"num": "selected_tokens", "den": "live_tokens",
            "kind": "decode", "scale": 100.0}
    assert step_field_ratio.read(run_with(steps, []), args) == \
        pytest.approx(100 * 32768 / 260000)
    assert step_field_ratio.read(
        run_with([{"t_end": 11.0, "kind": "decode"}], []), args) is None


def test_every_metric_of_the_cell_names_a_reader_that_takes_its_arguments():
    """Each ``.glm`` metric file's reader, handed a run with no trace
    and no counts (what the parent's program gives), returns nothing
    and does not raise."""
    from benchmark.harness import spec

    cell = "glm5-744b-a40b-int8.summarize-threads-32k"
    names = [m["name"] for m in spec.metric_files(cell, "per_layer")]
    assert len(names) == 28 and all(n.endswith(".glm") for n in names)
    run = dict(run_with([], []), trace=None, drive={"late_s": []},
               compile_times=[], seconds=51.0, setup_s=1.0,
               device={"kind": "TPU v5 lite", "memory_peak_bytes": 0})
    run["records"]["requests"] = []
    run["records"]["engine_requests"] = []
    for m in spec.metric_files(cell, "per_layer"):
        if m["reader"] in ("compiles", "memory_peak", "step_ratio",
                           "first_use_steps"):
            continue            # host counts: they read without a trace
        assert spec.module("readers", m["reader"]).read(
            run, m["args"]) is None, m["name"]


# ---------------------------------------------------------------------------
# a trace recorded on the chip: ``--rehearse --trace 1 --seconds 5`` of
# the cell on a TPU v5 lite (PR 37), trimmed by tools/trim_trace.py and
# cut by tools/cut_trace.py
# ---------------------------------------------------------------------------

# (named to sort after tiny_qa.xplane.pb: test_trace_roofline.py reads
# the first trace of the directory)
TRACE = HERE / "data" / "tiny_selected.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce_file(str(TRACE))


def test_the_recorded_trace_holds_both_programs_and_their_scopes(recorded):
    from copilot_for_consensus_tpu.obs import profile

    names = {n for st in recorded["steps"] for n, _d in st["modules"]}
    assert {DEC, ADM} <= names
    table = scope_reduce.reduce_planes(
        recorded["device_planes"],
        tuple(profile.SCOPES) + tuple(profile.GLM_SCOPES))
    dec, adm = table[DEC], table[ADM]
    for scope in ("indexer", "select", "moe_route", "moe_experts", "attn",
                  "qkv", "unembed", "kv_write"):
        assert dec["self_s"].get(scope, 0) > 0, scope
    for scope in ("indexer", "select", "moe_route", "moe_experts", "attn",
                  "latent_expand", "kv_write"):
        assert adm["self_s"].get(scope, 0) > 0, scope
    assert "mhc" not in dec["self_s"] and "mhc" not in adm["self_s"]
    # both kernels are there under their own names: the tiny cache still
    # leaves the latent kernel a block of 256 columns
    ops = {op[2] for plane in recorded["device_planes"]
           for op in plane["ops"]}
    assert any("grouped_qmatmul" in o for o in ops)
    assert any("mla_decode_attention" in o for o in ops)


def test_the_scope_readers_on_the_recorded_trace(recorded):
    run = run_with([], recorded["steps"], recorded["device_planes"])
    shares = {}
    for scope in (["moe_route", "moe_experts"], "indexer", "select",
                  "attn", "_unscoped_"):
        shares[str(scope)] = scope_shares_of.read(
            run, {"module": DEC, "scope": scope,
                  "declared": "GLM_SCOPES"})
    assert all(0 < v < 100 for v in shares.values()), shares
    assert sum(shares.values()) < 100
    for scope in ("indexer", "select", "latent_expand"):
        assert scope_shares_of.read(
            run, {"module": ADM, "scope": scope,
                  "declared": "GLM_SCOPES"}) > 0
