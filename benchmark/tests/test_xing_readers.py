"""What a step of the latent-attention configuration has to do
(``harness/xing_roofline``), against hand arithmetic at the tiny and at
the published widths, and the readers that came with its cell, on
hand-made runs and on a trace recorded on the chip: each gives the
number its arithmetic says, and gives nothing, without raising, for a
program that lacks what it reads."""
import json
import pathlib

import pytest

from benchmark.harness import scope_reduce, trace_reduce, xing_roofline
from benchmark.readers import (
    scope_shares_of,
    step_field_ratio,
    xing_decode_hbm,
    xing_prefill_mxu,
)

HERE = pathlib.Path(__file__).resolve().parent
CONFIG = json.loads((HERE.parent / "configs"
                     / "xing4-29b-a4b-int8.json").read_text())
DIMS = {k: v for k, v in CONFIG.items()
        if not isinstance(v, (dict, list)) or k == "rope_scaling"}
#: tests/test_xing_engine.py's sizes: d 64, 4 streams, 4 heads, latent
#: 32 + 8, queries through 48, 8 experts of 32 (2 a token) beside a
#: shared one, one dense layer of 160 and two expert layers, 512 ids
TINY = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            n_shared_experts=1, moe_intermediate_size=32,
            intermediate_size=160, first_k_dense_replace=1,
            num_hidden_layers=3, hc_mult=4, vocab_size=512)


def test_the_tiny_size_by_hand():
    # attention: 64 x 48 + 48 x 4 x 24 + 64 x 40 + 4 x 16 x 64 in int8,
    # wkv_b 32 x 4 x 32 in bfloat16; the two maps 2 x 256 x 24
    assert xing_roofline.attention_params(TINY) == (
        3072 + 4608 + 2560 + 4096, 4096)
    assert xing_roofline.map_params(TINY) == 12288
    assert xing_roofline.expert_params(TINY) == 3 * 64 * 32
    assert xing_roofline.latent_bytes_per_row(TINY, 2.0) == 3 * 40 * 2
    fixed = (3 * (14336 + 2 * 4096 + 4 * 12288)
             + 3 * 64 * 160                         # the dense SwiGLU
             + 2 * (3 * 64 * 32 + 4 * 64 * 8)       # shared, routers
             + 64 * 512)                            # the head
    assert xing_roofline.fixed_decode_bytes(TINY) == fixed
    # a dispatch of 8 steps that touched 55 experts and read 500 rows
    assert xing_roofline.decode_bytes(TINY, 8, 55, 500, 2.0) == \
        8 * fixed + 55 * 6144 + 500 * 240
    assert xing_roofline.decode_bytes(TINY, 8, 55, 500, 2.0,
                                      "experts") == 55 * 6144
    assert xing_roofline.decode_bytes(TINY, 8, 55, 500, 2.0,
                                      "latents") == 500 * 240
    active = (3 * (14336 + 4096 + 12288) + 3 * 64 * 160
              + 2 * ((2 + 1) * 6144 + 64 * 8))
    assert xing_roofline.active_params(TINY) == active
    # 100 tokens, 3000 pairs, 2 rows that yield a token: 2 x 4 heads x
    # (16 + 8 + 16) x 3 layers a pair
    assert xing_roofline.prefill_flops(TINY, 100, 3000, 2) == \
        2 * active * 100 + 2 * 64 * 512 * 2 + 960 * 3000


def test_the_published_widths_against_the_issues_arithmetic():
    """ISSUE 33: attention 28.4M a layer (with wkv_b), an expert 11.0M,
    the maps 0.69M; a cached position 1,152 B a layer."""
    att8, att16 = xing_roofline.attention_params(DIMS)
    assert att8 + att16 == 3584 * 768 + 768 * 6144 + 3584 * 576 \
        + 512 * 8192 + 4096 * 3584
    assert xing_roofline.expert_params(DIMS) == 3 * 3584 * 1024
    assert xing_roofline.map_params(DIMS) == 2 * 14336 * 24
    assert xing_roofline.latent_bytes_per_row(DIMS, 2.0) == 12 * 1152
    assert xing_roofline.layer_counts(DIMS) == (1, 11)


def run_with(steps, trace_steps, planes=None, dims=TINY):
    return {"records": {"steps": steps,
                        "engine": {"steps_per_dispatch": 8}},
            "trace": {"steps": trace_steps, "device_planes": planes},
            "dims": dims, "device": {"kind": "TPU v5 lite"},
            "window": (10.0, 20.0)}


DEC = "jit__decode_mla"


def test_decode_hbm_share_reads_the_programs_own_counts():
    step = {"seq": 5, "kind": "decode", "t_end": 12.0, "rows": 3,
            "experts_touched": 55, "window_tokens": 600}
    trace = [{"name": "decode", "step_num": 5, "modules": [(DEC, 0.002)]}]
    args = {"step": "decode", "module": DEC, "state_bytes": 2.0}
    # 8 steps over 3 sequences of 600 rows in all: 8 x 600 + 3 x 28
    need = xing_roofline.decode_bytes(TINY, 8, 55, 4884, 2.0)
    assert xing_decode_hbm.read(run_with([step], trace), args) == \
        pytest.approx(100 * need / 819e9 / 0.002)
    # a program that writes no such counts, or no trace: no value
    bare = {k: v for k, v in step.items() if k != "experts_touched"}
    assert xing_decode_hbm.read(run_with([bare], trace), args) is None
    assert xing_decode_hbm.read(run_with([step], []), args) is None
    # one scope against its own bytes needs the device's planes
    scoped = dict(args, scope="attn", part="latents",
                  declared="XING_SCOPES")
    assert xing_decode_hbm.read(run_with([step], trace), scoped) is None
    planes = [{"modules": [(0, 2_000_000_000, DEC, "")],
               "ops": [(0, 500_000_000, "fusion.1",
                        "jit(_decode_mla)/while/body/attn/dot"),
                       (500_000_000, 2_000_000_000, "grouped_qmatmul.1",
                        "jit(_decode_mla)/while/body/moe_experts/"
                        "grouped_qmatmul")]}]
    assert xing_decode_hbm.read(run_with([step], trace, planes), scoped) \
        == pytest.approx(100 * 4884 * 240 / 819e9 / 0.0005)
    assert xing_decode_hbm.read(
        run_with([step], trace, planes),
        dict(scoped, scope="moe_experts", part="experts")) \
        == pytest.approx(100 * 55 * 6144 / 819e9 / 0.0015)
    assert xing_decode_hbm.read(
        run_with([step], trace, planes),
        dict(scoped, declared="NO_SUCH_TUPLE")) is None


def test_prefill_mxu_share_reads_the_pairs_the_program_counted():
    step = {"seq": 7, "kind": "prefill", "t_end": 12.0, "tokens": 100,
            "attn_pairs": 3000, "new_tokens": 2}
    trace = [{"name": "prefill", "step_num": 7,
              "modules": [("jit__admit_mla", 0.001)]}]
    args = {"step": "prefill", "module": "jit__admit_mla"}
    need = xing_roofline.prefill_flops(TINY, 100, 3000, 2)
    assert xing_prefill_mxu.read(run_with([step], trace), args) == \
        pytest.approx(100 * need / 197e12 / 0.001)
    bare = {k: v for k, v in step.items() if k != "attn_pairs"}
    assert xing_prefill_mxu.read(run_with([bare], trace), args) is None
    assert xing_prefill_mxu.read(run_with([step], []), args) is None


def test_ratios_of_step_fields_over_the_counted_interval():
    steps = [{"t_end": t, "kind": k, "experts_touched": e,
              "expert_rows": r, "expert_rows_max": m}
             for t, k, e, r, m in (
                 (9.0, "decode", 99, 99, 99),          # before it
                 (11.0, "decode", 200, 512, 40),
                 (12.0, "prefill", 16, 4000, 700),
                 (15.0, "decode", 232, 512, 50),
                 (20.5, "decode", 99, 99, 99))]        # after it
    per_step = {"num": "experts_touched", "den": "decode_steps",
                "kind": "decode"}
    assert step_field_ratio.read(run_with(steps, []), per_step) == \
        (200 + 232) / 16
    share = {"num": "expert_rows_max", "den": "expert_rows",
             "scale": 100.0}
    assert step_field_ratio.read(run_with(steps, []), share) == \
        pytest.approx(100 * 790 / 5024)
    assert step_field_ratio.read(
        run_with([{"t_end": 11.0, "kind": "decode"}], []), per_step) is None


def test_scope_shares_add_and_need_the_programs_tuple():
    args = {"module": DEC, "scope": ["moe_route", "moe_experts"],
            "declared": "XING_SCOPES"}
    assert scope_shares_of.read(run_with([], []), args) is None
    assert scope_shares_of.read(
        run_with([], []), dict(args, declared="NO_SUCH_TUPLE")) is None
    planes = [{"modules": [(0, 100, DEC, "")],
               "ops": [(0, 50, "fusion.1", "jit(_decode_mla)/attn/dot"),
                       (50, 60, "fusion.2",
                        "jit(_decode_mla)/while/body/moe_route/top_k"),
                       (60, 90, "grouped_qmatmul.3",
                        "jit(_decode_mla)/while/body/moe_experts/"
                        "jit(grouped_qmatmul)/grouped_qmatmul"),
                       (90, 100, "fusion.4",
                        "jit(_decode_mla)/while/body/mhc/mul")]}]
    assert scope_shares_of.read(run_with([], [], planes), args) == \
        pytest.approx(40.0)
    assert scope_shares_of.read(
        run_with([], [], planes), dict(args, scope="mhc")) == \
        pytest.approx(10.0)


# ---------------------------------------------------------------------------
# a trace recorded on the chip: ``--rehearse --trace 1 --seconds 5`` of
# the cell on a TPU v5 lite (PR 33), trimmed by tools/trim_trace.py
# ---------------------------------------------------------------------------

TRACE = HERE / "data" / "tiny_xing.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce_file(str(TRACE))


def test_the_recorded_trace_holds_both_programs_and_their_scopes(recorded):
    from copilot_for_consensus_tpu.obs import profile

    names = {n for st in recorded["steps"] for n, _d in st["modules"]}
    assert {"jit__decode_mla", "jit__admit_mla"} <= names
    table = scope_reduce.reduce_planes(
        recorded["device_planes"],
        tuple(profile.SCOPES) + tuple(profile.XING_SCOPES))
    dec, adm = table["jit__decode_mla"], table["jit__admit_mla"]
    for scope in ("moe_route", "moe_experts", "mhc", "attn", "qkv",
                  "unembed", "kv_write"):
        assert dec["self_s"].get(scope, 0) > 0, scope
    for scope in ("moe_route", "moe_experts", "mhc", "attn",
                  "latent_expand", "kv_write"):
        assert adm["self_s"].get(scope, 0) > 0, scope
    # the kernel is there under its own name
    assert any("grouped_qmatmul" in op[2]
               for plane in recorded["device_planes"]
               for op in plane["ops"])


def test_the_scope_readers_on_the_recorded_trace(recorded):
    run = run_with([], recorded["steps"], recorded["device_planes"])
    shares = {}
    for scope in (["moe_route", "moe_experts"], "mhc", "attn",
                  "_unscoped_"):
        shares[str(scope)] = scope_shares_of.read(
            run, {"module": DEC, "scope": scope,
                  "declared": "XING_SCOPES"})
    assert all(0 < v < 100 for v in shares.values()), shares
    assert sum(shares.values()) < 100
    assert scope_shares_of.read(
        run, {"module": "jit__admit_mla", "scope": "latent_expand",
              "declared": "XING_SCOPES"}) > 0
