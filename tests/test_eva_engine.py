# attention="eva" (models/eva.py, EvaByte class) through the cache and
# through GenerationEngine, against the plain reference
# benchmark/reference/evabyte.py (float32, a mask over the whole
# sequence, no cache). Tiny sizes: windows of 32 positions in chunks of
# 4, two layers, four heads of 16, eight prediction heads of 320 ids.
#
# Tolerances, each with its reason:
#   TOL = 1e-4 on logits of size ~4: weights, activations and cache are
#   float32 here, so program and reference differ only by the order of
#   float32 sums (flash-style pieces against one softmax row); the
#   largest difference seen is 3e-6. A window or a summary store
#   rounded to float8_e4m3fn moves logits by 1e-2 and more: the fp8
#   tests below hold TOL to being at least ten times under that.
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import evabyte as ref
from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.engine.tokenizer import (
    N_SPECIALS,
    ByteTokenizer,
)
from copilot_for_consensus_tpu.models import eva
from copilot_for_consensus_tpu.models.configs import decoder_config

TOL = 1e-4
CFG = decoder_config("tiny-eva")
W, C, V = CFG.window_size, CFG.chunk_size, CFG.vocab_size
DIMS = dict(hidden_size=CFG.d_model, num_attention_heads=CFG.n_heads,
            num_key_value_heads=CFG.n_kv_heads,
            num_hidden_layers=CFG.n_layers, vocab_size=V,
            rms_norm_eps=CFG.norm_eps, rope_theta=CFG.rope_theta,
            window_size=W, chunk_size=C, num_pred_heads=CFG.num_pred_heads,
            norm_add_unit_offset=CFG.norm_unit_offset)
MAX_LEN, STEPS = 256, 8


@pytest.fixture(scope="module")
def params():
    return eva.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def greedy(logits, _key):
    return jnp.argmax(logits, -1).astype(jnp.int32)


def prefill(params, cache, slot, seq, piece):
    """Admit ``seq`` into ``slot`` in pieces of at most ``piece``, never
    across a window edge (what the engine's admission does)."""
    step = jax.jit(lambda c, t, n, p, s: eva.prefill_piece(
        params, t, n, p, s, CFG, c, "xla"))
    pos = 0
    while pos < len(seq):
        n = min(len(seq) - pos, W - pos % W, piece)
        toks = np.zeros((1, piece), np.int32)
        toks[0, :n] = seq[pos:pos + n]
        logits, cache = step(cache, jnp.asarray(toks), jnp.asarray([n]),
                             jnp.asarray([pos]), jnp.asarray([slot]))
        pos += n
    return np.asarray(logits[0]), cache


def decode(params, cache, tok, pos, dispatches, corrupt=None):
    """``dispatches`` x STEPS greedy tokens for every slot; returns the
    logits of every step [n, slots, heads * V], the tokens, the cache."""
    logits, toks = [], []
    for _ in range(dispatches):
        may_close = bool((pos % W + STEPS >= W).any())
        if corrupt:
            cache = corrupt(cache)
        t, cache, lg = jax.jit(
            lambda c, t, p: eva.decode_tokens(
                params, t, p, CFG, c, jax.random.PRNGKey(0), greedy,
                steps=STEPS, may_close=may_close, max_len=MAX_LEN,
                with_logits=True))(cache, jnp.asarray(tok),
                                   jnp.asarray(pos))
        logits.append(np.asarray(lg))
        toks.append(np.asarray(t))
        tok, pos = np.asarray(t)[-1], pos + STEPS
    return np.concatenate(logits), np.concatenate(toks), cache


def through_the_cache(params, plens, new, piece=W, corrupt=None, seed=0):
    """Worst absolute difference, over all slots, positions and all
    eight heads, between prefill-then-decode through the cache and the
    reference's full forward over the same tokens."""
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, V, size=n).tolist() for n in plens]
    cache = eva.init_cache(CFG, len(plens), MAX_LEN, jnp.float32,
                           margin=STEPS)
    first = []
    for slot, seq in enumerate(seqs):
        lg, cache = prefill(params, cache, slot, seq, piece)
        first.append(lg)
    tok = np.asarray([int(np.argmax(lg[:V])) for lg in first], np.int32)
    logits, toks, cache = decode(params, cache, tok,
                                 np.asarray(plens, np.int32),
                                 new // STEPS, corrupt)
    worst = 0.0
    for s, seq in enumerate(seqs):
        full = seq + [int(tok[s])] + toks[:-1, s].tolist()
        want = ref.all_head_logits(params, DIMS, full,
                                   np.arange(len(seq) - 1, len(full)))
        got = np.concatenate([first[s][None], logits[:, s]])
        assert got.shape == want.shape == (new + 1,
                                           CFG.num_pred_heads * V)
        worst = max(worst, float(np.abs(got - want).max()))
    return worst, cache


@pytest.mark.parametrize("plens,new,piece", [
    ((W - 1, W, W + 1), 48, W),        # a prompt ending one before, on
    #                                    and one after a window edge
    ((70, 5, 64), 40, 16),             # several windows, pieces of 16
    ((3 * W, W + C, 1), 40, W),        # three closed windows; a chunk in
], ids=["window-edge", "pieces-of-16", "three-windows"])
def test_prefill_then_decode_equals_the_reference_on_all_heads(
        params, plens, new, piece):
    worst, _ = through_the_cache(params, plens, new, piece)
    assert worst < TOL


def test_one_slot_compacts_mid_dispatch_and_its_neighbour_does_not(params):
    """Slot 0 starts a dispatch at fill W - 3: its window fills at the
    third of 8 steps, the other five see the 8 new summaries and a
    window that restarted; slot 1 is nowhere near its edge."""
    plens = (W - 3, 10)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, V, size=n).tolist() for n in plens]
    cache = eva.init_cache(CFG, 2, MAX_LEN, jnp.float32, margin=STEPS)
    first = []
    for slot, seq in enumerate(seqs):
        lg, cache = prefill(params, cache, slot, seq, W)
        first.append(lg)
    before = np.asarray(cache["ks"])
    tok = np.asarray([int(np.argmax(lg[:V])) for lg in first], np.int32)
    logits, toks, cache = decode(params, cache, tok,
                                 np.asarray(plens, np.int32), 1)
    after = np.asarray(cache["ks"])
    r, wc = after.shape[3], W // C
    # slot 0 gained W / C summaries at the top of its store, slot 1 none
    assert np.abs(after[:, 0, :, r - wc:]).min() > 0
    np.testing.assert_array_equal(after[:, 1, :, r - wc:],
                                  before[:, 1, :, r - wc:])
    for s, seq in enumerate(seqs):
        full = seq + [int(tok[s])] + toks[:-1, s].tolist()
        want = ref.all_head_logits(params, DIMS, full,
                                   np.arange(len(seq), len(full)))
        assert np.abs(logits[:, s] - want).max() < TOL


@pytest.mark.parametrize("halves", [("k", "v"), ("ks", "vs")],
                         ids=["fp8-window", "fp8-summaries"])
def test_an_fp8_window_or_fp8_summaries_break_the_tolerance(params, halves):
    def rounded(cache):
        return dict(cache, **{
            h: cache[h].astype(jnp.float8_e4m3fn).astype(cache[h].dtype)
            for h in halves})

    worst, _ = through_the_cache(params, (70, 45, 64), 16, corrupt=rounded)
    assert worst > 10 * TOL


def test_chunked_admission_equals_one_shot(params):
    """Pieces of 8 against whole windows: the same summaries and window
    in the cache, the same logits."""
    rng = np.random.default_rng(3)
    seq = rng.integers(0, V, size=2 * W + 13).tolist()
    fresh = lambda: eva.init_cache(CFG, 1, MAX_LEN, jnp.float32,  # noqa: E731
                                   margin=STEPS)
    whole, cache_a = prefill(params, fresh(), 0, seq, W)
    pieces, cache_b = prefill(params, fresh(), 0, seq, 8)
    assert np.abs(whole - pieces).max() < TOL
    r, n = cache_a["ks"].shape[3], 2 * W // C
    for half in ("ks", "vs"):
        np.testing.assert_allclose(cache_a[half][:, :, :, r - n:],
                                   cache_b[half][:, :, :, r - n:],
                                   atol=1e-5)
    for half in ("k", "v"):
        np.testing.assert_allclose(cache_a[half][:, :, :, :13],
                                   cache_b[half][:, :, :, :13], atol=1e-5)


# ---------------------------------------------------------------------------
# through GenerationEngine
# ---------------------------------------------------------------------------


def engine(params, **kw):
    args = dict(num_slots=3, max_len=MAX_LEN, prefill_buckets=(8, 16, 32),
                dtype=jnp.float32, attn_impl="xla", eos_id=-1,
                admission_token_budget=64)
    args.update(kw)
    return GenerationEngine(CFG, params, **args)


def served_gaps(params, prompts, comps):
    """For each completion: how far the served token's reference logit
    (head 0) lies under the reference's best, at its worst position."""
    out = []
    for prompt, comp in zip(prompts, comps):
        seq = prompt + comp.tokens
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        lg = ref.logits_at(params, DIMS, seq, at)
        out.append(float((lg.max(-1) - lg[np.arange(len(at)),
                                          np.asarray(comp.tokens)]).max()))
    return out


def test_engine_serves_the_references_best_byte_at_every_position(params):
    """submit/step through admission waves of mixed pieces, decode
    dispatches in which slots compact at different steps, retirement
    and slot reuse: every served byte is the reference's argmax (a
    wrong mask, a stale summary or a misplaced column shows as a gap
    of order 1; ties do not occur at float32)."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, V, size=n).tolist()
               for n in (31, 32, 33, 70, 5, 64, 100, 17)]
    eng = engine(params)
    comps = eng.generate(prompts, max_new_tokens=45)
    assert [len(c.tokens) for c in comps] == [45] * 8
    assert max(served_gaps(params, prompts, comps)) < TOL
    recs = eng.telemetry.recorder.records()
    assert {r.kind for r in recs} == {"prefill", "decode"}
    # each prompt closes len // W windows in admission and the rest
    # while decoding: 44 fed-back bytes are six dispatches of 8 steps,
    # and a dispatch runs all its steps on the device
    closed = sum((len(p) + 6 * STEPS) // W for p in prompts)
    assert sum(r.windows_compacted for r in recs) == closed
    assert max(r.window_tokens for r in recs) > 0
    assert max(r.summary_tokens for r in recs) >= 3 * W // C
    # new_tokens stays the count of tokens handed to requests
    assert sum(r.new_tokens for r in recs) == 8 * 45
    # two decode programs, whatever the lengths
    assert {k[1] for k in eng.programs_seen if k[0] == "decode"} \
        == {True, False}


def test_state_tokens_read_is_the_live_state_rounded_up_to_blocks(params):
    """`StepRecord.state_tokens_read`: what the blocks of the decode
    attention cover for the decoding slots. With every prompt admitted
    in one wave no slot is mid-admission while another decodes, so the
    live counts are the decoding slots': the blocks cover at least
    that, and at most a block a piece a slot more. (At these sizes the
    kernel's blocks are the window, 32 columns, and the whole store of
    64 summaries.)"""
    from copilot_for_consensus_tpu.ops.eva_attention import block_sizes

    wb, sb = block_sizes(W, MAX_LEN // C)
    assert (wb, sb) == (W, MAX_LEN // C)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, V, size=n).tolist() for n in (5, 20, 31)]
    eng = engine(params)
    eng.generate(prompts, max_new_tokens=60)
    recs = eng.telemetry.recorder.records()
    decodes = [r for r in recs if r.kind == "decode"]
    assert len(decodes) >= 8
    for r in decodes:
        live = r.window_tokens + r.summary_tokens
        assert live <= r.state_tokens_read <= live + r.rows * (wb + sb)
    # a window just opened behind summaries is read as one block of
    # each; an empty window is not read at all
    assert any(r.state_tokens_read > r.window_tokens + r.summary_tokens
               for r in decodes)
    assert all(r.state_tokens_read == 0 for r in recs
               if r.kind == "prefill")


def test_state_tokens_read_leaves_out_a_slot_that_is_mid_admission(params):
    """A prompt of three windows is admitted in three waves with the
    other slot's decode dispatches in between: its state counts as live
    (`window_tokens`, `summary_tokens`: all slots) and is not read by
    the decode attention, whose blocks cover the decoding slot only."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, V, size=n).tolist() for n in (9, 3 * W + 5)]
    eng = engine(params, num_slots=2)
    eng.generate(prompts, max_new_tokens=40)
    decodes = [r for r in eng.telemetry.recorder.records()
               if r.kind == "decode"]
    beside = [r for r in decodes if r.rows == 1
              and r.summary_tokens > 0 and r.state_tokens_read <= W]
    assert beside       # decoded beside the long prompt's admission
    for r in decodes:
        assert r.state_tokens_read <= (r.window_tokens + r.summary_tokens
                                       + r.rows * (W + MAX_LEN // C))


def test_slot_reuse_after_retire_leaves_no_summary_behind(params):
    """One slot: a long sequence fills its summary store, retires, and
    a short prompt takes the slot. It is served as in a fresh engine."""
    rng = np.random.default_rng(2)
    long_p = rng.integers(0, V, size=150).tolist()
    short_p = rng.integers(0, V, size=9).tolist()
    eng = engine(params, num_slots=1)
    eng.generate([long_p], max_new_tokens=20)
    assert float(jnp.abs(eng._cache["ks"]).max()) > 0     # left behind
    reused = eng.generate([short_p], max_new_tokens=40)
    fresh = engine(params, num_slots=1).generate([short_p],
                                                 max_new_tokens=40)
    assert reused[0].tokens == fresh[0].tokens
    assert served_gaps(params, [short_p], reused)[0] < TOL


def test_scheduler_chunking_and_small_buckets_serve_the_same_bytes(params):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, V, size=n).tolist() for n in (90, 40, 33)]
    want = [c.tokens for c in engine(params).generate(prompts, 24)]
    small = engine(params, prefill_buckets=(8,))
    assert [c.tokens for c in small.generate(prompts, 24)] == want
    from copilot_for_consensus_tpu.engine.scheduler import SchedulerConfig
    sched = engine(params, scheduler=SchedulerConfig(chunk_tokens=16))
    assert [c.tokens for c in sched.generate(prompts, 24)] == want


@pytest.mark.parametrize("option,word", [
    (dict(prefix_cache_blocks=8), "prefix cache"),
    (dict(kv_pool_blocks=64, prefill_chunk=16), "block pool"),
    (dict(spec_decode=True), "verify pass"),
    (dict(kv_dtype="float8_e4m3fn"), "8-bit keys"),
    (dict(windows_per_dispatch=2), "one window"),
    (dict(quantize="int4"), "int4"),
    (dict(max_len=MAX_LEN - 8), "multiple of window_size"),
], ids=lambda o: next(iter(o)) if isinstance(o, dict) else None)
def test_options_that_cannot_serve_this_state_refuse_it(params, option,
                                                        word):
    with pytest.raises(ValueError, match=word):
        engine(params, **option)


def test_a_mesh_refuses_it(params):
    from copilot_for_consensus_tpu.parallel.mesh import local_mesh

    mesh = local_mesh(tp=1)
    with pytest.raises(ValueError, match="sharding"):
        engine(params, mesh=mesh)


def test_dense_engines_write_no_eva_counts():
    cfg = decoder_config("tiny")
    eng = GenerationEngine(cfg, num_slots=2, max_len=128,
                           prefill_buckets=(32,), dtype=jnp.float32,
                           attn_impl="xla", eos_id=-1)
    eng.generate([[5, 6, 7]], max_new_tokens=9)
    for r in eng.telemetry.recorder.records():
        assert (r.windows_compacted, r.window_tokens, r.summary_tokens,
                r.state_tokens_read) == (0, 0, 0, 0)


def test_scopes_are_in_the_lowered_eva_programs(params):
    """The names the `.evab` scope metrics sum by are in the programs'
    op_name metadata (tests/test_engine_spans.py does this for the
    dense programs): `kv_compact` in both, in the decode program only
    where a window can fill."""
    import re

    eng = engine(params)
    key = jax.random.PRNGKey(0)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731

    def names(lowered):
        return set(re.findall(r'op_name="([^"]*)"',
                              lowered.compile().as_text()))

    admit = names(eng._admit_eva_fn.lower(
        eng.params, i32(2, 16), jnp.ones((2,), jnp.int32), i32(2), i32(2),
        eng._cache, key))
    for name in ("kv_compact", "attn", "kv_write", "kv_prefix", "qkv",
                 "ffn", "unembed"):
        assert any(f"/{name}/" in op and op.startswith("jit(_admit_eva)/")
                   for op in admit), name
    for may_close in (True, False):
        decode = names(eng._decode_eva_fn.lower(
            eng.params, i32(3), i32(3), eng._cache, key,
            may_close=may_close))
        for name in ("attn", "kv_write", "qkv", "ffn", "unembed", "sample"):
            assert any(f"/{name}/" in op
                       and op.startswith("jit(_decode_eva)/")
                       for op in decode), name
        assert any("/kv_compact/" in op for op in decode) == may_close


# ---------------------------------------------------------------------------
# the byte tokenizer against 320 ids
# ---------------------------------------------------------------------------


def test_generate_text_round_trips_utf8_through_ids_below_320(params):
    tok = ByteTokenizer(vocab_size=V)
    text = "Re: [wg] draft-07 — ünïcode, 日本語 ok?"
    ids = tok.encode(text, add_bos=True)
    assert max(ids) < V and tok.decode(ids) == text
    # the offset is the one the configuration file states
    cfg_file = pathlib.Path(__file__).resolve().parents[1] \
        / "benchmark" / "configs" / "evabyte-6.5b-int8.json"
    stated = json.loads(cfg_file.read_text())["assumed"]["byte_id_offset"]
    assert int(stated.split(":")[0]) == N_SPECIALS
    assert tok.encode("A") == [ord("A") + N_SPECIALS]
    eng = engine(params)
    seen = []
    step = eng.step

    def spy():
        comps = step()
        seen.extend(t for c in comps for t in c.tokens)
        return comps

    eng.step = spy
    out = eng.generate_text([text, "short"], tok, max_new_tokens=12)
    assert len(out) == 2 and all(isinstance(o, str) for o in out)
    assert len(seen) == 24 and max(seen) < V and min(seen) >= 0
