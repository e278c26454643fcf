# attention="mla" with a learned selection (models/xing.py, GLM-5 class:
# a latent cache and an index-key cache beside it, a lightning indexer
# and an exact top-k, a held share of the experts, one residual stream)
# through both caches and through GenerationEngine, against the plain
# reference benchmark/reference/glm_dsa.py (float32, one boolean
# selection matrix, expanded masked attention over the whole sequence,
# no cache, experts by plain indexing). Tiny sizes: d 64, 4 heads,
# latent 32 + 8, index keys of 16 scored by 2 heads, 24 positions
# chosen (the test lengths run to 130, so most queries really select),
# 8 experts of which 2 a token + a shared one, 1 dense + 2 expert
# layers.
#
# Tolerances, each with its reason:
#   TOL = 1e-4 on logits of size ~3: weights are int8 with float32
#   scales, activations and both caches float32 here, so program and
#   reference differ only by the order of float32 sums; the largest
#   difference seen is 3e-6. A position chosen otherwise moves logits
#   by 1e-2 and more, so the logits also hold the SELECTION to the
#   reference's; the sets themselves are compared below, where a
#   difference is allowed only within rounding of the k-th score.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_dsa as ref
from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.models import xing
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.ops import sparse_select

TOL = 1e-4
CFG = decoder_config("tiny-glm")
V, K, E = CFG.vocab_size, CFG.experts_per_token, CFG.n_routed_experts
TOPK = CFG.index_topk
MAX_LEN, STEPS, BUCKETS = 128, 8, (8, 16, 32)


def dims_of(cfg, held=None):
    first, count = held or xing.held_experts(cfg)
    return dict(
        model_type="glm_moe_dsa", hidden_size=cfg.d_model,
        num_attention_heads=cfg.n_heads, num_hidden_layers=cfg.n_layers,
        vocab_size=cfg.vocab_size, intermediate_size=cfg.d_ff,
        rms_norm_eps=cfg.norm_eps,
        rope_parameters={"rope_theta": cfg.rope_theta,
                         "rope_type": "default"},
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        index_n_heads=cfg.index_n_heads,
        index_head_dim=cfg.index_head_dim, index_topk=cfg.index_topk,
        n_routed_experts=count,
        held={"first_expert": first,
              "router_experts": cfg.n_routed_experts},
        n_shared_experts=cfg.n_shared_experts, num_experts_per_tok=K,
        moe_intermediate_size=cfg.moe_intermediate_size,
        first_k_dense_replace=cfg.first_k_dense_replace,
        routed_scaling_factor=cfg.routed_scaling_factor)


DIMS = dims_of(CFG)


@pytest.fixture(scope="module")
def params():
    return xing.init_params(jax.random.PRNGKey(1), CFG, dtype=jnp.float32,
                            quantize=True)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # several rounds of the expanded attention, and several blocks of
    # the selection's counting, at these lengths
    monkeypatch.setattr(xing, "KV_BLOCK", 16)
    monkeypatch.setattr(ref, "PAD_TO", 8)


def greedy(logits, _key):
    return jnp.argmax(logits, -1).astype(jnp.int32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, V, size=n)


def piece_fn(cfg):
    return jax.jit(lambda params, toks, lens, pos0, slots, cache:
                   xing.prefill_piece(params, toks, lens, pos0, slots, cfg,
                                      cache))


def decode_fn(cfg):
    return jax.jit(lambda params, tok, pos, cache: xing.decode_tokens(
        params, tok, pos, cfg, cache, jax.random.PRNGKey(0), greedy,
        steps=STEPS, max_len=MAX_LEN, with_logits=True))


def prefill(fn, params, cache, slot, seq, piece=32):
    """Admit ``seq`` into ``slot`` a piece a wave (what the engine's
    admission does). → the logits after its last token, the cache."""
    at = 0
    while at < len(seq):
        n = min(len(seq) - at, piece)
        toks = np.zeros((1, piece), np.int32)
        toks[0, :n] = seq[at:at + n]
        logits, cache, _ = fn(params, jnp.asarray(toks), jnp.asarray([n]),
                              jnp.asarray([at]), jnp.asarray([slot]), cache)
        at += n
    return np.asarray(logits[0]), cache


def through_both_caches(cfg, params, seq, new):
    """``seq`` admitted in pieces into slot 1 of 2, then ``new`` tokens
    decoded greedily (slot 0 stands idle at MAX_LEN): the logits at the
    prompt's last position and at every decoded one, the tokens."""
    cache = xing.init_cache(cfg, 2, MAX_LEN, jnp.float32)
    first, cache = prefill(piece_fn(cfg), params, cache, 1, seq)
    step = decode_fn(cfg)
    toks, logits, pos = [int(first.argmax())], [first], len(seq)
    while len(toks) < new:
        t, cache, _, lg = step(params, jnp.asarray([0, toks[-1]]),
                               jnp.asarray([MAX_LEN, pos]), cache)
        toks += [int(v) for v in np.asarray(t)[:, 1]]
        logits += list(np.asarray(lg)[:, 1])
        pos += STEPS
    return np.stack(logits[:new]), toks[:new]


# ---------------------------------------------------------------------------
# (a) prefill, then decode through both caches == the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [5, 32, 70], ids=["short-of-a-bucket",
                                                "one-piece", "three-pieces"])
def test_prefill_then_decode_matches_the_reference(params, n):
    """Prompts under and over ``index_topk`` (24): the logits after the
    prompt and through two decode dispatches equal the reference's full
    forward pass over prompt + served tokens."""
    seq = tokens(n, seed=n)
    got, toks = through_both_caches(CFG, params, seq, 1 + 2 * STEPS)
    full = np.concatenate([seq, toks[:-1]])
    want = ref.logits_at(params, DIMS, full,
                         np.arange(n - 1, n - 1 + len(toks)))
    assert np.abs(got - want).max() < TOL


def test_the_selection_is_what_decides_the_logits(params):
    """The same prompt through the reference with every earlier
    position read (no selection) gives other logits by far more than
    TOL: the comparison above holds the selection, not a tolerance."""
    seq = tokens(70, seed=70)
    sparse = ref.logits_at(params, DIMS, seq, [69])
    dense = ref.logits_at(params, DIMS, seq, [69], dense=True)
    assert np.abs(sparse - dense).max() > 100 * TOL


# ---------------------------------------------------------------------------
# (b) the selected sets == the reference's
# ---------------------------------------------------------------------------


def test_the_selected_sets_are_the_references(params):
    """Layer by layer, the set every query of a 100-token piece reads
    in the admission program equals the reference's boolean selection,
    min(t + 1, index_topk) positions a row. Both sides are float32
    here, so a difference could only be a score within rounding of the
    k-th; none is seen, and none is allowed."""
    seq = tokens(100, seed=7)
    selected = []
    ref.hidden_states(params, DIMS, seq, selected=selected)
    s = len(seq)
    got = program_sets(params, seq)
    assert len(got) == len(selected) == CFG.n_layers
    for want, have in zip(selected, got):
        want = want[:s, :s]
        assert want.sum(-1).tolist() == [min(t + 1, TOPK)
                                         for t in range(s)]
        assert have.sum(-1).tolist() == want.sum(-1).tolist()
        assert (have == want).all()


def test_in_bfloat16_the_sets_differ_only_within_rounding_of_the_kth(
        params):
    """The served types: bfloat16 activations and caches against the
    float32 reference. In the FIRST layer (whose inputs are the
    embedding alone: deeper layers inherit the stream's own rounding) a
    query's set may differ from the reference's only by positions whose
    reference score stands within rounding of the row's k-th largest:
    under a twentieth of the row's spread, where bfloat16 operands move
    a score by about a hundredth of it; and few queries differ at
    all."""
    keep = ("scale", "router", "e_bias")        # served in float32
    half = jax.tree_util.tree_map_with_path(
        lambda path, a: a if a.dtype != jnp.float32
        or path[-1].key in keep else a.astype(jnp.bfloat16), params)
    seq = tokens(100, seed=7)
    selected, margins = [], []
    ref.hidden_states(half, DIMS, seq, selected=selected, margins=margins)
    have = program_sets(half, seq, dtype=jnp.bfloat16)[0]
    want = selected[0][:100, :100]
    diff = have != want
    assert (diff.sum(-1) > 0).mean() < 0.25
    assert np.abs(margins[0][:100, :100][diff]).max(initial=0.0) < 0.05
    assert have.sum(-1).tolist() == want.sum(-1).tolist()


def program_sets(params, seq, dtype=jnp.float32):
    """Every layer's ``[S, S]`` selection as the admission program
    makes it for ``seq`` admitted as one piece: ``select_piece`` is
    wrapped so that each layer's ``keep`` is evaluated over all blocks
    and handed out through the scan as a stacked output."""
    s = len(seq)
    pad = -(-s // xing.KV_BLOCK) * xing.KV_BLOCK
    real_select, real_attn = xing.select_piece, xing.piece_attention
    taken = []

    def attn(q, cache_a, li, slots, q_pos, kv_len, n_blocks, layer, cfg,
             keep=None):
        sel = jnp.concatenate([keep(j) for j in range(pad // xing.KV_BLOCK)],
                              axis=-1)
        jax.debug.callback(lambda a: taken.append(np.asarray(a)), sel)
        return real_attn(q, cache_a, li, slots, q_pos, kv_len, n_blocks,
                         layer, cfg, keep)

    xing.piece_attention = attn
    try:
        toks = np.zeros((1, pad), np.int32)
        toks[0, :s] = seq
        cache = xing.init_cache(CFG, 1, MAX_LEN, dtype)
        out = xing.prefill_piece(params, jnp.asarray(toks),
                                 jnp.asarray([s]), jnp.asarray([0]),
                                 jnp.asarray([0]), CFG, cache)
        jax.block_until_ready(out)
        jax.effects_barrier()
    finally:
        xing.select_piece, xing.piece_attention = real_select, real_attn
    return [a[0, :s, :s] for a in taken]


def test_a_decode_steps_selection_is_the_references_row(params):
    """The set a decoded token reads (cached columns, the dispatch's
    own rows, itself) is the reference's row for its position."""
    seq = tokens(60, seed=3)
    cache = xing.init_cache(CFG, 1, MAX_LEN, jnp.float32)
    _, cache = prefill(piece_fn(CFG), params, cache, 0, seq[:-1])
    real = xing.select_step
    rows = []

    def spy(*args):
        keep_c, keep_o = real(*args)
        jax.debug.callback(
            lambda c, o, w: rows.append((int(w), np.asarray(c[0]),
                                         np.asarray(o[0]))),
            keep_c, keep_o, args[6])
        return keep_c, keep_o

    xing.select_step = spy
    try:
        t, _, _, _ = xing.decode_tokens(
            params, jnp.asarray([int(seq[-1])]), jnp.asarray([59]), CFG,
            cache, jax.random.PRNGKey(0), greedy, steps=STEPS,
            max_len=MAX_LEN, with_logits=True)
        jax.block_until_ready(t)
        jax.effects_barrier()
    finally:
        xing.select_step = real
    full = np.concatenate([seq, np.asarray(t)[:-1, 0]])
    selected = []
    ref.hidden_states(params, DIMS, full, selected=selected)
    assert len(rows) == STEPS * CFG.n_layers
    for i, (w, keep_c, keep_o) in enumerate(rows):
        layer = i % CFG.n_layers     # the layer scans run inside a step
        pos = 59 + w
        have = np.zeros(len(full) + STEPS, bool)
        have[:59] = keep_c[:59]
        assert not keep_c[59:].any()
        have[59:59 + w] = keep_o[:w]
        have[pos] = keep_o[-1]
        assert not keep_o[w:-1].any()
        want = selected[layer][pos, :len(full)]
        assert (have[:len(full)] == want).all(), (i, w)
        assert have.sum() == min(pos + 1, TOPK)


# ---------------------------------------------------------------------------
# (c) the threshold is the exact top-k, ties to the lower position
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["distinct", "ties", "zeros", "few-valid"])
def test_the_threshold_is_top_k_with_ties_to_the_lower_position(case):
    rng = np.random.default_rng(5)
    rows, cols, k = 6, 96, 17
    scores = rng.normal(size=(rows, cols)).astype(np.float32)
    valid = np.ones((rows, cols), bool)
    if case == "ties":
        scores = np.round(scores * 2) / 2          # many equal scores
    if case == "zeros":
        scores = np.where(rng.random((rows, cols)) < 0.8, 0.0,
                          scores).astype(np.float32)
        scores[0] *= -1.0                          # -0.0 among the zeros
    if case == "few-valid":
        valid = np.arange(cols)[None, :] < np.asarray([1, 5, 16, 17, 18,
                                                       96])[:, None]
    keys = sparse_select.sort_keys(jnp.asarray(scores), jnp.asarray(valid))
    n_valid = valid.sum(-1)
    kk = jnp.asarray(np.minimum(k, n_valid), jnp.int32)
    thr, cut = sparse_select.threshold(sparse_select.count_over(keys), kk,
                                       cols)
    got = np.asarray(sparse_select.chosen(keys, jnp.arange(cols),
                                          thr[:, None], cut[:, None]))
    for r in range(rows):
        order = sorted(np.flatnonzero(valid[r]),
                       key=lambda c: (-scores[r, c], c))
        want = np.zeros(cols, bool)
        want[order[:k]] = True
        assert (got[r] == want).all(), (case, r)


PIECE_CASES = ["distinct", "ties", "zeros", "few-valid", "unequal-rows",
               "causal-k"]


@pytest.mark.parametrize("case", PIECE_CASES)
def test_the_threshold_kernel_returns_the_xla_rounds_integers(
        case, monkeypatch):
    """An admission piece's threshold on the kernel's route (ops/
    select_threshold.py through the interpreter: the rounds over a
    query tile's keys, of its row's own live blocks) against the XLA
    rounds over the same buffer of sort keys: the same (thr, cut) for
    every query, and the chosen set ``jax.lax.top_k``'s, ties to the
    lower position."""
    from copilot_for_consensus_tpu.ops import (
        latent_prefill_attention,
        select_threshold,
    )

    rng = np.random.default_rng(6)
    n, s, t, k = 2, 16, 96, 17          # six blocks of 16, tiles of 8
    monkeypatch.setattr(select_threshold, "TQ", 8)
    scores = rng.normal(size=(n, s, t)).astype(np.float32)
    kv_len = np.asarray([t, t])
    q_pos = np.full((n, s), t - 1)       # every query sees its whole row
    if case == "ties":
        scores = np.round(scores * 2) / 2
    if case == "zeros":
        scores = np.where(rng.random((n, s, t)) < 0.8, 0.0,
                          scores).astype(np.float32)
        scores[0] *= -1.0
    if case == "few-valid":
        kv_len = np.asarray([18, 5])
    if case == "unequal-rows":
        # one wave: six live blocks beside two, the second row tied
        kv_len = np.asarray([t - 3, 29])
        scores[1] = np.round(scores[1] * 2) / 2
    if case == "causal-k":
        # a first piece: query i sees i + 1 columns, fewer than k in
        # the first tile and part of the second
        kv_len = np.asarray([s, s + 32])
        q_pos = kv_len[:, None] - s + np.arange(s)[None, :]
    col = jnp.arange(t)
    valid = np.asarray(xing._seen(col, jnp.asarray(q_pos),
                                  jnp.asarray(kv_len)))
    buf = sparse_select.sort_keys(jnp.asarray(scores), jnp.asarray(valid))
    n_blocks = jnp.int32(-(-int(kv_len.max()) // xing.KV_BLOCK))

    def route(kernel):
        monkeypatch.setattr(latent_prefill_attention, "serves",
                            lambda block: kernel)
        fn = jax.jit(lambda buf, q_pos, kv_len, n_blocks:
                     xing.piece_threshold(buf, q_pos, kv_len, n_blocks, k))
        args = (buf, jnp.asarray(q_pos), jnp.asarray(kv_len), n_blocks)
        assert ("select_threshold" in str(jax.make_jaxpr(fn)(*args))) \
            == kernel
        return fn(*args)

    (thr_x, cut_x), (thr, cut) = route(False), route(True)
    assert np.array_equal(thr, thr_x) and np.array_equal(cut, cut_x)
    if case in ("ties", "zeros", "unequal-rows"):
        assert (np.asarray(cut) < t).any()       # the ties' branch ran
    got = np.asarray(sparse_select.chosen(buf, col, thr[..., None],
                                          cut[..., None]))
    for r in range(n):
        for i in range(s):
            kk = min(k, int(valid[r, i].sum()))
            # (-0.0 and 0.0 are one score: + 0.0 makes them one float)
            _, top = jax.lax.top_k(jnp.where(valid[r, i],
                                             scores[r, i] + 0.0, -jnp.inf),
                                   kk)
            assert set(np.flatnonzero(got[r, i])) == set(
                np.asarray(top).tolist()), (case, r, i)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_keys_a_threshold_reads_follow_its_route(kernel, monkeypatch):
    from copilot_for_consensus_tpu.ops import latent_prefill_attention

    monkeypatch.setattr(latent_prefill_attention, "serves",
                        lambda block: kernel)
    # blocks of 16: rows of 3 and 1 live blocks, pieces of 32 queries
    got = xing.threshold_keys_read([40, 9], 32, 128)
    assert got == (32 * 16 * (3 + 1) if kernel
                   else sparse_select.PASSES * 2 * 32 * 16 * 3)


# ---------------------------------------------------------------------------
# (d) index_topk >= the length is dense latent attention
# ---------------------------------------------------------------------------


def test_a_selection_wider_than_the_sequence_is_dense_attention(params):
    """With ``index_topk`` at the cache's extent every query reads every
    earlier position: the program's logits equal the reference's with
    NO selection (plain MLA), through both caches."""
    wide = decoder_config("tiny-glm", index_topk=MAX_LEN)
    seq = tokens(70, seed=11)
    got, toks = through_both_caches(wide, params, seq, 1 + STEPS)
    full = np.concatenate([seq, toks[:-1]])
    want = ref.logits_at(params, DIMS, full,
                         np.arange(69, 69 + len(toks)), dense=True)
    assert np.abs(got - want).max() < TOL


# ---------------------------------------------------------------------------
# (e) the held shares add up to the uncut layer
# ---------------------------------------------------------------------------


def test_held_shares_of_the_experts_add_up_to_the_uncut_reference(params):
    """8 experts in shares of 2: each share's engine-side layer (its
    own experts' terms plus the shared expert) less the shared expert,
    summed over the four shares, plus the shared expert ONCE, is the
    uncut layer: in the program's ``ffn`` and in the reference's
    ``routed_part`` given the same shares."""
    layer = jax.tree.map(lambda a: a[1], {
        k: v for k, v in params["moe"].items() if k not in xing.EXPERTS})
    hid = jax.random.normal(jax.random.PRNGKey(3), (1, 24, CFG.d_model),
                            jnp.float32)
    live = jnp.ones((1, 24), bool)
    li = jnp.int32(1)
    whole, _ = xing.ffn(hid, layer, {k: params["moe"][k]
                                     for k in xing.EXPERTS},
                        li, CFG, live, jnp.float32)
    shared = xing.L.swiglu(hid, layer).astype(jnp.float32)
    total = shared
    for first in range(0, E, 2):
        share = {k: jax.tree.map(lambda a: a[:, first:first + 2],
                                 params["moe"][k]) for k in xing.EXPERTS}
        part, counts = xing.ffn(
            hid, layer, share, li,
            decoder_config("tiny-glm", held_experts=(first, 2)), live,
            jnp.float32)
        assert int(counts[0]) <= 2               # counted over its share
        total = total + (part - shared)
    assert np.abs(np.asarray(total - whole)).max() < TOL

    # and the reference, given each share, adds up to its uncut pass
    flat = hid[0]
    chosen, gates = xing.route(flat, layer, CFG)
    uncut = ref.routed_part(jnp.zeros_like(flat), flat, chosen, gates,
                            params["moe"], 1, None, (0, E))
    parts = jnp.zeros_like(flat)
    for first in range(0, E, 2):
        share = dict(params["moe"], **{
            k: jax.tree.map(lambda a: a[:, first:first + 2],
                            params["moe"][k]) for k in xing.EXPERTS})
        parts = parts + ref.routed_part(
            jnp.zeros_like(flat), flat, chosen, gates, share, 1, None,
            (first, 2))
    assert np.abs(np.asarray(parts - uncut)).max() < TOL
    # the program's whole layer is the reference's routed sum + shared
    assert np.abs(np.asarray(whole[0] - shared[0] - uncut)).max() < TOL


def test_a_held_share_through_both_caches_matches_the_reference():
    """The served path with a share: a config that holds experts 2-5 of
    8 serves, through admission and decode, the reference's logits for
    the same share (the router 8 wide, the absent experts' terms left
    out alike)."""
    cfg = decoder_config("tiny-glm", held_experts=(2, 4))
    p = xing.init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32,
                         quantize=True)
    assert p["moe"]["we_up"]["q"].shape[1] == 4
    assert p["moe"]["router"].shape[-1] == 8
    seq = tokens(50, seed=13)
    got, toks = through_both_caches(cfg, p, seq, 1 + STEPS)
    full = np.concatenate([seq, toks[:-1]])
    want = ref.logits_at(p, dims_of(cfg), full,
                         np.arange(49, 49 + len(toks)))
    assert np.abs(got - want).max() < TOL
    whole = ref.logits_at(p, dims_of(cfg), full, [49],
                          held=(2, 4))
    assert np.abs(want[:1] - whole).max() == 0


# ---------------------------------------------------------------------------
# (f) through GenerationEngine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(params):
    return GenerationEngine(
        CFG, params, num_slots=4, max_len=MAX_LEN, prefill_buckets=BUCKETS,
        admission_token_budget=64, eos_id=-1, quantize="int8",
        dtype=jnp.float32)


def test_the_engine_serves_the_references_best_tokens(engine, params):
    """submit/step through admission in pieces (two rows a wave), decode
    dispatches and retirement: every served token is the reference's
    best after the tokens before it (a gap under TOL)."""
    prompts = [tokens(n, seed=10 + n).tolist() for n in (5, 70, 41, 17)]
    done = engine.generate(prompts, 20)
    for prompt, c in zip(prompts, done):
        seq = prompt + list(c.tokens)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        want = ref.logits_at(params, DIMS, seq, at)
        gap = want.max(-1) - want[np.arange(len(at)), c.tokens]
        assert gap.max() < TOL
    kinds = {r.kind for r in engine.telemetry.recorder.records()}
    assert kinds == {"prefill", "decode"}
    assert set(engine._cache) == {"dense", "moe", "dense_idx", "moe_idx"}


def test_the_engine_records_what_the_selection_read(engine):
    prompts = [tokens(n, seed=30 + n).tolist() for n in (40, 12)]
    engine.generate(prompts, 9)
    recs = list(engine.telemetry.recorder.records())[-4:]
    assert {r.kind for r in recs} == {"prefill", "decode"}
    for r in recs:
        if r.kind == "prefill":
            # one wave of two whole prompts: every causal pair scored,
            # min(index_topk, position + 1) read a query
            assert r.live_tokens == r.attn_pairs
            if r.tokens == 52:
                assert r.selected_tokens == sum(
                    min(t + 1, TOPK) for n in (40, 12) for t in range(n))
            assert r.index_tokens_read == 0
            # off a TPU the threshold's XLA rounds walk the wave's live
            # blocks (of 16 here) of every row, padded ones too
            walked, rest = divmod(
                r.select_keys_read,
                sparse_select.PASSES * r.padded_tokens * xing.KV_BLOCK)
            assert 1 <= walked <= MAX_LEN // xing.KV_BLOCK and rest == 0
            # and as many rounds of keys and values are expanded
            assert r.expand_bytes_moved == walked * xing.expand_bytes_moved(
                [1] * r.batch, MAX_LEN, CFG, 4)
            continue
        assert r.select_keys_read == 0 and r.expand_bytes_moved == 0
        assert r.index_tokens_read == STEPS * 4 * MAX_LEN
        assert r.state_tokens_read == STEPS * 4 * MAX_LEN
        assert 0 < r.selected_tokens <= r.live_tokens
        assert r.selected_tokens <= r.rows * STEPS * TOPK
        # the first decode dispatch: sequences of 40 and 12 tokens
        lens = [40, 12] if r.rows == 2 else None
        if lens and r.live_tokens == sum(n + t + 1 for n in lens
                                         for t in range(STEPS)):
            assert r.selected_tokens == sum(
                min(n + t + 1, TOPK) for n in lens for t in range(STEPS))
    assert recs[-1].expert_rows > 0
    # a share is held: the rows the grouped matmul keeps are the pairs
    # counted, and a visit multiplies a whole tile for them
    assert all(0 < r.expert_group_rows == r.expert_rows
               <= r.expert_tile_rows for r in recs)


def test_the_kernel_route_serves_the_references_tokens(params, monkeypatch):
    """The TPU's decode route (here through the Pallas interpreter):
    the latent kernel walks each slot's live blocks under the
    selection's mask, and every served token is still the reference's
    best."""
    from copilot_for_consensus_tpu.ops import latent_attention

    monkeypatch.setattr(latent_attention, "BLOCK", 128)
    monkeypatch.setattr(latent_attention, "serves", lambda extent: True)
    eng = GenerationEngine(
        CFG, params, num_slots=4, max_len=512, prefill_buckets=BUCKETS,
        admission_token_budget=64, eos_id=-1, quantize="int8",
        dtype=jnp.float32)
    assert eng._reads_latent_blocks()
    prompts = [tokens(n, seed=50 + n).tolist() for n in (5, 250, 130)]
    done = eng.generate(prompts, 12)
    for prompt, c in zip(prompts, done):
        seq = prompt + list(c.tokens)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        want = ref.logits_at(params, DIMS, seq, at)
        gap = want.max(-1) - want[np.arange(len(at)), c.tokens]
        assert gap.max() < TOL
    dec = [r for r in eng.telemetry.recorder.records()
           if r.kind == "decode"]
    assert all(r.state_tokens_read % 128 == 0 and r.state_tokens_read
               < r.index_tokens_read for r in dec)


@pytest.mark.parametrize("option,word", [
    ({"prefix_cache_blocks": 8}, "prefix_cache_blocks"),
    ({"kv_pool_blocks": 8}, "kv_pool_blocks"),
    ({"kv_dtype": "fp8"}, "kv_dtype"),
    ({"quantize": "int4"}, "int4"),
])
def test_options_that_cannot_serve_two_kinds_of_state_refuse_it(
        params, option, word):
    kw = dict(num_slots=2, max_len=MAX_LEN, prefill_buckets=BUCKETS,
              quantize="int8", dtype=jnp.float32)
    kw.update(option)
    with pytest.raises(ValueError, match=word):
        GenerationEngine(CFG, params, **kw)


@pytest.mark.parametrize("bad", [
    {"held_experts": (6, 4)}, {"index_head_dim": 4}, {"index_n_heads": 0}])
def test_a_config_that_is_no_share_or_no_indexer_is_refused(bad):
    cfg = decoder_config("tiny-glm", **bad)
    with pytest.raises(ValueError, match="held_experts|index_topk"):
        GenerationEngine(cfg, None, num_slots=2, max_len=MAX_LEN,
                         prefill_buckets=BUCKETS, dtype=jnp.float32)


def test_an_admission_of_several_pieces_through_the_kernel_is_the_xla_routes(
        params, monkeypatch):
    """A prompt of 100 tokens admitted in pieces of 32 over rounds of
    16, on the admission kernel's route (ops/latent_prefill_attention.py:
    the selection's mask one more operand) and on the XLA rounds': the
    same chosen sets in every layer of every piece, the same last
    logits, both caches."""
    from copilot_for_consensus_tpu.ops import latent_prefill_attention

    seq = tokens(100, seed=9)
    real_attn = xing.piece_attention

    def admitted(kernel):
        monkeypatch.setattr(latent_prefill_attention, "serves",
                            lambda block: kernel)
        taken = []

        def attn(q, cache_a, li, slots, q_pos, kv_len, n_blocks, layer, cfg,
                 keep=None):
            sel = jnp.concatenate(
                [keep(j) for j in range(MAX_LEN // xing.KV_BLOCK)], axis=-1)
            jax.debug.callback(lambda a: taken.append(np.asarray(a)), sel,
                               ordered=True)
            return real_attn(q, cache_a, li, slots, q_pos, kv_len, n_blocks,
                             layer, cfg, keep)

        monkeypatch.setattr(xing, "piece_attention", attn)
        fn = piece_fn(CFG)
        text = str(jax.make_jaxpr(fn)(
            params, jnp.zeros((1, 32), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            xing.init_cache(CFG, 2, MAX_LEN, jnp.float32)))
        assert ("mla_prefill_attention" in text) == kernel
        taken.clear()
        logits, cache = prefill(fn, params,
                                xing.init_cache(CFG, 2, MAX_LEN, jnp.float32),
                                1, seq)
        jax.effects_barrier()
        return logits, cache, taken

    (want, cache_x, sets_x), (got, cache_k, sets_k) = (
        admitted(False), admitted(True))
    assert len(sets_x) == len(sets_k) == 4 * CFG.n_layers
    for a, b in zip(sets_x, sets_k):
        assert np.array_equal(a, b)
    # past the first pieces most queries really select
    assert sets_k[-1][0, :4].sum(-1).tolist() == [TOPK] * 4
    assert np.abs(got - want).max() < TOL
    for name in cache_x:
        assert np.abs(np.asarray(cache_k[name][:, 1, :, :100])
                      - np.asarray(cache_x[name][:, 1, :, :100])).max() < TOL


def test_an_admission_through_the_threshold_kernel_is_the_xla_rounds_exactly(
        params, monkeypatch):
    """A prompt of 100 tokens admitted in pieces of 32 and then, in ONE
    wave of two rows of unequal extents, its last piece (7 live blocks)
    beside another prompt's first (2), with the threshold on the
    kernel's route (ops/select_threshold.py) and on the XLA rounds',
    attention through the admission kernel both times: the threshold's
    integers are the same, so every logit and both caches are the same
    bit for bit."""
    from unittest import mock

    from copilot_for_consensus_tpu.ops import latent_prefill_attention

    monkeypatch.setattr(latent_prefill_attention, "serves",
                        lambda block: True)
    seq, other = tokens(100, seed=9), tokens(32, seed=10)
    real = xing.piece_threshold

    def xla_rounds(*args):
        with mock.patch.object(latent_prefill_attention, "serves",
                               lambda block: False):
            return real(*args)

    def admitted(kernel):
        monkeypatch.setattr(xing, "piece_threshold",
                            real if kernel else xla_rounds)
        fn = piece_fn(CFG)
        cache = xing.init_cache(CFG, 2, MAX_LEN, jnp.float32)
        toks = np.zeros((2, 32), np.int32)
        toks[0], toks[1, :4] = other, seq[96:]
        args = (params, jnp.asarray(toks), jnp.asarray([32, 4]),
                jnp.asarray([0, 96]), jnp.asarray([0, 1]))
        text = str(jax.make_jaxpr(fn)(*args, cache))
        assert "mla_prefill_attention" in text
        assert ("select_threshold" in text) == kernel
        first, cache = prefill(fn, params, cache, 1, seq[:96])
        last, cache, _ = fn(*args, cache)
        return first, np.asarray(last), cache

    (first_x, last_x, cache_x), (first_k, last_k, cache_k) = (
        admitted(False), admitted(True))
    assert np.array_equal(first_k, first_x)
    assert np.array_equal(last_k, last_x)
    for name in cache_x:
        assert np.array_equal(np.asarray(cache_k[name]),
                              np.asarray(cache_x[name]))
