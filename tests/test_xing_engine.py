# attention="mla" (models/xing.py, Xing4.0 class: a latent cache,
# dropless sparse experts beside a shared one, a four-stream residual)
# through the cache and through GenerationEngine, against the plain
# reference benchmark/reference/xing.py (float32, expanded attention
# over the whole sequence, no cache, experts by plain indexing). Tiny
# sizes: d 64, 4 streams, 4 heads, latent 32 + 8, 8 experts of which 2
# a token + a shared one, 1 dense + 2 expert layers.
#
# Tolerances, each with its reason:
#   TOL = 1e-4 on logits of size ~3: weights are int8 with float32
#   scales, activations and cache float32 here, so program and
#   reference differ only by the order of float32 sums (blocks of a
#   running softmax against one row, the absorbed form against the
#   expanded); the largest difference seen is 1e-5. A latent rounded
#   to float8 moves logits by 1e-2 and more, a flipped expert by 0.1
#   and more: in float32 a near-tie close enough to flip was not seen.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import xing_engine
from benchmark.reference import xing as ref
from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.models import xing
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.ops import grouped_matmul
from copilot_for_consensus_tpu.ops.grouped_matmul import grouped_qmatmul

TOL = 1e-4
CFG = decoder_config("tiny-xing")
V, K, E = CFG.vocab_size, CFG.experts_per_token, CFG.n_routed_experts
DIMS = dict(
    model_type="xing4_0", hidden_size=CFG.d_model,
    num_attention_heads=CFG.n_heads, num_key_value_heads=CFG.n_kv_heads,
    num_hidden_layers=CFG.n_layers, vocab_size=V,
    intermediate_size=CFG.d_ff, rms_norm_eps=CFG.norm_eps,
    rope_theta=CFG.rope_theta, rope_scaling=dict(CFG.rope_scaling),
    q_lora_rank=CFG.q_lora_rank, kv_lora_rank=CFG.kv_lora_rank,
    qk_nope_head_dim=CFG.qk_nope_head_dim,
    qk_rope_head_dim=CFG.qk_rope_head_dim, v_head_dim=CFG.v_head_dim,
    n_routed_experts=E, n_shared_experts=CFG.n_shared_experts,
    num_experts_per_tok=K, moe_intermediate_size=CFG.moe_intermediate_size,
    first_k_dense_replace=CFG.first_k_dense_replace,
    routed_scaling_factor=CFG.routed_scaling_factor, hc_mult=CFG.hc_mult,
    hc_sinkhorn_iters=CFG.hc_sinkhorn_iters, hc_eps=CFG.hc_eps,
    mhc_h_res_clamp_min=CFG.mhc_h_res_clamp_min,
    mhc_h_res_clamp_max=CFG.mhc_h_res_clamp_max)
MAX_LEN, STEPS, BUCKETS = 128, 8, (8, 16, 32)


@pytest.fixture(scope="module")
def params():
    return xing.init_params(jax.random.PRNGKey(1), CFG, dtype=jnp.float32,
                            quantize=True)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # several rounds of the expanded attention at these lengths
    monkeypatch.setattr(xing, "KV_BLOCK", 16)


def greedy(logits, _key):
    return jnp.argmax(logits, -1).astype(jnp.int32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, V, size=n)


@jax.jit
def piece_fn(params, toks, lens, pos0, slots, cache):
    return xing.prefill_piece(params, toks, lens, pos0, slots, CFG, cache)


@jax.jit
def decode_fn(params, tok, pos, cache):
    return xing.decode_tokens(
        params, tok, pos, CFG, cache, jax.random.PRNGKey(0), greedy,
        steps=STEPS, max_len=MAX_LEN, with_logits=True)


def prefill(params, cache, rows, piece=32, piece_fn=piece_fn):
    """Admit ``rows`` ``[(slot, seq)]`` together, a piece of at most
    ``piece`` a wave (what the engine's admission does). Returns each
    row's logits after its last token, the cache, the summed counts."""
    at = [0] * len(rows)
    last = [None] * len(rows)
    counts = np.zeros(xing.N_COUNTS, np.int64)
    while any(a < len(seq) for a, (_s, seq) in zip(at, rows)):
        live = [i for i, (_s, seq) in enumerate(rows) if at[i] < len(seq)]
        toks = np.zeros((len(live), piece), np.int32)
        lens = []
        for r, i in enumerate(live):
            n = min(len(rows[i][1]) - at[i], piece)
            toks[r, :n] = rows[i][1][at[i]:at[i] + n]
            lens.append(n)
        logits, cache, c = piece_fn(
            params, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray([at[i] for i in live]),
            jnp.asarray([rows[i][0] for i in live]), cache)
        counts += np.asarray(c)
        for r, i in enumerate(live):
            at[i] += lens[r]
            last[i] = np.asarray(logits[r])
    return last, cache, counts


def decode(params, cache, tok, pos, dispatches):
    logits, toks, counts = [], [], []
    for _ in range(dispatches):
        t, cache, c, lg = decode_fn(params, jnp.asarray(tok),
                                    jnp.asarray(pos), cache)
        logits.append(np.asarray(lg))
        toks.append(np.asarray(t))
        counts.append(np.asarray(c))
        tok = np.asarray(t[-1])
        pos = np.where(np.asarray(pos) < MAX_LEN, np.asarray(pos) + STEPS,
                       pos)
    return np.concatenate(logits), np.concatenate(toks), cache, counts


# ---------------------------------------------------------------------------
# (a) prefill, then decode through the latent cache == the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [5, 32, 70], ids=["short-of-a-bucket",
                                                "one-piece",
                                                "three-pieces"])
def test_prefill_matches_the_reference(params, n):
    seq = tokens(n, seed=n)
    cache = xing.init_cache(CFG, 2, MAX_LEN, dtype=jnp.float32)
    (got,), _cache, _c = prefill(params, cache, [(1, seq)])
    want = ref.logits_at(params, DIMS, seq, [n - 1])[0]
    assert np.abs(got - want).max() < TOL


def test_decode_across_a_dispatch_edge_matches_the_reference(params):
    """Two slots of different lengths, one parked; three dispatches:
    the later ones read what the earlier ones merged."""
    seqs = [tokens(40, 1), tokens(13, 2)]
    cache = xing.init_cache(CFG, 3, MAX_LEN, dtype=jnp.float32)
    last, cache, _c = prefill(params, cache, [(0, seqs[0]), (2, seqs[1])])
    tok = np.array([int(last[0].argmax()), 0, int(last[1].argmax())])
    pos = np.array([40, MAX_LEN, 13])
    logits, toks, _cache, _c = decode(params, cache, tok, pos, 3)
    for slot, seq in ((0, seqs[0]), (2, seqs[1])):
        full = list(seq) + [int(tok[slot])] + toks[:, slot].tolist()
        at = np.arange(len(seq), len(seq) + 3 * STEPS)
        want = ref.logits_at(params, DIMS, full[:-1], at)
        assert np.abs(logits[:, slot] - want).max() < TOL


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


# ---------------------------------------------------------------------------
# (b) the absorbed form == the expanded form
# ---------------------------------------------------------------------------


def test_absorbed_attention_equals_expanded_attention(params):
    """The token at position 50, once as an admission piece of one
    token (expanded: keys and values of every head from the cache) and
    once as a decode step (absorbed: all heads score the shared rows)."""
    seq = tokens(51, 3)
    cache = xing.init_cache(CFG, 1, MAX_LEN, dtype=jnp.float32)
    _last, cache, _c = prefill(params, cache, [(0, seq[:50])])
    piece = np.zeros((1, 8), np.int32)
    piece[0, 0] = seq[50]
    expanded, _cache, _c = piece_fn(
        params, jnp.asarray(piece), jnp.asarray([1]), jnp.asarray([50]),
        jnp.asarray([0]), cache)
    win = {k: jnp.zeros((a.shape[0], 1, STEPS, a.shape[2]), a.dtype)
           for k, a in cache.items()}
    absorbed, _cols, _c = xing.decode_step(
        params, jnp.asarray(seq[50:51]), jnp.asarray([50]), jnp.int32(0),
        CFG, cache, win, MAX_LEN)
    assert np.abs(np.asarray(expanded) - np.asarray(absorbed)).max() < TOL


# ---------------------------------------------------------------------------
# (c) dropless: batch-mates that crowd a token's experts change nothing
# ---------------------------------------------------------------------------


def test_a_rows_logits_do_not_depend_on_its_batch_mates(params):
    """The same prompt beside three other prompts and beside three
    copies of itself (every copy chooses the same experts, so each of
    them is four times as crowded): bit for bit the same logits. (The
    two waves have one shape: XLA picks a matmul's tiling by its row
    count, so a wave of one row rounds its dense layers otherwise.) A
    capacity dispatch (models/moe.py) would have dropped some."""
    seq = tokens(32, 4)
    cache = xing.init_cache(CFG, 4, MAX_LEN, dtype=jnp.float32)
    others, _cache, c1 = prefill(
        params, cache,
        [(0, seq)] + [(s, tokens(32, 40 + s)) for s in (1, 2, 3)])
    crowd, _cache, c4 = prefill(params, cache,
                                [(s, seq) for s in range(4)])
    for got in crowd:
        np.testing.assert_array_equal(got, others[0])
    # as many pairs, on a quarter of the tokens' choices
    assert c4[1] == c1[1] and c4[0] < c1[0] and c4[2] > c1[2]
    # and alone, a wave of one row: the same up to the tiling
    (alone,), _cache, _c = prefill(params, cache, [(0, seq)])
    assert np.abs(alone - crowd[0]).max() < TOL


def test_decode_rows_do_not_depend_on_their_batch_mates(params):
    seq = tokens(20, 5)
    cache = xing.init_cache(CFG, 4, MAX_LEN, dtype=jnp.float32)
    last, cache, _c = prefill(params, cache, [(s, seq) for s in range(4)])
    tok = np.full(4, int(last[0].argmax()))
    alone, _t, _c, _n = decode(params, cache, tok,
                               np.array([20] + [MAX_LEN] * 3), 1)
    crowd, _t, _c, _n = decode(params, cache, tok, np.full(4, 20), 1)
    for slot in range(4):
        np.testing.assert_array_equal(crowd[:, slot], alone[:, 0])


# ---------------------------------------------------------------------------
# (d) shares of the experts add up to the layer
# ---------------------------------------------------------------------------


def test_disjoint_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """Four devices with two experts each: every one routes over all
    eight and adds its own experts' terms; the four routed parts and
    the shared expert, counted once, are the whole layer, which is the
    reference's."""
    layer = jax.tree.map(lambda a: a[1], params["moe"])
    experts = {k: params["moe"][k] for k in xing.EXPERTS}
    hid = jnp.asarray(np.random.default_rng(6).standard_normal(
        (1, 24, CFG.d_model)), jnp.float32)
    live = jnp.ones((1, 24), bool)
    whole, counts = xing.ffn(hid, layer, experts, jnp.int32(1), CFG, live,
                             jnp.float32)
    shared = xing.L.swiglu(hid, layer).astype(jnp.float32)
    parts, kept = [], []
    for first in range(0, E, 2):
        held = {k: jax.tree.map(lambda a: a[:, first:first + 2], v)
                for k, v in experts.items()}
        part, c = xing.routed_experts(
            hid[0], layer, held, jnp.int32(1), CFG, live[0],
            held=(first, 2), dtype=jnp.float32)
        # the routing is counted over all experts; the rows the grouped
        # matmul keeps are this share's
        np.testing.assert_array_equal(np.asarray(c[:3]),
                                      np.asarray(counts[:3]))
        kept.append(int(c[3]))
        parts.append(np.asarray(part))
    assert sum(kept) == int(counts[3]) == int(counts[1])
    np.testing.assert_allclose(np.asarray(shared[0]) + sum(parts),
                               np.asarray(whole[0]), atol=1e-5)
    # and the reference's feed-forward part on the same input
    scores = jax.nn.sigmoid(hid[0] @ layer["router"])
    _, chosen = jax.lax.top_k(scores + layer["e_bias"], K)
    picked = jnp.take_along_axis(scores, chosen, -1)
    gates = CFG.routed_scaling_factor * picked / picked.sum(-1,
                                                            keepdims=True)
    want = ref.routed_part(
        jnp.asarray(shared[0]), hid[0], chosen, gates, params["moe"], 1,
        None)
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(want),
                               atol=1e-4)


# m, groups' sizes, k, n, the row tile the shapes give, the layer read;
# the rows past the sizes' sum belong to no group
GROUPED_CASES = {
    # 8 rows a group: the decode regime's one short m-tile, deep k
    "a-handful-of-rows": (64, [10, 0, 7, 20, 0, 0, 3, 9], 256, 384, 64, 1),
    # 32 rows a group and more: the row tile follows them
    "a-group-of-exactly-a-tile": (128, [32, 32, 20, 10], 64, 128, 32, 1),
    "a-group-across-several-tiles": (128, [5, 100, 3, 0], 64, 128, 32, 0),
    "an-empty-group-between-two-full-ones":
        (128, [32, 0, 64, 0], 64, 128, 32, 1),
    "rows-of-no-group-at-the-end": (256, [10, 0, 15, 7], 64, 128, 64, 1),
    "the-first-layer-of-the-stack": (128, [40, 30, 8, 50], 64, 128, 32, 0),
    "several-n-tiles": (96, [20, 0, 41, 30], 192, 384, 16, 1),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
def test_the_grouped_kernel_equals_ragged_dot(case, monkeypatch):
    """ops/grouped_matmul.py (the TPU's route, here through the Pallas
    interpreter) against jax.lax.ragged_dot over dequantized experts,
    in both of its regimes: groups that are empty, that fill a tile to
    the row and that span several, rows that belong to no group, a
    layer read out of the stack in place by a traced index; and what it
    says of its tiles against a recount."""
    m, sizes, k, n, tm, li = GROUPED_CASES[case]
    if case == "several-n-tiles":
        # a block of 128 columns is all that fits (shapes no other
        # case has: the jitted kernel is cached by them)
        monkeypatch.setattr(grouped_matmul, "RESIDENT", k * 128)
    tiles = grouped_matmul.tiling(m, len(sizes), k, n)
    assert tiles.tm == tm and tiles.resident == (m // len(sizes) >= 16)
    assert n // tiles.tn == (3 if case == "several-n-tiles" else
                             n // 128 if not tiles.resident else 1)
    rng = np.random.default_rng(7)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    q = jnp.asarray(rng.integers(-127, 128, (2, len(sizes), k, n)),
                    jnp.int8)
    scale = jnp.asarray(rng.uniform(0.005, 0.015, (2, len(sizes), 1, n)),
                        jnp.float32)
    got = grouped_qmatmul(lhs, q, scale, jnp.asarray(sizes), jnp.int32(li))
    want = jax.lax.ragged_dot(
        lhs.astype(jnp.float32), q[li].astype(jnp.float32) * scale[li],
        jnp.asarray(sizes), precision="highest")
    used = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:used]),
                               np.asarray(want[:used]), atol=1e-3)
    # a visit for every m-tile a group has a row in, tm rows each
    ends = np.cumsum(sizes)
    visits = sum(-(-e // tm) - (e - size) // tm
                 for e, size in zip(ends, sizes) if size)
    assert grouped_matmul.tile_counts(jnp.asarray(sizes), m, k, n
                                      ).tolist() == [used, visits * tm]


# ---------------------------------------------------------------------------
# (e) the residual maps: doubly stochastic, and alive when seeded
# ---------------------------------------------------------------------------


def test_sinkhorn_rows_and_columns_sum_to_one():
    rng = np.random.default_rng(8)
    # logits of the size the seeded maps give (std about 0.45)
    logits = [[jnp.asarray(0.5 * rng.standard_normal(500), jnp.float32)
               for _ in range(4)] for _ in range(4)]
    a = np.asarray(xing.sinkhorn(logits, 20, 1e-6))       # [4, 4, S]
    assert np.abs(a.sum(0) - 1).max() < 1e-4
    assert np.abs(a.sum(1) - 1).max() < 1e-4
    assert a.min() >= 0


@pytest.mark.parametrize("seed", [1, 2**31 + 5], ids=["small", "large"])
def test_the_benchmarks_seeded_maps_are_alive(seed):
    """The builder's weights at the tiny size, over a replayed request:
    H_res mixes the streams (off-diagonal mass between 0.1 and 0.9) and
    H_pre, H_post differ from token to token — else a wrong Sinkhorn or
    a dropped stream would pass ``correct``."""
    weights = xing_engine.seeded_weights(DIMS, seed, dtype=jnp.float32)
    seq = tokens(64, 9)
    x = np.asarray(ref.hidden_states(weights, DIMS, seq))[:64]
    x = jnp.asarray(x.transpose(1, 0, 2))                  # [n, S, d]
    for name in ("dense", "moe"):
        layer = jax.tree.map(lambda a: a[-1], weights[name])
        for sub in ("attn", "ffn"):
            pre, post, res = xing.mhc_maps(x, layer, sub, CFG)
            res = np.asarray(res)                          # [4, 4, S]
            assert np.abs(res.sum(0) - 1).max() < 1e-4
            assert np.abs(res.sum(1) - 1).max() < 1e-4
            off = 1 - np.trace(res) / res.sum((0, 1))
            assert 0.1 < off.min() and off.max() < 0.9
            assert np.asarray(pre).std(axis=1).min() > 0.01
            assert np.asarray(post).std(axis=1).min() > 0.01


# ---------------------------------------------------------------------------
# (f) what cannot serve this state refuses it, by mechanism
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("option,word", [
    (dict(prefix_cache_blocks=8), "prefix cache"),
    (dict(kv_pool_blocks=64, prefill_chunk=16), "block pool"),
    (dict(spec_decode=True), "verify pass"),
    (dict(kv_dtype="float8_e4m3fn"), "8-bit latent"),
    (dict(windows_per_dispatch=2), "one window"),
    (dict(quantize="int4"), "int4"),
    (dict(max_len=MAX_LEN - 8), "multiple of the largest"),
], ids=lambda o: next(iter(o)) if isinstance(o, dict) else None)
def test_options_that_cannot_serve_this_state_refuse_it(params, option,
                                                        word):
    args = dict(num_slots=4, max_len=MAX_LEN, prefill_buckets=BUCKETS,
                eos_id=-1, dtype=jnp.float32)
    args.update(option)
    with pytest.raises(ValueError, match=word):
        GenerationEngine(CFG, params, **args)


def test_a_mesh_refuses_it(params):
    from copilot_for_consensus_tpu.parallel.mesh import local_mesh

    with pytest.raises(ValueError, match="sharding"):
        GenerationEngine(CFG, params, mesh=local_mesh(tp=1), num_slots=4,
                         max_len=MAX_LEN, prefill_buckets=BUCKETS)


# ---------------------------------------------------------------------------
# (g) the routing's counts == a recount from the reference's routing
# ---------------------------------------------------------------------------


def test_the_counts_of_a_dispatch_equal_a_numpy_recount(params):
    seqs = [tokens(30, 11), tokens(9, 12)]
    cache = xing.init_cache(CFG, 3, MAX_LEN, dtype=jnp.float32)
    last, cache, got_p = prefill(params, cache,
                                 [(0, seqs[0]), (1, seqs[1])])
    tok = np.array([int(last[0].argmax()), int(last[1].argmax()), 0])
    pos = np.array([30, 9, MAX_LEN])
    _lg, toks, _cache, (got_d,) = decode(params, cache, tok, pos, 1)
    chosen = []
    for slot, seq in enumerate(seqs):
        full = list(seq) + [int(tok[slot])] + toks[:, slot].tolist()
        routed = []
        ref.hidden_states(params, DIMS, full[:-1], routed=routed)
        chosen.append(np.stack(routed))                  # [layers, S, k]
    n_moe = chosen[0].shape[0]
    # admission: both prompts went in one wave a piece, 30 then 9 real
    # tokens (the second row's piece is padded, and not routed)
    want_rows = sum(len(s) for s in seqs) * K * n_moe
    assert got_p[1] == want_rows
    # the decode dispatch, step by step: the two live slots' tokens
    touched = rows = busiest = 0
    for step in range(STEPS):
        for layer in range(n_moe):
            ids = np.concatenate([chosen[s][layer, len(seqs[s]) + step]
                                  for s in range(2)])
            per = np.bincount(ids, minlength=E)
            touched += (per > 0).sum()
            rows += per.sum()
            busiest += per.max()
    assert got_d[:3].tolist() == [touched, rows, busiest]


def test_the_engine_records_the_counts_with_its_dispatches(engine):
    prompts = [tokens(n, seed=30 + n).tolist() for n in (40, 12)]
    engine.generate(prompts, 9)
    recs = [r for r in engine.telemetry.recorder.records()][-4:]
    n_moe = CFG.n_layers - CFG.first_k_dense_replace
    for r in recs:
        if r.kind == "prefill":
            assert r.expert_rows == r.tokens * K * n_moe
            assert r.attn_pairs > 0
            # whole rounds of expanded keys and values, for every layer
            # and (padded) row of the wave
            rounds, rest = divmod(r.expand_bytes_moved, xing.expand_bytes_moved(
                [1] * r.batch, MAX_LEN, CFG, 4))
            assert 1 <= rounds <= MAX_LEN // xing.KV_BLOCK and rest == 0
        else:
            assert r.expand_bytes_moved == 0
            assert r.expert_rows == r.rows * STEPS * K * n_moe
            assert r.state_tokens_read == STEPS * 4 * MAX_LEN
        assert 0 < r.experts_touched <= r.expert_rows
        # every expert is held here, and a visit multiplies a whole tile
        assert r.expert_group_rows == r.expert_rows <= r.expert_tile_rows
        assert r.expert_rows_max * E >= r.expert_rows / (
            (STEPS if r.kind == "decode" else 1))


# ---------------------------------------------------------------------------
# (h) the kernel's route through the engine (a TPU's; here through the
# Pallas interpreter): the same tokens, and the step records count the
# blocks read
# ---------------------------------------------------------------------------


def test_the_kernel_route_serves_the_references_tokens_and_counts_its_blocks(
        params, monkeypatch):
    """A cache of four blocks a slot, three sequences that cross
    dispatch edges and one a block's edge while decoding: every served
    token is the reference's best, and each decode record's
    `state_tokens_read` is the columns under the blocks of the decoding
    slots' lengths, times the dispatch's steps."""
    from copilot_for_consensus_tpu.ops import latent_attention

    blk, max_len = 128, 512
    monkeypatch.setattr(latent_attention, "BLOCK", blk)
    monkeypatch.setattr(latent_attention, "serves", lambda extent: True)
    eng = GenerationEngine(
        CFG, params, num_slots=4, max_len=max_len, prefill_buckets=BUCKETS,
        admission_token_budget=64, eos_id=-1, quantize="int8",
        dtype=jnp.float32)
    assert eng._reads_latent_blocks()
    began = []
    served = eng._decode_mla_fn

    def watched(params, toks, positions, cache, key):
        began.append(np.array(positions))      # a copy: the engine moves on
        return served(params, toks, positions, cache, key)

    eng._decode_mla_fn = watched
    prompts = [tokens(n, seed=50 + n).tolist() for n in (5, 250, 130)]
    done = eng.generate(prompts, 20)
    for prompt, c in zip(prompts, done):
        seq = prompt + list(c.tokens)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        want = ref.logits_at(params, DIMS, seq, at)
        gap = want.max(-1) - want[np.arange(len(at)), c.tokens]
        assert gap.max() < TOL
    recs = [r for r in eng.telemetry.recorder.records()
            if r.kind == "decode"]
    assert len(recs) == len(began) >= 3
    for rec, pos in zip(recs, began):
        live = pos[pos < max_len]
        assert rec.rows == len(live)
        assert rec.window_tokens == live.sum()
        assert rec.state_tokens_read == STEPS * (
            -(-live // blk) * blk).sum()
    # a sequence crossed a block's edge between two dispatches
    assert any(250 <= p <= 256 for pos in began for p in pos) \
        and any(256 < p < max_len for pos in began for p in pos)
    # and off the kernel's route the whole extent is counted
    monkeypatch.setattr(latent_attention, "serves", lambda extent: False)
    assert not eng._reads_latent_blocks()
    assert eng._latent_read(STEPS) == STEPS * 4 * max_len


def test_an_admission_of_several_pieces_through_the_kernel_is_the_xla_routes(
        params, monkeypatch):
    """Two prompts admitted together in pieces of 32 over rounds of 16
    (three waves; the shorter row ends in the second, in mid-piece),
    on the admission kernel's route (ops/latent_prefill_attention.py)
    and on the XLA rounds': each row's last logits, the latent rows
    written, the routing's counts, and the reference's logits."""
    from copilot_for_consensus_tpu.ops import latent_prefill_attention

    rows = [(2, tokens(90, seed=4)), (0, tokens(37, seed=5))]

    def admitted(kernel):
        monkeypatch.setattr(latent_prefill_attention, "serves",
                            lambda block: kernel)
        # a jit of its own: the route is read when the program is traced
        fn = jax.jit(lambda *a: xing.prefill_piece(*a[:5], CFG, a[5]))
        text = str(jax.make_jaxpr(fn)(
            params, jnp.zeros((2, 32), jnp.int32), jnp.ones((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.arange(2),
            xing.init_cache(CFG, 4, MAX_LEN, jnp.float32)))
        assert ("mla_prefill_attention" in text) == kernel
        return prefill(params, xing.init_cache(CFG, 4, MAX_LEN, jnp.float32),
                       rows, piece_fn=fn)

    (want, cache_x, counts_x), (got, cache_k, counts_k) = (
        admitted(False), admitted(True))
    assert counts_x.tolist() == counts_k.tolist()
    for (slot, seq), w, g in zip(rows, want, got):
        assert np.abs(g - w).max() < TOL
        assert np.abs(g - ref.logits_at(params, DIMS, seq.tolist(),
                                        [len(seq) - 1])[0]).max() < TOL
        for name in cache_x:
            assert np.abs(np.asarray(cache_k[name][:, slot, :, :len(seq)])
                          - np.asarray(cache_x[name][:, slot, :, :len(seq)])
                          ).max() < TOL
