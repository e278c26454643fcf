# attention="mixed" (models/mixed.py, the cohere2_moe class: window and
# global layers in one model, a ring beside a full extent, a parallel
# attention + experts block on one LayerNorm, a held share of the
# experts, a tied head) through both caches and through
# GenerationEngine, against the plain reference
# benchmark/reference/cohere2_moe.py (float32, the window as one boolean
# matrix, no cache and no ring, experts by plain indexing). Tiny sizes:
# d 64, 8 query heads on 2 key/value heads of 16, two periods of three
# window layers (16 positions, rotary) and a global one (no positions),
# 8 experts of which 2 a token beside 2 averaged shared ones. The
# largest admission piece is 16, so a ring is 32 columns and a prompt
# of 100 turns it over three times.
#
# Tolerance, with its reason:
#   TOL = 1e-4 on logits of size ~3: weights are int8 with float32
#   scales, activations and both caches float32 here, so program and
#   reference differ only by the order of float32 sums; the largest
#   difference seen is 4e-6. A column of the ring read one position off
#   the window's edge, or left over from a slot's last request, moves
#   logits by 1e-2 and more.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import cohere2_moe as ref
from benchmark.reference import glm_dsa as ref_common
from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.models import mixed, xing
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.ops import dense_attention

TOL = 1e-4
CFG = decoder_config("tiny-mixed")
V, K, E = CFG.vocab_size, CFG.experts_per_token, CFG.n_routed_experts
W = CFG.sliding_window
MAX_LEN, STEPS, PIECE, BUCKETS = 128, 8, 16, (8, 16)
RING = mixed.ring_len(CFG, PIECE)


def dims_of(cfg, held=None):
    first, count = held or xing.held_experts(cfg)
    return dict(
        model_type="cohere2_moe", hidden_size=cfg.d_model,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab_size,
        intermediate_size=cfg.moe_intermediate_size,
        layer_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        sliding_window=cfg.sliding_window,
        layer_types=["full_attention" if k == "full"
                     else "sliding_attention"
                     for k in mixed.layer_kinds(cfg)],
        num_experts=count,
        held={"first_expert": first,
              "router_experts": cfg.n_routed_experts},
        num_shared_experts=cfg.n_shared_experts, num_experts_per_tok=K,
        shared_expert_combination_strategy=cfg.shared_expert_combine,
        logit_scale=cfg.logit_scale)


DIMS = dims_of(CFG)


@pytest.fixture(scope="module")
def params():
    return mixed.init_params(jax.random.PRNGKey(1), CFG, dtype=jnp.float32,
                             quantize=True)


@pytest.fixture(autouse=True)
def small_pad(monkeypatch):
    monkeypatch.setattr(ref_common, "PAD_TO", 8)


def greedy(logits, _key):
    return jnp.argmax(logits, -1).astype(jnp.int32)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, V, size=n)


def piece_fn(cfg, impl="auto"):
    return jax.jit(lambda params, toks, lens, pos0, slots, cache:
                   mixed.prefill_piece(params, toks, lens, pos0, slots, cfg,
                                       cache, attn_impl=impl))


def decode_fn(cfg, live_blocks=False):
    return jax.jit(lambda params, tok, pos, cache: mixed.decode_tokens(
        params, tok, pos, cfg, cache, jax.random.PRNGKey(0), greedy,
        steps=STEPS, max_len=MAX_LEN, with_logits=True,
        live_blocks=live_blocks))


def prefill(fn, params, cache, slot, seq):
    """Admit ``seq`` into ``slot`` a piece a wave (what the engine's
    admission does). → the logits after its last token, the cache."""
    at = 0
    while at < len(seq):
        n = min(len(seq) - at, PIECE)
        toks = np.zeros((1, PIECE), np.int32)
        toks[0, :n] = seq[at:at + n]
        logits, cache, _ = fn(params, jnp.asarray(toks), jnp.asarray([n]),
                              jnp.asarray([at]), jnp.asarray([slot]), cache)
        at += n
    return np.asarray(logits[0]), cache


def through_both_caches(cfg, params, seq, new, cache=None,
                        live_blocks=False):
    """``seq`` admitted in pieces into slot 1 of 2, then ``new`` tokens
    decoded greedily (slot 0 stands idle at MAX_LEN): the logits at the
    prompt's last position and at every decoded one, the tokens, the
    cache."""
    if cache is None:
        cache = mixed.init_cache(cfg, 2, MAX_LEN, PIECE, jnp.float32)
    first, cache = prefill(piece_fn(cfg), params, cache, 1, seq)
    step = decode_fn(cfg, live_blocks)
    toks, logits, pos = [int(first.argmax())], [first], len(seq)
    while len(toks) < new:
        t, cache, _, lg = step(params, jnp.asarray([0, toks[-1]]),
                               jnp.asarray([MAX_LEN, pos]), cache)
        toks += [int(v) for v in np.asarray(t)[:, 1]]
        logits += list(np.asarray(lg)[:, 1])
        pos += STEPS
    return np.stack(logits[:new]), toks[:new], cache


def reference_logits(params, dims, seq, toks):
    full = np.concatenate([seq, toks[:-1]])
    at = np.arange(len(seq) - 1, len(seq) - 1 + len(toks))
    return ref.logits_at(params, dims, full, at)


# ---------------------------------------------------------------------------
# (a) prefill, then decode through both caches == the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [5, 16, 21, 100], ids=[
    "under-the-window", "at-the-window", "a-piece-across-its-edge",
    "ring-turned-three-times"])
def test_prefill_then_decode_matches_the_reference(params, n):
    """Prompts under, at and several times the window (16), one whose
    second piece straddles the window's edge, one that turns the ring
    of 32 columns three times; then 17 decoded tokens, which cross the
    window's edge (n = 5, 16) and wrap the ring again (n = 100: columns
    100 % 32 = 4 on): the logits after the prompt and through three
    decode dispatches equal the reference's full forward pass."""
    seq = tokens(n, seed=n)
    got, toks, _ = through_both_caches(CFG, params, seq, 1 + 2 * STEPS)
    want = reference_logits(params, DIMS, seq, toks)
    assert np.abs(got - want).max() < TOL


def test_the_window_is_what_decides_the_logits(params):
    """The same sequence through a reference whose window is twice as
    wide reads other logits: TOL holds the window's edge."""
    seq = tokens(60, seed=3)
    got, toks, _ = through_both_caches(CFG, params, seq, 4)
    wide = ref.logits_at(params, dict(DIMS, sliding_window=2 * W),
                         np.concatenate([seq, toks[:-1]]),
                         np.arange(59, 59 + len(toks)))
    assert np.abs(got - wide).max() > 100 * TOL


def test_the_window_layers_keep_a_ring_and_the_full_layers_an_extent():
    cache = mixed.init_cache(CFG, 2, MAX_LEN, PIECE, jnp.float32)
    shapes = {k: v.shape for k, v in cache.items()}
    assert RING == W + PIECE == 32
    assert shapes == {
        "window_k": (6, 2, CFG.n_kv_heads, RING, CFG.head_dim),
        "window_v": (6, 2, CFG.n_kv_heads, RING, CFG.head_dim),
        "full_k": (2, 2, CFG.n_kv_heads, MAX_LEN, CFG.head_dim),
        "full_v": (2, 2, CFG.n_kv_heads, MAX_LEN, CFG.head_dim)}
    assert mixed.layer_kinds(CFG) == ("window", "window", "window",
                                      "full") * 2


def test_a_reused_slot_shows_nothing_of_its_last_request(params):
    """A long request fills the slot's ring and extent; the next one in
    the same slot, shorter than the ring, reads the reference's logits
    as if the slot had been empty, though the old columns still lie
    there."""
    _, _, cache = through_both_caches(CFG, params, tokens(100, seed=8), 9)
    old = np.asarray(cache["window_k"][:, 1])
    assert np.abs(old).min(axis=(0, 1, 3)).all()     # every column used
    seq = tokens(11, seed=9)
    got, toks, cache = through_both_caches(CFG, params, seq, 1 + STEPS,
                                           cache=cache)
    want = reference_logits(params, DIMS, seq, toks)
    assert np.abs(got - want).max() < TOL
    # columns the new request has not reached still hold the old one's
    assert (np.asarray(cache["window_k"][:, 1, :, 24:]) == old[:, :, 24:]
            ).all()


def test_the_ring_holds_position_p_in_column_p_mod_r(params):
    """After 100 positions the ring's column c holds the key of
    position c + 32 * ((99 - c) // 32): what a full-layer-shaped cache
    holds there (the first layer is a window layer: compare with the
    same keys computed for a cache four times as long)."""
    seq = tokens(100, seed=4)
    _, cache = prefill(piece_fn(CFG), params,
                       mixed.init_cache(CFG, 2, MAX_LEN, PIECE,
                                        jnp.float32), 1, seq)
    wide = decoder_config("tiny-mixed", sliding_window=112)
    _, long = prefill(piece_fn(wide), params,
                      mixed.init_cache(wide, 2, MAX_LEN, PIECE,
                                       jnp.float32), 1, seq)
    assert long["window_k"].shape[3] == 128
    c = np.arange(RING)
    held = c + RING * ((99 - c) // RING)
    # the last piece (4 tokens, padded to 16) laid its padding over
    # positions 68-79, more than a window behind every later query
    c, held = c[held >= 80], held[held >= 80]
    assert len(c) == 20 > W
    # layer 0's keys depend on nothing it read: the same in both
    assert np.abs(np.asarray(cache["window_k"][0, 1][:, c])
                  - np.asarray(long["window_k"][0, 1][:, held])).max() < 1e-6


# ---------------------------------------------------------------------------
# (b) the two routes of attention (a TPU's, through the interpreter)
# ---------------------------------------------------------------------------


def test_the_kernel_route_of_decode_reads_both_caches_in_place(
        params, monkeypatch):
    """``dense_attention.live_partial`` over a full layer's one range
    and a ring's two (blocks of 16 columns here): the logits through
    three dispatches that wrap the ring are the XLA route's."""
    monkeypatch.setattr(dense_attention, "BLOCK", 16)
    seq = tokens(90, seed=6)
    xla, toks, _ = through_both_caches(CFG, params, seq, 1 + 3 * STEPS)
    ker, toks_k, _ = through_both_caches(CFG, params, seq, 1 + 3 * STEPS,
                                         live_blocks=True)
    assert toks == toks_k
    assert np.abs(xla - ker).max() < TOL


@pytest.mark.parametrize("lo,hi,want", [
    (0, 0, ((0, 0), (0, 0))),            # nothing cached
    (0, 20, ((0, 20), (0, 0))),          # a young sequence
    (40, 56, ((8, 24), (0, 0))),         # one run
    (85, 100, ((21, 32), (0, 4))),       # wrapped
    (64, 96, ((0, 32), (0, 0))),         # the whole ring, unwrapped
])
def test_a_rings_live_positions_are_two_runs_of_columns(lo, hi, want):
    got = mixed.ring_ranges(np.asarray(lo), np.asarray(hi), RING)
    assert tuple((int(a), int(b)) for a, b in got) == want


def test_admission_through_the_flash_kernel_is_the_xla_routes(params):
    """A prompt of several pieces through ``ops/flash_attention.py``
    (interpreted): the ring turned into timeline order, the begin bound
    of a young sequence, the window as a distance."""
    seq = tokens(70, seed=12)

    def cache():
        return mixed.init_cache(CFG, 2, MAX_LEN, PIECE, jnp.float32)

    xla, c_x = prefill(piece_fn(CFG, "xla"), params, cache(), 1, seq)
    ker, c_k = prefill(piece_fn(CFG, "pallas"), params, cache(), 1, seq)
    assert np.abs(xla - ker).max() < TOL
    for name in c_x:
        assert np.abs(np.asarray(c_x[name]) - np.asarray(c_k[name])
                      ).max() < TOL


# ---------------------------------------------------------------------------
# (c) a held share of the experts
# ---------------------------------------------------------------------------


def test_held_shares_of_the_experts_add_up_to_the_uncut_reference(params):
    """8 experts in shares of 2: each share's program-side experts part
    (its own experts' terms plus the shared experts' mean) less the
    shared experts, summed over the four shares, plus the shared
    experts ONCE, is the uncut layer; and the reference's layer, given
    each share, adds up the same way to its uncut pass."""
    layers, experts = mixed._split(params["layers"])
    layer = jax.tree.map(lambda a: a[1], layers)
    hid = jax.random.normal(jax.random.PRNGKey(3), (1, 24, CFG.d_model),
                            jnp.float32)
    live, li = jnp.ones((1, 24), bool), jnp.int32(1)
    whole, _ = mixed.experts_part(hid, layer, experts, li, CFG, live,
                                  jnp.float32)
    none = decoder_config("tiny-mixed", held_experts=(0, 1))
    zero = {k: jax.tree.map(lambda a: jnp.zeros_like(a[:, :1]), v)
            for k, v in experts.items()}
    shared, _ = mixed.experts_part(hid, layer, zero, li, none, live,
                                   jnp.float32)
    total = shared
    for first in range(0, E, 2):
        share = {k: jax.tree.map(lambda a: a[:, first:first + 2], v)
                 for k, v in experts.items()}
        part, counts = mixed.experts_part(
            hid, layer, share, li,
            decoder_config("tiny-mixed", held_experts=(first, 2)), live,
            jnp.float32)
        assert int(counts[0]) <= 2               # counted over its share
        total = total + (part - shared)
    assert np.abs(np.asarray(total - whole)).max() < TOL

    # the reference: the shares' routed terms, what every chip computes
    # alike counted once, against the uncut pass, layer by layer
    seq = tokens(24, seed=5)
    uncut: list = []
    ref.hidden_states(params, DIMS, seq, parts=uncut)
    for li in (0, 3):
        alike, routed = uncut[li]
        summed = np.zeros_like(routed)
        for first in range(0, E, 2):
            share = dict(params, layers=dict(params["layers"], **{
                k: jax.tree.map(lambda a: a[:, first:first + 2],
                                params["layers"][k])
                for k in xing.EXPERTS}))
            got: list = []
            ref.hidden_states(share, dims_of(CFG, (first, 2)), seq,
                              parts=got)
            if li == 0:          # later layers' inputs differ by share
                assert np.abs(got[0][0] - alike).max() < TOL
                summed += got[0][1]
        if li == 0:
            assert np.abs(summed - routed).max() < TOL


def test_a_held_share_through_both_caches_matches_the_reference():
    """The served path with a share: a config that holds experts 2-5 of
    8 serves, through admission and decode, the reference's logits for
    the same share (the router 8 wide, the absent experts' terms left
    out alike)."""
    cfg = decoder_config("tiny-mixed", held_experts=(2, 4))
    p = mixed.init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32,
                          quantize=True)
    assert p["layers"]["we_up"]["q"].shape[1] == 4
    assert p["layers"]["router"].shape[-1] == 8
    seq = tokens(50, seed=13)
    got, toks, _ = through_both_caches(cfg, p, seq, 1 + STEPS)
    want = reference_logits(p, dims_of(cfg), seq, toks)
    assert np.abs(got - want).max() < TOL


def test_the_lower_precision_controls_read_other_logits(params):
    seq = tokens(60, seed=21)
    at = np.arange(30, 60)
    sound = ref.logits_at(params, DIMS, seq, at)
    for lower in ref.LOWERS:
        low = ref.logits_at(params, DIMS, seq, at, lower=lower)
        assert np.abs(low - sound).max() > 100 * TOL


# ---------------------------------------------------------------------------
# (d) through GenerationEngine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(params):
    return GenerationEngine(
        CFG, params, num_slots=4, max_len=MAX_LEN, prefill_buckets=BUCKETS,
        admission_token_budget=32, eos_id=-1, quantize="int8",
        dtype=jnp.float32)


def test_the_engine_serves_the_references_best_tokens(engine, params):
    """submit/step through admission in pieces (two rows a wave), decode
    dispatches, retirement and the reuse of slots (six requests on four
    slots): every served token is the reference's best after the tokens
    before it (a gap under TOL)."""
    prompts = [tokens(n, seed=10 + n).tolist()
               for n in (5, 100, 41, 17, 64, 9)]
    done = engine.generate(prompts, 20)
    for prompt, c in zip(prompts, done):
        seq = prompt + list(c.tokens)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        want = ref.logits_at(params, DIMS, seq, at)
        gap = want.max(-1) - want[np.arange(len(at)), c.tokens]
        assert gap.max() < TOL
    kinds = {r.kind for r in engine.telemetry.recorder.records()}
    assert kinds == {"prefill", "decode"}
    assert set(engine._cache) == {"window_k", "window_v", "full_k",
                                  "full_v"}
    assert engine._cache["window_k"].shape[3] == RING


def test_the_engine_records_what_each_kind_of_layer_read(engine):
    prompts = [tokens(n, seed=30 + n).tolist() for n in (40, 12)]
    engine.generate(prompts, 9)
    recs = list(engine.telemetry.recorder.records())[-5:]
    assert {r.kind for r in recs} == {"prefill", "decode"}
    for r in recs:
        if r.kind == "prefill":
            # a window layer's queries read at most a window each
            assert 0 < r.window_attn_pairs <= r.attn_pairs
            if r.tokens == 28:       # the first wave: 16 of 40, and 12
                assert r.attn_pairs == 16 * 17 // 2 + 12 * 13 // 2
                assert r.window_attn_pairs == r.attn_pairs
            assert r.window_tokens_read == r.state_tokens_read == 0
            # a piece here is one tile against an extent that is one
            # (the kernel's tiles are wider than either): a (padded
            # row, layer, head) each, seen in part or, where a row's
            # piece lies wholly ahead of the ring's first column, whole
            tiles = (r.attn_tiles_whole, r.attn_tiles_edge,
                     r.attn_tiles_dead)
            assert sum(tiles) == r.batch * CFG.n_layers * CFG.n_heads
            assert r.attn_tiles_edge > 0 == r.attn_tiles_dead
            continue
        assert r.attn_tiles_whole == r.attn_tiles_edge == 0
        # off a TPU every column of either cache is scored
        assert r.state_tokens_read == STEPS * 4 * MAX_LEN
        assert r.window_tokens_read == STEPS * 4 * RING
    assert recs[-1].expert_rows > 0
    assert all(0 < r.expert_group_rows <= r.expert_tile_rows for r in recs)


def test_the_kernel_route_serves_the_references_tokens(params, monkeypatch):
    """The TPU's decode route (here through the Pallas interpreter) in
    the engine: live blocks of both caches in place, and what the
    records count for a window layer stays under a ring however long
    the sequence, and under what a full layer reads."""
    monkeypatch.setattr(dense_attention, "BLOCK", 16)
    monkeypatch.setattr(dense_attention, "MIN_BLOCK", 16)
    monkeypatch.setattr(dense_attention, "serves", lambda extent: True)
    eng = GenerationEngine(
        CFG, params, num_slots=4, max_len=MAX_LEN, prefill_buckets=BUCKETS,
        admission_token_budget=32, eos_id=-1, quantize="int8",
        dtype=jnp.float32)
    assert eng._reads_ring_blocks()
    prompts = [tokens(n, seed=50 + n).tolist() for n in (5, 100, 70)]
    done = eng.generate(prompts, 12)
    for prompt, c in zip(prompts, done):
        seq = prompt + list(c.tokens)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        want = ref.logits_at(params, DIMS, seq, at)
        gap = want.max(-1) - want[np.arange(len(at)), c.tokens]
        assert gap.max() < TOL
    dec = [r for r in eng.telemetry.recorder.records()
           if r.kind == "decode"]
    assert all(0 < r.window_tokens_read <= r.rows * STEPS * RING
               and r.window_tokens_read % 16 == 0 for r in dec)
    # once a sequence is longer than the ring a window layer reads less
    # than a full one
    assert any(r.window_tokens_read < r.state_tokens_read for r in dec)
    assert all(r.window_tokens_read <= r.state_tokens_read + r.rows
               * STEPS * 16 for r in dec)


@pytest.mark.parametrize("option,word", [
    ({"prefix_cache_blocks": 8}, "prefix_cache_blocks"),
    ({"kv_pool_blocks": 8}, "kv_pool_blocks"),
    ({"spec_decode": True}, "spec_decode"),
    ({"kv_dtype": "fp8"}, "kv_dtype"),
    ({"quantize": "int4"}, "int4"),
    ({"windows_per_dispatch": 2}, "windows_per_dispatch"),
])
def test_options_that_assume_one_cache_layout_refuse_it(params, option,
                                                        word):
    kw = dict(num_slots=2, max_len=MAX_LEN, prefill_buckets=BUCKETS,
              quantize="int8", dtype=jnp.float32)
    kw.update(option)
    with pytest.raises(ValueError, match=word):
        GenerationEngine(CFG, params, **kw)


def test_a_mesh_refuses_it(params):
    from copilot_for_consensus_tpu.parallel.mesh import local_mesh

    with pytest.raises(ValueError, match="mesh"):
        GenerationEngine(CFG, params, num_slots=2, max_len=MAX_LEN,
                         prefill_buckets=BUCKETS, dtype=jnp.float32,
                         mesh=local_mesh(tp=1))


@pytest.mark.parametrize("bad", [
    {"held_experts": (6, 4)}, {"sliding_window": 24},
    {"sliding_window": 0}, {"sliding_window": 256}, {"n_layers": 6},
    {"parallel_block": False}, {"global_member": 4},
    {"tie_embeddings": False}])
def test_a_config_the_two_caches_cannot_hold_is_refused(bad):
    cfg = decoder_config("tiny-mixed", **bad)
    with pytest.raises(ValueError, match="held_experts|attention='mixed'"):
        GenerationEngine(cfg, None, num_slots=2, max_len=MAX_LEN,
                         prefill_buckets=BUCKETS, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# (e) the benchmark's cell of this architecture resolves by name
# ---------------------------------------------------------------------------


def test_the_cells_files_resolve_through_the_harness():
    """``benchmark/harness/spec.py`` finds the new cell's builder,
    reference, traffic, generator and every metric's reader by the
    names the data files give; the builder's program config keeps the
    published widths and reads the pattern off ``layer_types``."""
    from benchmark.builders import cmda_engine
    from benchmark.builders._decoder import dims_of
    from benchmark.harness import spec

    cell = spec.load_cell(
        "command-a-plus-218b-a25b-int8.summarize-mixed-24k")
    data = cell["config_data"]
    assert cell["chips"] == 1
    assert spec.module("builders", data["builder"]) is cmda_engine
    assert spec.module("reference", data["reference"]) is ref
    plan = spec.module("generators", cell["traffic_data"]["generator"]
                       ).plan(cell["traffic_data"], 1, 51.0)
    lens = [it["prompt_len"] for it in plan["items"][:32]]
    assert lens.count(24576) == 9 and sum(n < 4096 for n in lens) == 11
    for kind in ("end_to_end", "per_layer"):
        for m in spec.metric_files(cell["name"], kind):
            assert hasattr(spec.module("readers", m["reader"]), "read")
    assert data["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    for rehearse, want in ((False, (4096, 128, 8, 128, 4096, 6144)),
                           (True, (256, 8, 2, 32, 32, 64))):
        system = cmda_engine.System.__new__(cmda_engine.System)
        system.dims = dims_of(data, rehearse)
        assert cmda_engine.pattern(system.dims) == (4, 3)
        cfg = system.program_config("cell")
        engine = dict(data["engine"], **(data["rehearsal"]["engine"]
                                         if rehearse else {}))
        assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                cfg.sliding_window,
                mixed.ring_len(cfg, max(engine["prefill_buckets"]))
                ) == want
        assert mixed.layer_kinds(cfg) == ("window",) * 3 + ("full",) \
            + ("window",) * 3 + ("full",)
        assert cfg.held_experts == (0, system.dims["num_experts"])
        assert cfg.experts_per_token == (2 if rehearse else 8)
