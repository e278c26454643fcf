# The kernel that folds a round of an admission piece's expanded latent
# attention without its scores leaving the chip
# (ops/latent_prefill_attention.py), through the Pallas interpreter,
# against `models/xing.py:piece_attention`'s XLA rounds: the same walk
# of the live rounds, the same keys and values (expanded heads first on
# the kernel's route), the same arithmetic in another order of sums.
#
# Sizes: the two tiny configurations, whose keys and values stand in
# the served ones' ratios (tiny-xing 24 / 16 as 192 / 128, tiny-glm
# 24 / 24 as 256 / 256), four heads, rounds of 128 columns in a cache
# of six (so that a test's few hundred positions span several rounds)
# and query tiles of 64 (so that a piece of 128 or 256 is several).
# Tolerances, each with its reason:
#   F32 = 2e-6 on outputs of size ~1: both routes are float32 here and
#   differ by the order of float32 sums (a tile's partial sums against a
#   round's); the largest difference seen is 4e-7.
#   BF16 = 8e-3: with bfloat16 operands both routes round the
#   probabilities to bfloat16 before the value dot and the output to
#   bfloat16 after the division (8 bits: 4e-3 at the outputs' size,
#   ~1); the difference seen is one such step.
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from copilot_for_consensus_tpu.models import xing
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.ops import latent_prefill_attention as lpa

# The kernel's route has a round's keys and values heads first one of
# two ways by the widths (`xing._rotary_in_weight`): written so by
# `xing.expand_heads`, the shared rotary key taken through the keys'
# weight, or `xing.expand`'s turned over. The `expansion` fixture runs
# a test both ways at the tiny widths (16 + 8), where a lane tile of
# 128 takes the first as the served 192 + 64 do, and a tile of 16 the
# second as the served 128 + 64 do.
BLK, EXTENT, SLOTS, N_L = 128, 6 * 128, 5, 2
F32, BF16 = 2e-6, 8e-3
CFGS = {"xing": decoder_config("tiny-xing"),
        "glm": decoder_config("tiny-glm")}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(xing, "KV_BLOCK", BLK)
    monkeypatch.setattr(lpa, "TQ", 64)


@pytest.fixture(params=["heads_first", "turned_over"])
def expansion(request, monkeypatch):
    if request.param == "turned_over":
        monkeypatch.setattr(xing, "LANE_TILE", 16)
    assert all(xing._rotary_in_weight(c) == (request.param == "heads_first")
               for c in CFGS.values())
    return request.param


def test_the_sizes_here_stand_in_the_served_ratios():
    widths = {name: (c.qk_nope_head_dim + c.qk_rope_head_dim, c.v_head_dim)
              for name, c in CFGS.items()}
    assert widths == {"xing": (24, 16), "glm": (24, 24)}
    assert 192 * 16 == 128 * 24 and EXTENT // BLK == 6


def state(cfg, seed, n, s, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    h, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim

    def rand(*shape, scale=1.0):
        return (jnp.asarray(rng.normal(size=shape), jnp.float32)
                * scale).astype(dtype)

    cache_a = rand(N_L, SLOTS, xing.latent_width(cfg), EXTENT)
    layer = {"wkv_b": rand(cfg.kv_lora_rank, h * (dn + dv),
                           scale=cfg.kv_lora_rank ** -0.5)}
    q = rand(n, s, h, dn + cfg.qk_rope_head_dim, scale=0.3)
    return cache_a, layer, q


def both_routes(cfg, cache_a, layer, q, pos0, lens, keep=None, li=1,
                slots=None, poison=True):
    """`piece_attention` on its two routes, each under a jit that takes
    the number of live rounds as an argument: (the XLA rounds', the
    kernel's), float32 ``[n, S, H dv]``. Columns past the live rounds
    hold NaN when the kernel reads them."""
    n, s = q.shape[:2]
    pos0, lens = jnp.asarray(pos0, jnp.int32), jnp.asarray(lens, jnp.int32)
    slots = jnp.arange(n, dtype=jnp.int32) if slots is None \
        else jnp.asarray(slots, jnp.int32)
    q_pos = pos0[:, None] + jnp.arange(s)[None, :]
    kv_len = pos0 + lens
    n_blocks = (jnp.max(kv_len) + BLK - 1) // BLK

    def keep_of(j):
        return jax.lax.dynamic_slice(jnp.asarray(keep), (0, 0, j * BLK),
                                     (n, s, BLK))

    def route(kernel):
        def run(q, cache_a, n_blocks):
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(lpa, "serves", lambda block: kernel)
                return xing.piece_attention(
                    q, cache_a, jnp.int32(li), slots, q_pos, kv_len,
                    n_blocks, layer, cfg,
                    None if keep is None else keep_of)

        return jax.jit(run)

    dead = jnp.arange(EXTENT) >= n_blocks * BLK
    read = jnp.where(dead, jnp.asarray(jnp.nan, cache_a.dtype), cache_a) \
        if poison else cache_a
    want = route(False)(q, cache_a, n_blocks)
    got = route(True)(q, read, n_blocks)
    assert got.dtype == q.dtype and got.shape == want.shape
    return np.asarray(want.astype(jnp.float32)), \
        np.asarray(got.astype(jnp.float32))


def test_the_kernel_route_is_the_kernel():
    cfg = CFGS["xing"]
    cache_a, layer, q = state(cfg, 0, 1, 64)

    def run(kernel):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(lpa, "serves", lambda block: kernel)
            return str(jax.make_jaxpr(lambda q: xing.piece_attention(
                q, cache_a, jnp.int32(0), jnp.zeros((1,), jnp.int32),
                jnp.arange(64)[None], jnp.asarray([64]), jnp.int32(1),
                layer, cfg))(q))

    assert "mla_prefill_attention" in run(True)
    assert "pallas_call" not in run(False)


# (rows, bucket) as the engine's waves have them, scaled: one long
# piece, two, four and eight shorter ones
@pytest.mark.parametrize("cfg", CFGS.values(), ids=CFGS.keys())
@pytest.mark.parametrize("n,s", [(1, 256), (2, 128), (4, 64), (8, 32),
                                 (2, 256)],
                         ids=lambda v: str(v))
def test_kernel_route_equals_the_xla_rounds(cfg, n, s):
    """Every row a later piece of its prompt: earlier rounds seen
    whole, the piece's own through the causal edge."""
    cache_a, layer, q = state(cfg, n * s, n, s)
    pos0 = [(2 * BLK // s) * s + (r % 2) * s for r in range(n)]
    want, got = both_routes(cfg, cache_a, layer, q, pos0, [s] * n,
                            slots=[r % SLOTS for r in range(n)])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "kept"])
@pytest.mark.parametrize("cfg", CFGS.values(), ids=CFGS.keys())
def test_rows_at_different_places_in_one_call(cfg, masked, expansion):
    """A first piece, a piece deep in its prompt, a short last piece:
    each row's rounds past its own length are not its business (they
    are expanded with the wave's and folded by nobody), and a query
    tile that lies wholly before a round is skipped; with and without
    a selection's mask."""
    cache_a, layer, q = state(cfg, 1, 4, 128)
    pos0, lens = [0, 4 * BLK, BLK, 2 * BLK + 128], [128, 128, 37, 1]
    keep = None
    if masked:
        keep = seen_of(pos0, lens, 128) & (
            np.random.default_rng(3).random((4, 128, EXTENT)) < 0.3)
    want, got = both_routes(cfg, cache_a, layer, q, pos0, lens, keep,
                            slots=[3, 0, 4, 1])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "kept"])
@pytest.mark.parametrize("cfg", CFGS.values(), ids=CFGS.keys())
def test_a_piece_shorter_than_a_query_tile(cfg, masked, expansion):
    """Pieces of 32 queries under tiles of 64: one tile a row, the
    rounds' operands as wide as ever."""
    cache_a, layer, q = state(cfg, 29, 2, 32, jnp.bfloat16)
    pos0, lens = [BLK + 96, 3 * BLK], [32, 20]
    keep = None
    if masked:
        keep = seen_of(pos0, lens, 32) & (
            np.random.default_rng(5).random((2, 32, EXTENT)) < 0.4)
        keep[:, :, 0] = True
    want, got = both_routes(cfg, cache_a, layer, q, pos0, lens, keep)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < BF16


@pytest.mark.parametrize("pos0", [0, BLK - 64, BLK, 3 * BLK + 64],
                         ids=["first", "straddles", "edge", "deep"])
def test_the_causal_edge_inside_a_pieces_own_rounds(pos0):
    """A query sees its own column and not the next: moving what lies
    past each query's position changes nothing."""
    cfg = CFGS["glm"]
    cache_a, layer, q = state(cfg, pos0 + 3, 1, 256)
    want, got = both_routes(cfg, cache_a, layer, q, [pos0], [256])
    assert np.abs(got - want).max() < F32
    later = cache_a.at[:, :, :, pos0 + 200:].add(1.0)
    _, again = both_routes(cfg, later, layer, q, [pos0], [256])
    assert np.array_equal(again[0, :200], got[0, :200])
    assert np.abs(again[0, 200:] - got[0, 200:]).max() > 1e-3


def test_padded_query_rows_come_out_finite():
    """Queries past a row's real tokens see the row's columns and
    nothing of their own; a row of no token at position 0 sees nothing
    at all and comes out 0."""
    cfg = CFGS["xing"]
    cache_a, layer, q = state(cfg, 5, 2, 128)
    want, got = both_routes(cfg, cache_a, layer, q, [BLK, 0], [5, 0])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32
    assert np.all(got[1] == 0)
    # and the padded queries of row 0 all read the same five columns
    assert np.abs(got[0, 5:] - want[0, 5:]).max() < F32


def test_one_compiled_function_serves_two_lengths():
    """The number of live rounds is traced: the same jitted function
    serves a piece two rounds in and one five rounds in, and columns
    past the live rounds (NaN here) change nothing."""
    cfg = CFGS["xing"]
    cache_a, layer, q = state(cfg, 7, 2, 128)
    slots = jnp.asarray([1, 3], jnp.int32)
    traces = []

    def run(q, cache_a, pos0):
        traces.append(1)
        q_pos = pos0[:, None] + jnp.arange(128)[None, :]
        kv_len = pos0 + 128
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(lpa, "serves", lambda block: True)
            return xing.piece_attention(
                q, cache_a, jnp.int32(0), slots, q_pos, kv_len,
                (jnp.max(kv_len) + BLK - 1) // BLK, layer, cfg)

    fn = jax.jit(run)
    for pos0 in ([BLK, 0], [4 * BLK, 3 * BLK]):
        live = (max(pos0) + 128 + BLK - 1) // BLK * BLK
        dirty = cache_a.at[:, :, :, live:].set(jnp.nan)
        clean, got = (np.asarray(fn(q, c, jnp.asarray(pos0, jnp.int32)))
                      for c in (cache_a, dirty))
        assert np.isfinite(got).all() and np.array_equal(clean, got)
        want, _ = both_routes(cfg, cache_a, layer, q, pos0, [128, 128],
                              li=0, slots=slots)
        assert np.abs(got - want).max() < F32
    assert len(traces) == 1


@pytest.mark.parametrize("cfg", CFGS.values(), ids=CFGS.keys())
def test_bf16_operands_and_float32_partials(cfg):
    cache_a, layer, q = state(cfg, 9, 2, 128, jnp.bfloat16)
    want, got = both_routes(cfg, cache_a, layer, q, [2 * BLK, BLK + 128],
                            [128, 100])
    assert np.abs(got - want).max() < BF16
    # the carry itself is float32 whatever the operands are
    acc, stats = lpa.empty_carry(2, cfg.n_heads, 128, cfg.v_head_dim)
    k, v = xing.expand(cache_a[0, :2, :, :BLK], layer, cfg)
    acc, stats = lpa.fold_round(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), (acc, stats), jnp.int32(0),
        lpa.plan_queries(jnp.full((2, 128), BLK), jnp.full((2,), 2 * BLK)))
    assert acc.dtype == stats.dtype == jnp.float32
    assert stats.shape == (2, cfg.n_heads, lpa.STAT_ROWS, 128)
    assert np.isfinite(np.asarray(stats[:, :, :2])).all()


# ---------------------------------------------------------------------------
# under a selection (`keep`): of the columns a query sees only those the
# mask marks; the mask is one more operand, shared by all heads
# ---------------------------------------------------------------------------


def seen_of(pos0, lens, s):
    pos0, lens = np.asarray(pos0), np.asarray(lens)
    col = np.arange(EXTENT)[None, None, :]
    q_pos = (pos0[:, None] + np.arange(s)[None, :])[..., None]
    return (col <= q_pos) & (col < (pos0 + lens)[:, None, None])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32),
                                       (jnp.bfloat16, BF16)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("share", [0.02, 0.3, 1.0],
                         ids=["sparse", "third", "all"])
def test_kernel_route_under_a_mask_equals_the_xla_rounds(share, dtype, tol):
    """Two rows at different depths, a random share of what each query
    sees kept: sparse enough that many queries keep nothing of a whole
    round."""
    cfg = CFGS["glm"]
    rng = np.random.default_rng(int(share * 100))
    pos0, lens = [3 * BLK, BLK + 128], [128, 90]
    cache_a, layer, q = state(cfg, 11, 2, 128, dtype)
    keep = seen_of(pos0, lens, 128) & (rng.random((2, 128, EXTENT)) < share)
    keep[:, :, 0] = True            # a query always keeps something
    if share < 1:
        assert (keep.reshape(2, 128, -1, BLK).sum(-1) == 0).any()
    want, got = both_routes(cfg, cache_a, layer, q, pos0, lens, keep)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < tol


@pytest.mark.parametrize("cfg", CFGS.values(), ids=CFGS.keys())
def test_a_query_with_no_column_chosen_in_one_round_or_in_any(cfg):
    """Query 3 keeps nothing of the second round (its maximum and sums
    pass through it untouched), query 5 nothing of the first (it stands
    at -inf until the second), query 7 nothing anywhere: it comes out
    0, finite, as the XLA rounds have it."""
    rng = np.random.default_rng(2)
    pos0, lens = [BLK + 64], [64]
    cache_a, layer, q = state(cfg, 13, 1, 64)
    keep = seen_of(pos0, lens, 64) & (rng.random((1, 64, EXTENT)) < 0.5)
    keep[0, 3, BLK:] = False
    keep[0, 5, :BLK] = False
    keep[0, 7] = False
    want, got = both_routes(cfg, cache_a, layer, q, pos0, lens, keep)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32
    assert np.all(got[0, 7] == 0) and np.abs(got[0, 3]).max() > 0
    # what the mask drops is not read: moving it changes nothing
    moved = cache_a.at[:, :, :, BLK:].add(
        jnp.where(jnp.asarray(keep[0, 3, BLK:]), 0.0, 1.0))
    _, again = both_routes(cfg, moved, layer, q, pos0, lens, keep)
    assert np.array_equal(again[0, 3], got[0, 3])


def test_the_mask_is_shared_by_all_heads_and_read_a_layer_at_a_time():
    """The same mask for every head; another layer of the stack gives
    another answer."""
    cfg = CFGS["glm"]
    rng = np.random.default_rng(4)
    pos0, lens = [2 * BLK], [128]
    cache_a, layer, q = state(cfg, 17, 1, 128)
    keep = seen_of(pos0, lens, 128) & (rng.random((1, 128, EXTENT)) < 0.2)
    keep[:, :, 0] = True
    want, got = both_routes(cfg, cache_a, layer, q, pos0, lens, keep, li=0)
    assert np.abs(got - want).max() < F32
    _, other = both_routes(cfg, cache_a, layer, q, pos0, lens, keep, li=1)
    assert np.abs(got - other).max() > 1e-2


# ---------------------------------------------------------------------------
# the kernel route's expansion: `expand`'s keys and values, heads first
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("cfg", CFGS.values(), ids=CFGS.keys())
def test_the_kernels_operands_are_expands_transposed(cfg, dtype):
    """`expand_heads` against `expand` (the XLA rounds' and the
    oracle's) on a round's latents of three rows: the same keys and
    values, heads before positions. The rotary columns bit for bit in
    either type: the shared key times 1 summed with zeros. The rest are
    float32 sums of the same products, which the CPU's dot blocks by
    the width of what it writes (448 columns a position there, a
    head's here): equal to the last bit or two in float32, and in
    bfloat16, the served type, equal but where that bit decides a
    rounding (a value in a few hundred, one step apart). Whether the
    chip's are bit for bit is `chip_smoke.py`'s to say
    (`latent_expand_rates`)."""
    cache_a, layer, _q = state(cfg, 31, 1, 64, dtype)
    latent = cache_a[1, :3, :, BLK:2 * BLK]
    want_k, want_v = (np.asarray(a.transpose(0, 2, 1, 3), np.float32)
                      for a in xing.expand(latent, layer, cfg))
    w_k, w_v = xing.expansion_weights(layer, cfg)
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim
    assert w_v.shape == (r, cfg.n_heads, cfg.v_head_dim)
    assert w_k.shape == (r + dr, cfg.n_heads, dn + dr)
    k, v = jax.jit(lambda lat: xing.expand_heads(lat, w_k, w_v, dtype))(
        latent)
    assert k.dtype == v.dtype == dtype
    assert k.shape == (3, cfg.n_heads, BLK, dn + dr)
    assert v.shape == (3, cfg.n_heads, BLK, cfg.v_head_dim)
    k, v = np.asarray(k, np.float32), np.asarray(v, np.float32)
    assert np.array_equal(k[..., dn:], want_k[..., dn:])
    for got, want in ((k, want_k), (v, want_v)):
        off = np.abs(got - want)
        if dtype == jnp.bfloat16:
            assert (off <= np.abs(want) * 2.0 ** -7).all()
            assert (off > 0).mean() < 0.01
        else:
            assert off.max() <= 4 * np.finfo(np.float32).eps \
                * np.abs(want).max()


@pytest.mark.parametrize("widths,in_weight", [
    (dict(qk_nope_head_dim=192, qk_rope_head_dim=64), True),
    (dict(qk_nope_head_dim=128, qk_rope_head_dim=64), False),
    (dict(qk_nope_head_dim=128, qk_rope_head_dim=128), False),
    (dict(qk_nope_head_dim=64, qk_rope_head_dim=64), True)],
    ids=["glm", "xing", "whole-tiles", "half-tile"])
def test_the_rotary_key_rides_in_the_weight_where_it_adds_no_tile(
        widths, in_weight):
    """By the widths alone: a round is expanded heads first, the rotary
    key through the keys' weight, when a head's whole key fills no more
    tiles of 128 columns than its no-position part (the served 192 +
    64); where that part fills its tiles (the served 128 + 64) the
    route keeps `expand`."""
    cfg = dataclasses.replace(CFGS["glm"], **widths)
    assert xing._rotary_in_weight(cfg) == in_weight


@pytest.mark.parametrize("cfg", CFGS.values(), ids=CFGS.keys())
def test_the_bytes_a_waves_rounds_expand(cfg):
    """Rows of 3 and 1 live blocks (of 128 here) in a wave: the longest
    row's rounds, every row's columns, all the layers."""
    got = xing.expand_bytes_moved([2 * BLK + 5, 9], EXTENT, cfg, 2)
    width = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim
    assert got == 3 * cfg.n_layers * 2 * cfg.n_heads * BLK * width * 2
    assert xing.expand_bytes_moved([0, 0], EXTENT, cfg, 2) == 0


# ---------------------------------------------------------------------------
# the route, the tiles, the plan
# ---------------------------------------------------------------------------


def test_serves_follows_the_backend_and_the_round(monkeypatch):
    assert not lpa.serves(1024)                  # the CPU's route
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lpa.serves(1024) and lpa.serves(lpa.MIN_BLOCK)
    assert not lpa.serves(1000) and not lpa.serves(64)


def test_tiles_divide_what_they_tile(monkeypatch):
    monkeypatch.setattr(lpa, "TQ", 512)
    assert [lpa._tile(s, lpa.TQ) for s in (256, 512, 1024, 2048)] \
        == [256, 512, 512, 512]
    assert lpa._tile(1024, lpa.TK) == 1024 and lpa._tile(128, lpa.TK) == 128
    assert lpa._tile(768, 512) == 256 and lpa._tile(64, 512) == 64


def test_the_plan_tells_a_tile_seen_whole_from_one_not_seen():
    """Row 0 a full piece at 256, row 1 five tokens at 0 (tiles of 64):
    the last column each query sees, and its least and largest a
    tile."""
    q_pos = jnp.asarray([256, 0])[:, None] + jnp.arange(128)[None, :]
    last, lo, hi = lpa.plan_queries(q_pos, jnp.asarray([384, 5]))
    assert last.shape == (2, 128, 1) and last.dtype == jnp.int32
    assert np.asarray(last[0, :, 0]).tolist() == list(range(256, 384))
    assert np.asarray(last[1, :, 0]).tolist() == [0, 1, 2, 3] + [4] * 124
    assert np.asarray(lo).tolist() == [256, 320, 0, 4]
    assert np.asarray(hi).tolist() == [319, 383, 4, 4]
    # a row of no token sees nothing: -1
    last, lo, hi = lpa.plan_queries(q_pos[1:], jnp.asarray([0]))
    assert np.all(np.asarray(last) == -1) and np.asarray(hi).tolist() \
        == [-1, -1]


def test_a_round_no_query_sees_leaves_the_carry_as_it_was():
    """The upper half of a piece's own rounds, and a shorter row's
    rounds past its length: nothing is computed, the carry passes."""
    cfg = CFGS["xing"]
    cache_a, layer, q = state(cfg, 19, 2, 64)
    h, dv = cfg.n_heads, cfg.v_head_dim
    rng = np.random.default_rng(0)
    acc = jnp.asarray(rng.normal(size=(2, h, 64, dv)), jnp.float32)
    stats = jnp.asarray(rng.normal(size=(2, h, lpa.STAT_ROWS, 64)),
                        jnp.float32)
    k, v = xing.expand(
        jnp.full_like(cache_a[0, :2, :, :BLK], jnp.nan), layer, cfg)
    plan = lpa.plan_queries(jnp.arange(64)[None] + jnp.zeros((2, 1),
                                                             jnp.int32),
                            jnp.asarray([64, 64]))
    got_acc, got_stats = lpa.fold_round(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), (acc, stats), jnp.int32(BLK), plan)
    assert np.array_equal(np.asarray(got_acc), np.asarray(acc))
    assert np.array_equal(np.asarray(got_stats), np.asarray(stats))


def test_rounds_wider_than_a_column_tile_walk_their_tiles(monkeypatch):
    """A round of several column tiles (TK under the round's width):
    the carry stays in the kernel while they pass, the mask's tiles
    come by a leading index."""
    monkeypatch.setattr(lpa, "TK", 32)
    for name, masked in (("xing", False), ("glm", True)):
        cfg = CFGS[name]
        cache_a, layer, q = state(cfg, 23, 2, 128)
        pos0, lens = [BLK + 64, 0], [128, 77]
        keep = None
        if masked:
            keep = seen_of(pos0, lens, 128) & (
                np.random.default_rng(1).random((2, 128, EXTENT)) < 0.3)
        want, got = both_routes(cfg, cache_a, layer, q, pos0, lens, keep)
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() < F32
