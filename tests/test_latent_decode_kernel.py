# The kernel that reads only the live blocks of a slot of the stacked
# latent cache (ops/latent_attention.py), through the Pallas
# interpreter, against the XLA route over every slot's whole extent
# (ops/attention.py `decode_attention_prefix_window` with the latent
# rows as the one kv head's keys AND values): the kernel's partial +
# the XLA partial of the dispatch's own rows + `combine_partials` is the
# same softmax.
#
# Sizes: a cache of four of the kernel's blocks a slot, whatever the
# block the chip chose; three layers, four heads, rows of 32 + 8.
# Tolerances, each with its reason:
#   F32 = 2e-6 on outputs of size ~1: both routes are float32 here and
#   differ by the order of float32 sums (flash partials against one
#   softmax row); the largest difference seen is 5e-7.
#   BF16 = 8e-3: with bf16 queries and cache both routes round the
#   probabilities and the output to bf16 (8 bits: 2e-3 at the outputs'
#   size, 0.35), the kernel before normalising and the XLA route
#   after; the difference seen is one such step, 2e-3.
#   STEP = 1e-4 on logits of size ~3 (tests/test_xing_engine.py's TOL,
#   for its reason: float32 throughout, another order of sums).
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from copilot_for_consensus_tpu.models import xing
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.ops import dense_attention
from copilot_for_consensus_tpu.ops import latent_attention as la
from copilot_for_consensus_tpu.ops.attention import (
    combine_partials,
    decode_attention_prefix_window,
    decode_window_partial,
)

EXTENT = 4 * la.BLOCK
BLK = la.block_size(EXTENT)
N_L, H, RANK, ROPE, W = 3, 4, 32, 8, 8
WIDTH = RANK + ROPE
F32, BF16, STEP = 2e-6, 8e-3, 1e-4
FULL = EXTENT - W - 1


def test_the_sizes_here_give_four_blocks_a_slot():
    assert BLK == la.BLOCK >= la.MIN_BLOCK
    assert EXTENT // BLK == 4


def state(seed, slots, dtype):
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    return rand(N_L, slots, WIDTH, EXTENT), dict(
        q=rand(slots, H, WIDTH), win=rand(slots, W, WIDTH),
        cur=rand(slots, WIDTH))


def poisoned(cache_a, hi):
    """NaN in every column that is not live."""
    dead = np.arange(EXTENT)[None, :] >= np.asarray(hi)[:, None]
    return jnp.where(jnp.asarray(dead)[None, :, None, :],
                     jnp.asarray(jnp.nan, cache_a.dtype), cache_a)


def both_routes(cache_a, local, pos0, w, li=1, poison=True):
    """(a layer's rows whole under one softmax with the dispatch's own,
    the kernel's partial folded with their XLA partial), ``[B, H,
    RANK]`` float32 each, and the rows of slots that hold a
    sequence."""
    pos0 = jnp.asarray(pos0, jnp.int32)
    w = jnp.int32(w)
    q, win, cur = local["q"], local["win"], local["cur"]
    one = lambda a: a[:, None]  # noqa: E731
    rows = one(cache_a[li].transpose(0, 2, 1))
    want = decode_attention_prefix_window(
        q, rows, rows, one(win), one(win), one(cur), one(cur), pos0,
        w)[..., :RANK]
    _, hi = dense_attention.live_range(pos0, pos0, 0, EXTENT)
    read = poisoned(cache_a, hi) if poison else cache_a

    def kernel_route(read, q):
        past = la.live_partial(q, read, jnp.int32(li),
                               la.plan_blocks(pos0, extent=EXTENT),
                               rank=RANK)
        own = decode_window_partial(
            one(q), one(win), one(win[..., :RANK]), one(cur),
            one(cur[..., :RANK]), pos0, w)
        return combine_partials([tuple(one(a) for a in past), own],
                                q.dtype)[:, 0]

    got = jax.jit(kernel_route)(read, q)
    live = np.asarray(pos0) < EXTENT
    return (np.asarray(want.astype(jnp.float32)),
            np.asarray(got.astype(jnp.float32)), live)


@pytest.mark.parametrize("w", [0, 3], ids=["w0", "w3"])
@pytest.mark.parametrize(
    "length", [0, 1, BLK - 1, BLK, BLK + 1, 2 * BLK + BLK // 3, FULL],
    ids=["0", "1", "edge-1", "edge", "edge+1", "mid-block", "full"])
def test_kernel_route_equals_the_prefix_window_route(length, w):
    """One slot of the given length beside a slot of another; dead
    columns hold NaN."""
    cache_a, local = state(length * 7 + w, 2, jnp.float32)
    want, got, _ = both_routes(cache_a, local, [length, FULL - length], w)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32


def test_mixed_slots_in_one_call_and_slots_with_nothing_live():
    """Five slots: free (parked at the extent), a sequence of no
    column yet, one column, mid-block extents, another parked one; each
    live slot is served as it is alone."""
    cache_a, local = state(2, 5, jnp.float32)
    pos0 = [EXTENT, 0, 1, 2 * BLK + 77, EXTENT]
    want, got, live = both_routes(cache_a, local, pos0, 0)
    assert np.isfinite(got).all()
    assert live.tolist() == [False, True, True, True, False]
    assert np.abs(got - want)[live].max() < F32
    # a slot with nothing live, at the dispatch's first step, attends to
    # its own row alone: the output is that row's value
    own = np.asarray(local["cur"])[:, None, :RANK]
    for slot in (0, 1, 4):
        np.testing.assert_allclose(
            got[slot], np.broadcast_to(own[slot], got[slot].shape),
            atol=F32)


def test_a_slot_with_nothing_live_carries_an_empty_partial():
    cache_a, local = state(3, 3, jnp.float32)
    pos0 = jnp.asarray([EXTENT, 5, 0], jnp.int32)
    acc, m, l = la.live_partial(
        local["q"], poisoned(cache_a, [0, 5, 0]), jnp.int32(0),
        la.plan_blocks(pos0, extent=EXTENT), rank=RANK)
    assert acc.shape == (3, H, RANK) and m.shape == l.shape == (3, H, 1)
    for slot in (0, 2):
        assert np.all(np.asarray(m[slot]) == -np.inf)
        assert np.all(np.asarray(l[slot]) == 0)
        assert np.all(np.asarray(acc[slot]) == 0)
    assert np.isfinite(np.asarray(m[1])).all() and np.all(
        np.asarray(l[1]) >= 1)


def test_the_plan_is_the_dense_plan_counted_in_this_kernels_blocks():
    pos0 = np.asarray([EXTENT, 0, 1, 2 * BLK + 77, BLK, EXTENT])
    steps, n_steps, hi = la.plan_blocks(jnp.asarray(pos0), extent=EXTENT)
    assert steps.shape == (6, 6 * 4)
    steps = np.asarray(steps)[:, :int(n_steps)]
    # steps: 1 (nothing) + 1 (nothing) + 1 + 3 + 1 + 1 (nothing)
    assert int(n_steps) == 8
    assert steps[la._READS].tolist() == [0, 0, 1, 1, 1, 1, 1, 0]
    assert steps[la._SLOT].tolist() == [0, 1, 2, 3, 3, 3, 4, 5]
    assert steps[la._FIRST].tolist() == [1, 1, 1, 1, 0, 0, 1, 1]
    assert steps[la._LAST].tolist() == [1, 1, 1, 0, 0, 1, 1, 1]
    reads = steps[la._READS] == 1
    assert list(zip(steps[la._KSLOT][reads], steps[la._KBLK][reads])) == [
        (2, 0), (3, 0), (3, 1), (3, 2), (4, 0)]
    assert np.asarray(hi).tolist() == [0, 0, 1, 2 * BLK + 77, BLK, 0]
    # and the columns under them are what the host counts
    assert reads.sum() * BLK == sum(
        la.blocks_read(int(n), EXTENT) for n in np.asarray(hi))


@pytest.mark.parametrize("width", [la.MIN_BLOCK, 2 * la.BLOCK])
def test_the_plan_follows_the_kernels_own_block_width(monkeypatch, width):
    """Not `dense_attention.BLOCK`'s: a full slot of a cache eight
    blocks long takes eight steps, whichever the width."""
    monkeypatch.setattr(la, "BLOCK", width)
    extent = 8 * width
    pos0 = jnp.asarray([extent - 1, width + 1, extent])
    steps, n_steps, _ = la.plan_blocks(pos0, extent=extent)
    assert steps.shape == (6, 3 * 8) and int(n_steps) == 8 + 2 + 1
    assert la.blocks_read(width + 1, extent) == 2 * width


def test_bf16_queries_over_a_bf16_cache():
    cache_a, local = state(4, 3, jnp.bfloat16)
    want, got, live = both_routes(cache_a, local,
                                  [EXTENT, BLK + 2, 3 * BLK], 7)
    assert np.isfinite(got).all()
    assert np.abs(got - want)[live].max() < BF16


@pytest.mark.parametrize("li", [0, 1, 2])
def test_the_layer_is_read_by_pointer_out_of_a_stack_of_several(li):
    """The same call at another layer index reads other blocks: the
    answer is that layer's, and no other layer's."""
    cache_a, local = state(6, 2, jnp.float32)
    want, got, _ = both_routes(cache_a, local, [100, FULL], 1, li=li)
    assert np.abs(got - want).max() < F32
    other, _, _ = both_routes(cache_a, local, [100, FULL], 1,
                              li=(li + 1) % N_L)
    assert np.abs(got - other).max() > 1e-2


def test_dead_columns_change_nothing():
    """Poisoned with NaN or left as they are: the same bits."""
    cache_a, local = state(8, 3, jnp.float32)
    pos0 = [BLK + 9, 3 * BLK - 1, EXTENT]
    _, clean, live = both_routes(cache_a, local, pos0, 2, poison=False)
    _, dirty, _ = both_routes(cache_a, local, pos0, 2, poison=True)
    assert np.array_equal(clean[live], dirty[live])


def test_blocks_read_rounds_the_length_up_to_blocks():
    assert la.blocks_read(0, EXTENT) == 0
    assert la.blocks_read(1, EXTENT) == BLK
    assert la.blocks_read(BLK, EXTENT) == BLK
    assert la.blocks_read(BLK + 1, EXTENT) == 2 * BLK
    # an extent the block does not divide takes the common divisor, and
    # one that leaves less than a lane tile of columns is not served
    assert la.block_size(16384) == la.BLOCK
    assert la.block_size(la.BLOCK + la.MIN_BLOCK) == la.MIN_BLOCK
    assert la.block_size(1000) == 8


def test_serves_follows_the_backend_and_the_extent(monkeypatch):
    assert not la.serves(16384)                  # the CPU's route
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.serves(16384) and la.serves(la.BLOCK + la.MIN_BLOCK)
    assert not la.serves(1000)


# ---------------------------------------------------------------------------
# the model step through both routes
# ---------------------------------------------------------------------------

CFG = decoder_config("tiny-xing")


@pytest.fixture(scope="module")
def step_state():
    params = xing.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32,
                              quantize=True)
    rng = np.random.default_rng(7)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    slots = 4
    width = xing.latent_width(CFG)
    cache = {name: rand(count, slots, width, EXTENT)
             for name, count in xing.stacks(CFG).items()}
    win = {name: rand(count, slots, W, width)
           for name, count in xing.stacks(CFG).items()}
    return params, cache, win


@pytest.mark.parametrize("w", [0, 3], ids=["w0", "w3"])
def test_the_model_step_through_the_kernel_equals_the_xla_step(
        step_state, w):
    """`xing.decode_step` with the dispatch's plan (the stacks closed
    over, the kernel) against it without (the stacks scanned, scored
    whole): logits and the step's new rows. Slot 0 is parked, the
    others lie in different blocks. At `w` = 0 only the token's own row
    joins the softmax, at `w` > 0 the dispatch's earlier rows too."""
    params, cache, win = step_state
    pos0 = jnp.asarray([EXTENT, 3, BLK + 5, 3 * BLK - 1], jnp.int32)
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)

    def step(live_blocks, cache):
        plan = la.plan_blocks(pos0, extent=EXTENT) if live_blocks else None
        logits, cols, _counts = xing.decode_step(
            params, tok, pos0, jnp.int32(w), CFG, cache, win, EXTENT, plan)
        return logits, cols

    (want, want_cols), (got, got_cols) = (
        jax.jit(step, static_argnums=0)(flag, cache)
        for flag in (False, True))
    live = np.asarray(pos0) < EXTENT
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < STEP
    for name in cache:
        diff = np.abs(np.asarray(got_cols[name])
                      - np.asarray(want_cols[name]))
        assert diff[:, live].max() < STEP
    # the dispatch's own rows were part of it
    if w:
        moved = {n: a.at[:, :, 0].add(1.0) for n, a in win.items()}
        again, _cols, _c = jax.jit(lambda c: xing.decode_step(
            params, tok, pos0, jnp.int32(w), CFG, c, moved, EXTENT,
            la.plan_blocks(pos0, extent=EXTENT)))(cache)
        assert np.abs(np.asarray(again) - np.asarray(got))[live].max() \
            > 1e-3


def test_a_dispatch_through_the_kernel_equals_the_xla_dispatch(step_state):
    """`decode_tokens` whole: the plan made once, eight tokens, the
    merge; the tokens and the cache afterwards."""
    params, cache, _win = step_state
    pos0 = jnp.asarray([EXTENT, 3, BLK + 5, 3 * BLK - 1], jnp.int32)
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)

    def greedy(logits, _key):
        return jnp.argmax(logits, -1).astype(jnp.int32)

    outs = [jax.jit(lambda c, flag=flag: xing.decode_tokens(
        params, tok, pos0, CFG, c, jax.random.PRNGKey(0), greedy, steps=W,
        max_len=EXTENT, with_logits=True, live_blocks=flag))(cache)
        for flag in (False, True)]
    (toks_x, cache_x, counts_x, logits_x), (toks_k, cache_k, counts_k,
                                            logits_k) = outs
    live = np.asarray(pos0) < EXTENT
    assert np.array_equal(np.asarray(toks_x)[:, live],
                          np.asarray(toks_k)[:, live])
    assert np.abs(np.asarray(logits_k)
                  - np.asarray(logits_x))[:, live].max() < STEP
    assert np.asarray(counts_x).tolist() == np.asarray(counts_k).tolist()
    for name in cache:
        assert np.abs(np.asarray(cache_k[name])
                      - np.asarray(cache_x[name]))[:, live].max() < STEP


# ---------------------------------------------------------------------------
# under a selection (`keep`, PR 37): of the live columns only those the
# mask marks are scored; its blocks ride beside the cache's
# ---------------------------------------------------------------------------


def kept_routes(cache_a, local, pos0, w, keep_c, keep_o, li=1):
    """`xing._kept_attention` on its two routes: the layer's rows whole
    in XLA under the masks, and the kernel over the live blocks with
    the cached mask beside them (dead columns NaN)."""
    pos0 = jnp.asarray(pos0, jnp.int32)
    _, hi = dense_attention.live_range(pos0, pos0, 0, EXTENT)
    keep = (jnp.asarray(keep_c), jnp.asarray(keep_o))
    q, win, cur = local["q"], local["win"], local["cur"]
    want = xing._kept_attention(q, cur, cache_a[li], win, None, keep, RANK)
    got = jax.jit(lambda read, q: xing._kept_attention(
        q, cur, None, win,
        (read, jnp.int32(li), la.plan_blocks(pos0, extent=EXTENT)), keep,
        RANK))(poisoned(cache_a, hi), q)
    return (np.asarray(want.astype(jnp.float32)),
            np.asarray(got.astype(jnp.float32)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32),
                                       (jnp.bfloat16, BF16)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("share", [0.03, 0.5, 1.0],
                         ids=["sparse", "half", "all"])
def test_kernel_route_under_a_mask_equals_the_xla_route(share, dtype, tol):
    """Three slots (one past a block's edge, one a few columns long, one
    free), a random share of each slot's live columns kept (sparse
    enough that whole blocks hold none), the dispatch's own rows kept
    in part."""
    rng = np.random.default_rng(int(share * 100))
    lens = [2 * BLK + 5, 7, EXTENT]
    cache_a, local = state(3, 3, dtype)
    live = np.arange(EXTENT)[None, :] < np.asarray(lens)[:, None]
    keep_c = live & (rng.random((3, EXTENT)) < share)
    keep_c[:2, 0] = True            # a query always keeps something
    keep_o = np.zeros((3, W + 1), bool)
    keep_o[:, :3] = rng.random((3, 3)) < 0.5
    keep_o[:, -1] = True
    want, got = kept_routes(cache_a, local, lens, 3, keep_c, keep_o)
    assert np.isfinite(got[:2]).all()
    assert np.abs(got[:2] - want[:2]).max() < tol


def test_a_slot_whose_cached_columns_are_all_dropped_reads_its_own_rows():
    """`keep` drops every cached column of a slot: the kernel's partial
    carries no mass and the output is the softmax over the dispatch's
    own rows alone."""
    cache_a, local = state(5, 2, jnp.float32)
    lens = [BLK + 3, 40]
    keep_c = np.zeros((2, EXTENT), bool)
    keep_c[1, :40] = True
    keep_o = np.ones((2, W + 1), bool)
    keep_o[:, 3:-1] = False
    want, got = kept_routes(cache_a, local, lens, 3, keep_c, keep_o)
    assert np.abs(got - want).max() < F32
    alone = xing._kept_attention(
        local["q"], local["cur"], jnp.zeros_like(cache_a[1]), local["win"],
        None, (jnp.zeros((2, EXTENT), bool), jnp.asarray(keep_o)), RANK)
    assert np.abs(got[0] - np.asarray(alone)[0]).max() < F32
