# The kernel that reads only the live blocks of a slot's window and
# summaries (ops/eva_attention.py), through the Pallas interpreter,
# against the XLA route over whole pieces (models/eva.py
# `joint_attention`): the kernel's partial + the XLA partial of the
# dispatch's own pieces + `combine_partials` is the same softmax.
#
# Sizes: windows of 256 columns and a summary store of 256, so that the
# kernel's constants (128-column blocks) give two blocks a piece; two
# layers, two heads of 16. Tolerances, each with its reason:
#   F32 = 2e-6 on outputs of size ~1: both routes are float32 here and
#   differ by the order of float32 sums (flash partials against one
#   softmax row); the largest difference seen is 2.7e-7.
#   BF16 = 8e-3: with bf16 queries and state both routes round the
#   probabilities and the output to bf16 (8 bits: 2e-3 at the outputs'
#   size, 0.35), the kernel before normalising and the XLA route
#   after; the difference seen is one such step, 2e-3.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from copilot_for_consensus_tpu.models import eva
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.ops import eva_attention as ea

W, C, MAX_LEN, MARGIN, STEPS = 256, 16, 4096, 8, 8
WC, R = W // C, MAX_LEN // C
N_L, H, DH = 2, 2, 16
WB, SB = ea.block_sizes(W, R)
F32, BF16 = 2e-6, 8e-3


def test_the_sizes_here_give_two_blocks_a_piece():
    assert (WB, SB) == (ea.WIN_BLOCK, ea.SUM_BLOCK) == (128, 128)
    assert (W // WB, R // SB) == (2, 2)


def state(seed, slots, dtype, H=H):
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    cache = {"k": rand(N_L, slots, H, W + MARGIN, DH),
             "v": rand(N_L, slots, H, W + MARGIN, DH),
             "ks": rand(N_L, slots, H, R, DH),
             "vs": rand(N_L, slots, H, R, DH)}
    local = dict(q=rand(slots, H, DH), k_cur=rand(slots, H, DH),
                 v_cur=rand(slots, H, DH), k_buf=rand(slots, H, STEPS, DH),
                 v_buf=rand(slots, H, STEPS, DH), k_new=rand(slots, H, WC, DH),
                 v_new=rand(slots, H, WC, DH))
    return cache, local


def poisoned(cache, win_len, sum_n):
    """NaN in every column that is not live, the margin included."""
    dead_w = np.arange(W + MARGIN)[None, :] >= np.asarray(win_len)[:, None]
    dead_s = np.arange(R)[None, :] < R - np.asarray(sum_n)[:, None]
    out = {}
    for half, dead in (("k", dead_w), ("v", dead_w), ("ks", dead_s),
                       ("vs", dead_s)):
        out[half] = jnp.where(jnp.asarray(dead)[None, :, None, :, None],
                              jnp.nan, cache[half])
    return out


def both_routes(cache, local, win_len, sum_n, n_buf, crossed, li=1,
                poison=True):
    """(whole pieces under one softmax, the kernel's partial folded
    with the XLA partial), ``[B, H, Dh]`` float32 each. ``n_buf``: the
    dispatch's own columns seen; ``crossed``: the slots that see
    ``k_new`` in place of their window."""
    win_len, sum_n, crossed = (jnp.asarray(a) for a in
                               (win_len, sum_n, crossed))
    b = win_len.shape[0]
    m_buf = jnp.broadcast_to(jnp.arange(STEPS)[None, :] < n_buf, (b, STEPS))
    pieces = [(local["k_buf"], local["v_buf"], m_buf),
              (local["k_new"], local["v_new"],
               jnp.broadcast_to(crossed[:, None], (b, WC)))]
    m_win = jnp.arange(W + MARGIN)[None, :] < win_len[:, None]
    m_sum = jnp.arange(R)[None, :] >= R - sum_n[:, None]
    want = eva.joint_attention(
        local["q"], local["k_cur"], local["v_cur"],
        [(cache["k"][li], cache["v"][li], m_win), pieces[0],
         (cache["ks"][li], cache["vs"][li], m_sum), pieces[1]])
    read = poisoned(cache, win_len, sum_n) if poison else cache
    got = jax.jit(eva.live_attention, static_argnums=(7,))(
        local["q"], local["k_cur"], local["v_cur"], pieces, read,
        jnp.int32(li), ea.plan_blocks(win_len, sum_n, window=W, store=R), W)
    return (np.asarray(want.astype(jnp.float32)),
            np.asarray(got.astype(jnp.float32)))


@pytest.mark.parametrize("n_sum", [0, WC, R - WC],
                         ids=["no-summary", "one-window", "full-store"])
@pytest.mark.parametrize("fill", [0, 1, WB - 1, WB, WB + 1, W - 1])
def test_kernel_route_equals_joint_attention(fill, n_sum):
    """One slot at the given fill behind the given summaries beside a
    slot at another extent; dead columns and the margin hold NaN."""
    cache, local = state(fill * 7 + n_sum, 2, jnp.float32)
    want, got = both_routes(cache, local, [fill, W - 1 - fill],
                            [n_sum, R - WC - n_sum], 3, [False, False])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32


def test_a_slot_that_crossed_sees_its_fresh_summaries_and_no_window():
    """Slot 0's window filled earlier in the dispatch: its window is
    dead whatever it holds, `k_new` is seen. Slot 1 goes on."""
    cache, local = state(1, 2, jnp.float32)
    want, got = both_routes(cache, local, [0, 200], [3 * WC, WC], 5,
                            [True, False])
    assert np.abs(got - want).max() < F32
    # and the fresh summaries were part of it
    local["v_new"] = local["v_new"] + 1.0
    _, moved = both_routes(cache, local, [0, 200], [3 * WC, WC], 5,
                           [True, False])
    assert np.abs(moved[0] - got[0]).max() > 1e-2
    assert np.abs(moved[1] - got[1]).max() == 0


def test_mixed_slots_in_one_call_and_a_slot_with_nothing_live():
    """Four slots: nothing live at all (a parked slot: its plan is one
    step that reads nothing), a window alone, summaries alone, both;
    each is served as it is alone."""
    cache, local = state(2, 4, jnp.float32)
    win_len, sum_n = [0, 77, 0, 255], [0, 0, 5 * WC, R - WC]
    want, got = both_routes(cache, local, win_len, sum_n, 0, [False] * 4)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32
    # a slot with nothing live and none of the dispatch's columns
    # attends to its own key alone: the output is its own value
    np.testing.assert_allclose(got[0], np.asarray(local["v_cur"][0]),
                               atol=F32)
    steps, n_steps, lens = ea.plan_blocks(
        jnp.asarray(win_len), jnp.asarray(sum_n), window=W, store=R)
    # steps: 1 (nothing) + 1 + 1 + (2 + 2); the rest of the grid idles
    assert int(n_steps) == 7
    assert np.asarray(steps[0]).tolist()[:8] == [2, 0, 1, 0, 0, 1, 1, 2]
    assert np.asarray(lens).tolist() == [win_len, sum_n]


def test_bf16_queries_and_state():
    cache, local = state(3, 3, jnp.bfloat16)
    want, got = both_routes(cache, local, [0, 130, 255],
                            [R - WC, 2 * WC, 0], 8, [False] * 3)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < BF16


def test_the_layer_is_read_by_pointer():
    """The same call against layer 0 and layer 1 reads other blocks."""
    cache, local = state(4, 2, jnp.float32)
    for li in (0, 1):
        want, got = both_routes(cache, local, [100, 256 - 1], [WC, 64], 2,
                                [False, False], li=li)
        assert np.abs(got - want).max() < F32


def test_blocks_read_rounds_each_piece_to_its_blocks():
    assert ea.blocks_read(0, 0, W, R) == (0, 0)
    assert ea.blocks_read(1, WC, W, R) == (WB, SB)
    assert ea.blocks_read(WB, SB, W, R) == (WB, SB)
    assert ea.blocks_read(WB + 1, SB + WC, W, R) == (2 * WB, 2 * SB)
    # EvaByte's widths: summaries come 128 at a time, a block exactly
    assert ea.block_sizes(2048, 1024) == (128, 128)
    assert ea.blocks_read(300, 384, 2048, 1024) == (384, 384)


# ---------------------------------------------------------------------------
# the whole decode dispatch through both routes
# ---------------------------------------------------------------------------

CFG = decoder_config("tiny-eva", window_size=W, chunk_size=C,
                     max_seq_len=MAX_LEN)


@pytest.mark.parametrize("may_close", [False, True],
                         ids=["plain", "may-close"])
def test_decode_tokens_through_the_kernel_equal_the_xla_route(
        monkeypatch, may_close):
    """`decode_tokens` with the kernel route forced on (the interpreter
    here) against the XLA route, same cache and tokens: every step's
    logits of all heads, and the merged cache. Slot 0 is parked
    (position `max_len`), slot 1 decodes deep in its window, slot 2
    fills its window at the dispatch's fourth step when `may_close`
    (its later steps see `k_new` and no window), slot 3 decodes from an
    empty window behind summaries."""
    params = eva.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    assert (CFG.n_layers, CFG.head_dim) == (N_L, DH)
    cache, _ = state(5, 4, jnp.float32, CFG.n_heads)
    pos0 = jnp.asarray([MAX_LEN, W + 131,
                        2 * W + (W - 4 if may_close else 40), 3 * W],
                       jnp.int32)
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)

    def run():
        return jax.jit(lambda c: eva.decode_tokens(
            params, tok, pos0, CFG, c, jax.random.PRNGKey(0),
            lambda lg, _k: jnp.argmax(lg, -1).astype(jnp.int32),
            steps=STEPS, may_close=may_close, max_len=MAX_LEN,
            with_logits=True))(cache)

    assert not eva._reads_live_blocks()          # the CPU's route
    toks_x, cache_x, logits_x = run()
    monkeypatch.setattr(eva, "_reads_live_blocks", lambda: True)
    toks_k, cache_k, logits_k = run()
    live = np.asarray(pos0) < MAX_LEN
    assert np.abs(np.asarray(logits_k - logits_x))[:, live].max() < 1e-4
    assert np.array_equal(np.asarray(toks_k)[:, live],
                          np.asarray(toks_x)[:, live])
    for half in cache_x:
        np.testing.assert_allclose(
            np.asarray(cache_k[half])[:, live],
            np.asarray(cache_x[half])[:, live], atol=1e-5)
