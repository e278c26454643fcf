# The kernel that reads only the live blocks of a slot of the dense
# stacked cache (ops/dense_attention.py), through the Pallas
# interpreter, against the XLA route over a whole prefix
# (ops/attention.py `decode_attention_prefix_window`): the kernel's
# partial + the XLA partial of the dispatch's own pieces +
# `combine_partials` is the same softmax.
#
# Sizes: a cache of four of the kernel's blocks a slot, whatever the
# block the chip chose; two layers, two kv heads of 16. Tolerances, each
# with its reason:
#   F32 = 2e-6 on outputs of size ~1: both routes are float32 here and
#   differ by the order of float32 sums (flash partials against one
#   softmax row); the largest difference seen is 4e-7.
#   BF16 = 8e-3: with bf16 queries and cache both routes round the
#   probabilities and the output to bf16 (8 bits: 2e-3 at the outputs'
#   size, 0.35), the kernel before normalising and the XLA route
#   after; the difference seen is one such step, 2e-3. An fp8 cache is
#   cast to bf16 on load by both: the same bound.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.models import decoder
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.ops import dense_attention as da
from copilot_for_consensus_tpu.ops.attention import (
    combine_partials,
    decode_attention_prefix_window,
    decode_window_partial,
)

EXTENT = 4 * da.BLOCK
BLK = da.block_size(EXTENT)
N_L, HKV, DH, W = 2, 2, 16, 8
F32, BF16 = 2e-6, 8e-3


def test_the_sizes_here_give_four_blocks_a_slot():
    assert BLK == da.BLOCK >= da.MIN_BLOCK
    assert EXTENT // BLK == 4


def state(seed, slots, dtype, group, kv_dtype=None, n_done=0):
    rng = np.random.default_rng(seed)

    def rand(*shape, dt=dtype):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dt)

    cache = {h: rand(N_L, slots, HKV, EXTENT, DH, dt=kv_dtype or dtype)
             for h in "kv"}
    local = dict(q=rand(slots, HKV * group, DH),
                 k_win=rand(slots, HKV, W, DH), v_win=rand(slots, HKV, W, DH),
                 k_cur=rand(slots, HKV, DH), v_cur=rand(slots, HKV, DH))
    if n_done:
        local.update(k_done=rand(slots, HKV, n_done, DH),
                     v_done=rand(slots, HKV, n_done, DH))
    return cache, local


def poisoned(cache, lo, hi):
    """NaN in every column that is not live."""
    col = np.arange(EXTENT)[None, :]
    dead = (col < np.asarray(lo)[:, None]) | (col >= np.asarray(hi)[:, None])
    return {h: jnp.where(jnp.asarray(dead)[None, :, None, :, None],
                         jnp.asarray(jnp.nan, cache[h].dtype), cache[h])
            for h in cache}


def both_routes(cache, local, pos0, w, window=0, li=1, poison=True):
    """(the prefix whole under one softmax with the dispatch's pieces,
    the kernel's partial folded with their XLA partial), ``[B, Hq, Dh]``
    float32 each, and the rows of slots that hold a sequence."""
    pos0 = jnp.asarray(pos0, jnp.int32)
    w = jnp.int32(w)
    q = local["q"]
    b, hq, dh = q.shape
    done = {n: local[n] for n in ("k_done", "v_done") if n in local}
    n_done = done["k_done"].shape[2] if done else 0
    want = decode_attention_prefix_window(
        q, cache["k"][li], cache["v"][li], local["k_win"], local["v_win"],
        local["k_cur"], local["v_cur"], prefix_lengths=pos0, w=w,
        window=window, **done)
    lo, hi = da.live_range(pos0, pos0 + n_done + w, window, EXTENT)
    read = poisoned(cache, lo, hi) if poison else cache

    def kernel_route(read, q):
        qg = q.reshape(b, HKV, hq // HKV, dh)
        part = da.live_partial(qg, read["k"], read["v"], jnp.int32(li),
                               da.plan_blocks(lo, hi, extent=EXTENT))
        loc = decode_window_partial(
            qg, local["k_win"], local["v_win"], local["k_cur"],
            local["v_cur"], pos0, w, window=window, **done)
        return combine_partials([part, loc], q.dtype).reshape(b, hq, dh)

    got = jax.jit(kernel_route)(read, q)
    live = np.asarray(pos0) < EXTENT
    return (np.asarray(want.astype(jnp.float32)),
            np.asarray(got.astype(jnp.float32)), live)


@pytest.mark.parametrize("group", [1, 4], ids=["G1", "G4"])
@pytest.mark.parametrize(
    "length", [0, 1, BLK - 1, BLK, BLK + 1, EXTENT - W - 1],
    ids=["0", "1", "edge-1", "edge", "edge+1", "full"])
def test_kernel_route_equals_the_prefix_window_route(length, group):
    """One slot of the given length beside a slot of another; dead
    columns hold NaN."""
    cache, local = state(length * 7 + group, 2, jnp.float32, group)
    want, got, _ = both_routes(
        cache, local, [length, EXTENT - W - 1 - length], 3)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < F32


@pytest.mark.parametrize("group", [1, 4], ids=["G1", "G4"])
def test_mixed_slots_in_one_call_and_slots_with_nothing_live(group):
    """Five slots: free (parked at the extent), a sequence of no
    column yet, one column, mid-block extents, another parked one; each
    live slot is served as it is alone."""
    cache, local = state(2, 5, jnp.float32, group)
    pos0 = [EXTENT, 0, 1, 2 * BLK + 77, EXTENT]
    want, got, live = both_routes(cache, local, pos0, 0)
    assert np.isfinite(got).all()
    assert live.tolist() == [False, True, True, True, False]
    assert np.abs(got - want)[live].max() < F32
    # a slot with nothing live, at the dispatch's first step, attends to
    # its own key alone: the output is its own value
    own = np.repeat(np.asarray(local["v_cur"]), group, axis=1)
    for slot in (0, 1, 4):
        np.testing.assert_allclose(got[slot], own[slot], atol=F32)


def test_plan_lists_exactly_the_live_blocks():
    pos0 = np.asarray([EXTENT, 0, 1, 2 * BLK + 77, BLK, EXTENT])
    lo, hi = da.live_range(pos0, pos0, 0, EXTENT)
    steps, n_steps, bounds = da.plan_blocks(jnp.asarray(lo), jnp.asarray(hi),
                                            extent=EXTENT)
    steps = np.asarray(steps)[:, :int(n_steps)]
    # steps: 1 (nothing) + 1 (nothing) + 1 + 3 + 1 + 1 (nothing)
    assert int(n_steps) == 8
    assert steps[da._READS].tolist() == [0, 0, 1, 1, 1, 1, 1, 0]
    assert steps[da._SLOT].tolist() == [0, 1, 2, 3, 3, 3, 4, 5]
    assert steps[da._FIRST].tolist() == [1, 1, 1, 1, 0, 0, 1, 1]
    assert steps[da._LAST].tolist() == [1, 1, 1, 0, 0, 1, 1, 1]
    reads = steps[da._READS] == 1
    assert list(zip(steps[da._KSLOT][reads], steps[da._KBLK][reads])) == [
        (2, 0), (3, 0), (3, 1), (3, 2), (4, 0)]
    # a step that reads nothing points at the block fetched last (or,
    # ahead of the first, at that one): no fetch of its own
    assert list(zip(steps[da._KSLOT], steps[da._KBLK]))[:2] == [(2, 0)] * 2
    assert (steps[da._KSLOT][-1], steps[da._KBLK][-1]) == (4, 0)
    assert np.asarray(bounds).tolist() == [[0] * 6,
                                           [0, 0, 1, 2 * BLK + 77, BLK, 0]]
    # and the columns under them are what the host counts
    assert reads.sum() * BLK == sum(
        da.blocks_read(int(a), int(b), EXTENT) for a, b in zip(lo, hi))


@pytest.mark.parametrize("w", [0, 5])
def test_a_sliding_window_shorter_than_the_length_reads_its_blocks_only(w):
    """Window of a block and a half: a slot three blocks long reads the
    two blocks its window lies in; a slot shorter than the window reads
    all it has. The left edge moves with the step index."""
    window = BLK + BLK // 2
    cache, local = state(3, 3, jnp.float32, 4)
    pos0 = [3 * BLK - 7, BLK // 2, EXTENT]
    want, got, live = both_routes(cache, local, pos0, w, window=window)
    assert np.isfinite(got).all()
    assert np.abs(got - want)[live].max() < F32
    lo, hi = da.live_range(np.asarray(pos0), np.asarray(pos0) + w, window,
                           EXTENT)
    assert lo.tolist() == [3 * BLK - 7 + w + 1 - window, 0, 0]
    assert [da.blocks_read(int(a), int(b), EXTENT)
            for a, b in zip(lo, hi)] == [2 * BLK, BLK, 0]
    n_steps = da.plan_blocks(jnp.asarray(lo), jnp.asarray(hi),
                             extent=EXTENT)[1]
    assert int(n_steps) == 2 + 1 + 1


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "fp8"])
def test_bf16_queries_over_a_bf16_or_fp8_cache(kv_dtype):
    cache, local = state(4, 3, jnp.bfloat16, 4, kv_dtype=kv_dtype)
    assert cache["k"].dtype == kv_dtype
    want, got, live = both_routes(cache, local, [EXTENT, BLK + 2, 3 * BLK],
                                  7, poison=kv_dtype != jnp.float8_e4m3fn)
    assert np.isfinite(got).all()
    assert np.abs(got - want)[live].max() < BF16


def test_a_second_window_of_the_dispatch_sees_the_first_as_done_columns():
    """`n_windows = 2`: the completed window rides as `k_done`, before
    the current window on the dispatch's timeline; under a sliding
    window the query's position counts them."""
    cache, local = state(5, 2, jnp.float32, 4, n_done=W)
    for window in (0, BLK):
        want, got, _ = both_routes(cache, local, [2 * BLK + 3, 40], 2,
                                   window=window)
        assert np.abs(got - want).max() < F32
    # and the done columns were part of it
    local["v_done"] = local["v_done"] + 1.0
    _, moved, _ = both_routes(cache, local, [2 * BLK + 3, 40], 2, window=BLK)
    assert np.abs(moved - got).max() > 1e-2


def test_the_layer_is_read_by_pointer():
    """The same call against layer 0 and layer 1 reads other blocks."""
    cache, local = state(6, 2, jnp.float32, 4)
    outs = []
    for li in (0, 1):
        want, got, _ = both_routes(cache, local, [100, EXTENT - W - 1], 1,
                                   li=li)
        assert np.abs(got - want).max() < F32
        outs.append(got)
    assert np.abs(outs[0] - outs[1]).max() > 1e-2


def test_blocks_read_rounds_the_live_range_out_to_blocks():
    assert da.blocks_read(0, 0, EXTENT) == 0
    assert da.blocks_read(0, 1, EXTENT) == BLK
    assert da.blocks_read(0, BLK, EXTENT) == BLK
    assert da.blocks_read(0, BLK + 1, EXTENT) == 2 * BLK
    assert da.blocks_read(BLK - 1, BLK + 1, EXTENT) == 2 * BLK
    assert da.blocks_read(BLK, BLK + 1, EXTENT) == BLK
    # an extent the block does not divide takes the common divisor, and
    # one that leaves less than a lane tile of columns is not served
    assert da.block_size(4096) == da.BLOCK
    assert da.block_size(da.BLOCK + da.MIN_BLOCK) == da.MIN_BLOCK
    assert da.block_size(1000) == 8


def test_serves_follows_the_backend_and_the_extent(monkeypatch):
    assert not da.serves(4096)                   # the CPU's route
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert da.serves(4096) and da.serves(da.BLOCK + da.MIN_BLOCK)
    assert not da.serves(1000)


# ---------------------------------------------------------------------------
# the model step and the engine through both routes
# ---------------------------------------------------------------------------

CFG = decoder_config("tiny")


@pytest.mark.parametrize("sliding", [0, 160], ids=["full", "sliding"])
@pytest.mark.parametrize("n_done", [0, W], ids=["first-window", "second"])
def test_the_model_step_through_the_kernel_equals_the_xla_step(
        sliding, n_done):
    """`decode_step_windowed_live` over the whole cache against
    `decode_step_windowed` over it: logits and the step's new columns.
    Slot 0 is parked, the others lie in different blocks."""
    import dataclasses

    cfg = dataclasses.replace(CFG, sliding_window=sliding)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg,
                                 dtype=jnp.float32)
    rng = np.random.default_rng(7)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    slots = 4
    shape = (cfg.n_layers, slots, cfg.n_kv_heads)
    cache = {h: rand(*shape, EXTENT, cfg.head_dim) for h in "kv"}
    k_win, v_win = (rand(*shape, W, cfg.head_dim) for _ in range(2))
    done = {} if not n_done else dict(
        k_done=rand(*shape, n_done, cfg.head_dim),
        v_done=rand(*shape, n_done, cfg.head_dim))
    pos0 = jnp.asarray([EXTENT, 3, BLK + 5, 3 * BLK - 1], jnp.int32)
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)
    outs = [jax.jit(lambda c: step(params, tok, pos0, jnp.int32(3), cfg, c,
                                   k_win, v_win, **done))(cache)
            for step in (decoder.decode_step_windowed,
                         decoder.decode_step_windowed_live)]
    live = np.asarray(pos0) < EXTENT
    for want, got in zip(*outs):
        axis = 0 if want.ndim == 2 else 1
        want, got = (np.compress(live, np.asarray(a), axis=axis)
                     for a in (want, got))
        assert np.abs(got - want).max() < 1e-4


def test_state_tokens_read_is_what_the_blocks_of_every_step_cover(
        monkeypatch):
    """A dense engine on the kernel's route (forced here: the
    interpreter) records the columns under the blocks its decode
    dispatches read, summed over their steps; on the XLA route 0."""
    params = decoder.init_params(jax.random.PRNGKey(1), CFG,
                                 dtype=jnp.float32)
    prompts = [[3 + i % 40 for i in range(n)] for n in (5, BLK + 3)]

    def run():
        eng = GenerationEngine(CFG, params, num_slots=4, max_len=EXTENT,
                               prefill_buckets=(64, 2 * BLK),
                               dtype=jnp.float32, attn_impl="xla",
                               eos_id=-1, decode_window=4)
        comps = eng.generate(prompts, max_new_tokens=9)
        return [c.tokens for c in comps], [
            r for r in eng.telemetry.recorder.records()
            if r.kind == "decode"]

    toks_x, recs_x = run()
    assert [r.state_tokens_read for r in recs_x] == [0, 0]
    monkeypatch.setattr(da, "serves", lambda extent: True)
    toks_k, recs_k = run()
    assert toks_k == toks_x
    # two dispatches of 4 steps; both slots within their first and
    # second block throughout
    assert [r.state_tokens_read for r in recs_k] == [4 * 3 * BLK] * 2
