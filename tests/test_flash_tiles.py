# The flash kernel's tile classes (ops/flash_attention.py), fast: tiny
# shapes through the Pallas interpreter in float32, against a masked
# softmax, and its live-range arithmetic against a count over the mask.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from copilot_for_consensus_tpu.models import decoder_config, mixed
from copilot_for_consensus_tpu.ops import flash_attention as fa
from copilot_for_consensus_tpu.ops.attention import attention_xla

ATOL = 1e-5


def qkv(b, hq, hkv, s, t, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, hq, s, d), jnp.float32),
            jax.random.normal(keys[1], (b, hkv, t, d), jnp.float32),
            jax.random.normal(keys[2], (b, hkv, t, d), jnp.float32))


def seen(s, t, off, begin, kv_len, causal, window):
    """The mask ``[B, s, t]`` by its definition, in numpy."""
    pos = np.asarray(off)[:, None, None] + np.arange(s)[None, :, None]
    col = np.arange(t)[None, None, :]
    mask = (col >= np.asarray(begin)[:, None, None]) \
        & (col < np.asarray(kv_len)[:, None, None])
    if causal:
        mask = mask & (col <= pos)
    if window > 0:
        mask = mask & (col > pos - window)
    return np.broadcast_to(mask, (len(off), s, t))


def masked_softmax(q, k, v, mask):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    logits = jnp.where(jnp.asarray(mask)[:, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def brute_counts(mask, bq, bk):
    """(whole, edge, dead) tiles of ``mask [B, s, t]`` cut into
    ``[bq, bk]`` tiles, queries past ``s`` standing where they would
    and columns past ``t`` seen by none: by looking at every tile."""
    b, s, t = mask.shape
    tiles = mask.reshape(b, s // bq, bq, t // bk, bk)
    whole = tiles.all(axis=(2, 4))
    live = tiles.any(axis=(2, 4))
    return int(whole.sum()), int((live & ~whole).sum()), int((~live).sum())


# -- (a) the kernel against the oracle, tile class by tile class ------------

# name: (b, hq, hkv, s, t, causal, window, off, begin, kv_len, tile)
CASES = {
    "causal": (2, 4, 2, 64, 64, True, 0, None, None, None, (16, 16)),
    "window": (2, 4, 2, 64, 64, True, 24, None, None, None, (16, 16)),
    "window_not_causal": (1, 2, 2, 48, 64, False, 20, None, None, None,
                          (16, 16)),
    # the ring's call: queries at the end of the timeline, a begin
    # bound past the first tile, the window's edge inside a tile
    "ring": (2, 4, 1, 32, 96, True, 40, [64, 64], [37, 0], [96, 83],
             (16, 32)),
    # a full layer's call: rows at unequal offsets in a long extent
    "offsets": (2, 4, 2, 32, 128, True, 0, [0, 83], [0, 0], [32, 115],
                (16, 32)),
    # one live tile a query tile
    "one_tile": (1, 2, 2, 16, 128, True, 0, [40], [32], [56], (16, 32)),
    # every tile live, none whole but the middle ones
    "all_tiles": (1, 2, 1, 16, 64, False, 0, None, [3], [61], (16, 16)),
    # nothing live: a row whose length is 0, a begin bound past the end
    "none": (2, 2, 2, 32, 64, True, 0, [0, 8], [0, 64], [0, 64], (16, 16)),
    "gqa4": (1, 8, 2, 32, 32, True, 0, None, None, None, (16, 16)),
    "gqa16": (1, 16, 1, 32, 64, True, 12, [32], [0], [64], (16, 32)),
    "not_tile_multiples": (2, 4, 2, 50, 75, True, 0, [25, 25], [0, 7],
                           [75, 60], (32, 32)),
    "tiles_from_shapes": (2, 4, 2, 40, 72, True, 30, [32, 30], [5, 0],
                          [72, 70], None),
    "one_tile_from_shapes": (1, 2, 1, 24, 24, True, 0, None, None, None,
                             None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_masked_softmax(case):
    b, hq, hkv, s, t, causal, window, off, begin, kv_len, tile = CASES[case]
    q, k, v = qkv(b, hq, hkv, s, t, seed=len(case))
    args = {name: None if a is None else jnp.asarray(a, jnp.int32)
            for name, a in (("q_offsets", off), ("kv_begins", begin),
                            ("kv_lengths", kv_len))}
    bq, bk = tile or (None, None)
    got = fa.flash_attention(q, k, v, causal=causal, window=window,
                             block_q=bq, block_kv=bk, interpret=True,
                             **args)
    mask = seen(s, t, off or [0] * b, begin or [0] * b, kv_len or [t] * b,
                causal, window)
    want = masked_softmax(q, k, v, mask)
    assert got.shape == want.shape
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < ATOL
    # a query that sees nothing comes out 0
    blind = ~mask.any(axis=-1)
    assert not np.asarray(got)[np.broadcast_to(
        blind[:, None], got.shape[:3])].any()


def test_the_oracle_here_is_attention_xla():
    q, k, v = qkv(2, 4, 2, 48, 48)
    lens = jnp.asarray([48, 29])
    mask = seen(48, 48, [0, 0], [0, 0], lens, True, 20)
    a = masked_softmax(q, k, v, mask)
    b = attention_xla(q, k, v, causal=True, window=20, kv_lengths=lens)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < ATOL


# -- (b) the live-range arithmetic against a count over the mask ------------


@pytest.mark.parametrize("case", list(CASES))
def test_tile_counts_are_a_count_over_the_mask(case):
    b, _hq, _hkv, s, t, causal, window, off, begin, kv_len, tile = \
        CASES[case]
    off, begin, kv_len = off or [0] * b, begin or [0] * b, kv_len or [t] * b
    bq, bk = fa.tiles(s, t, 16, *(tile or (None, None)))
    s_pad, t_pad = -(-s // bq) * bq, -(-t // bk) * bk
    mask = seen(s_pad, t_pad, off, begin, kv_len, causal, window)
    got = fa.tile_counts(off, begin, kv_len, s, t, 16, causal=causal,
                         window=window, block_q=bq, block_kv=bk)
    assert got == brute_counts(mask, bq, bk)
    assert sum(got) == b * (s_pad // bq) * (t_pad // bk)


def test_the_walk_is_the_live_tiles_in_order():
    """Every live tile once, a (row, query tile)'s in a run that begins
    FIRST and ends LAST; a blind one is a step of its own."""
    off, begin, kv_len = [0, 40, 0], [0, 9, 0], [32, 72, 0]
    bq, bk, n_q, n_k = 16, 16, 2, 6
    mask = seen(32, 96, off, begin, kv_len, True, 24)
    ranges = fa.tile_ranges(
        jnp, *(jnp.asarray(a, jnp.int32) for a in (off, begin, kv_len)),
        n_q, n_k, causal=True, window=24, bq=bq, bk=bk)
    steps, row, qt, kt, kinds = (np.asarray(a)
                                 for a in fa._walk(*ranges, n_k))
    tiles = mask.reshape(3, n_q, bq, n_k, bk)
    want = []
    for r in range(3):
        for i in range(n_q):
            live = [j for j in range(n_k) if tiles[r, i, :, j].any()]
            want += [(r, i, j, fa.WHOLE if tiles[r, i, :, j].all()
                      else fa.EDGE) for j in live] or [(r, i, None, 0)]
    assert steps == len(want) <= row.size == 3 * n_q * n_k
    for t, (r, i, j, kind) in enumerate(want):
        assert (row[t], qt[t]) == (r, i)
        assert j is None or kt[t] == j
        assert kinds[t] & (fa.WHOLE | fa.EDGE) == kind
        assert bool(kinds[t] & fa.FIRST) == (t == 0 or want[t - 1][:2]
                                             != (r, i))
        assert bool(kinds[t] & fa.LAST) == (t == len(want) - 1
                                            or want[t + 1][:2] != (r, i))


# -- (c) a wave's counts on the step record's arithmetic --------------------

CFG = decoder_config("tiny-mixed")      # window 16, period of 4 layers


@pytest.mark.parametrize("name,pos0,lens", [
    ("first_piece", [0, 0], [32, 9]),
    ("mid", [64, 32], [32, 32]),
    ("last_piece", [224, 192], [32, 5]),
    ("ring_before_it_wraps", [0, 32], [32, 32]),
    ("ring_after_it_wraps", [96, 160], [32, 20]),
])
def test_a_waves_tile_counts_are_a_count_over_its_masks(
        name, pos0, lens, monkeypatch):
    """``mixed.piece_tiles`` (what ``GenerationEngine._admit_pieces``
    writes into the step record) for pieces of 32 in an extent of 256
    beside a ring of 48, tiles of 16 x 16: layer kind by layer kind the
    count over the mask ``piece_attention`` describes, times the layers
    of the kind and the query heads."""
    monkeypatch.setattr(fa, "Q_TILE", 16)
    monkeypatch.setattr(fa, "KV_TILE", 16)
    s, max_len, ring = 32, 256, 48
    pos0, lens = np.asarray(pos0), np.asarray(lens)
    want = np.zeros(3, np.int64)
    kinds = mixed.layer_kinds(CFG)
    for kind, t in (("full", max_len), ("window", ring)):
        off, begin, kv_len, window = mixed.piece_timeline(
            kind, pos0, lens, s, t, CFG)
        mask = seen(s, t, off, begin, kv_len, True, window)
        # the mask by the positions the columns hold, for the ring
        if kind == "window":
            held = pos0[:, None] + s - t + np.arange(t)[None, :]
            q_at = pos0[:, None] + np.arange(s)[None, :]
            by_position = ((held[:, None, :] >= 0)
                           & (held[:, None, :] <= q_at[:, :, None])
                           & (held[:, None, :] > q_at[:, :, None]
                              - CFG.sliding_window)
                           & (held[:, None, :] < (pos0 + lens)[:, None, None]))
            assert (mask == by_position).all()
        want += kinds.count(kind) * CFG.n_heads * np.asarray(
            brute_counts(mask, 16, 16))
    got = mixed.piece_tiles(pos0, lens, s, max_len, ring, CFG)
    assert got == tuple(int(x) for x in want)
    assert got[0] > 0 or name == "first_piece"
    assert got[2] > 0
