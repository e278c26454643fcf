"""Pallas flash attention for TPU.

Online-softmax tiling (Flash-Attention-2 style) over the tiles the mask
leaves. From the rows' query offsets, begin bounds and lengths (and the
static ``causal`` / ``window``) the wrapper works out, for every (row,
query tile), the first and the last key tile any of its queries sees
(``tile_ranges``), and lays the LIVE (row, query tile, key tile)
triples end to end in one walk (``_walk``) that rides the
scalar-prefetch lane. The grid is ``(q_head, steps of the walk)`` with
the walk innermost and as long as the walk is (a traced bound): a key
tile no query of the tile sees is neither fetched nor stepped over. TPU
executes innermost grid steps in order on-core, so the running max /
denominator / accumulator live in VMEM scratch while a (row, query
tile)'s key tiles pass. A live tile that every query of the tile sees
WHOLE (under the diagonal, inside the window, at or past the begin
bound, under the length) runs dot → max → exp → sum → dot alone; only
an EDGE tile builds the mask. Supports causal masking, Mistral
sliding-window, GQA (kv head indexed as ``q_head // group``), padded kv
via per-row lengths, a query offset and a kv begin bound per row.

The arithmetic: ``q`` and ``k`` meet in the type they arrive in
(bfloat16 products are exact in float32), scores, max, exp, sums and
both accumulators float32, the probabilities float32 against the
values widened to float32.

Numerics oracle: ``ops.attention.attention_xla`` (``tests/
test_flash_tiles.py`` in float32 to 1e-5, tile class by tile class;
``tests/test_ops_attention.py``). On non-TPU backends the kernel runs
in interpret mode, so the same code path is exercised in CI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: what a step of the walk is, as bits of its ``kinds`` entry: the
#: first / the last of its (row, query tile), a tile seen whole, a tile
#: seen in part. A (row, query tile) that sees nothing is one step that
#: is first and last and neither: its output is 0
FIRST, LAST, WHOLE, EDGE = 1, 2, 4, 8

_NT = (((1,), (1,)), ((), ()))     # contract both operands' last axis


#: query and key rows a grid step holds where the caller names none and
#: the extents reach them, at heads of 128: a ``[1024, 1024]`` float32
#: score tile is 4 MB of VMEM. Read on the chip (``PERF.md`` §6, PR 44):
#: at every served shape the widest of 256 / 512 / 1024 either way is
#: the fastest (a grid step costs its ~0.35 us whatever it holds, a
#: query tile fetches its key tiles once), ``[512, 2048]`` is behind
Q_TILE = 1024
KV_TILE = 1024


def tiles(s_q: int, s_kv: int, d: int, block_q: int | None = None,
          block_kv: int | None = None) -> tuple[int, int]:
    """Query and key rows a grid step holds for queries ``[s_q, d]``
    against keys ``[s_kv, d]``: the caller's, or from the shapes (a
    wider head takes fewer key rows a step; an extent shorter than a
    tile is one tile)."""
    bq = block_q or Q_TILE
    bk = block_kv or KV_TILE * 128 // max(d, 128)
    return min(bq, s_q), min(bk, s_kv)


def tile_ranges(xp, q_offsets, kv_begins, kv_lengths, n_q: int, n_k: int,
                *, causal: bool, window: int, bq: int, bk: int):
    """Per (row, query tile) ``[B, n_q]`` each: the first and last key
    tile SOME query of the tile sees (``lo``, ``hi``; ``hi = lo - 1``
    where none sees any) and the first and last key tile EVERY query of
    it sees whole (``wlo``, ``whi``, within ``lo..hi``; ``whi < wlo``
    where there is none). Query i of row r stands at ``q_offsets[r] +
    i`` and sees column c when ``kv_begins[r] <= c < kv_lengths[r]``,
    ``c <= `` its position (``causal``) and ``c >`` its position less
    ``window`` (``window > 0``). ``xp`` is ``numpy`` on the host (the
    engine's step records) or ``jax.numpy`` under a trace: the same
    integer arithmetic."""
    first = q_offsets[:, None] + xp.arange(n_q)[None, :] * bq
    last = first + bq - 1                  # the tile's last query
    begin = xp.broadcast_to(xp.maximum(kv_begins, 0)[:, None], first.shape)
    end = xp.broadcast_to(xp.minimum(kv_lengths, n_k * bk)[:, None] - 1,
                          first.shape)
    # columns some query sees: from the first query's window to the
    # last query's own column
    lo_col = xp.maximum(begin, first - window + 1) if window > 0 else begin
    hi_col = xp.minimum(end, last) if causal else end
    lo = lo_col // bk
    hi = xp.where(lo_col <= hi_col, hi_col // bk, lo - 1)
    # columns every query sees: from the last query's window to the
    # first query's own column
    w_lo_col = xp.maximum(begin, last - window + 1) if window > 0 else begin
    w_hi_col = xp.maximum(xp.minimum(end, first) if causal else end, -1)
    wlo = xp.maximum((w_lo_col + bk - 1) // bk, lo)
    whi = xp.minimum((w_hi_col + 1) // bk - 1, hi)
    return lo, hi, wlo, whi


def tile_counts(q_offsets, kv_begins, kv_lengths, s_q: int, s_kv: int,
                d: int, *, causal: bool = True, window: int = 0,
                block_q: int | None = None, block_kv: int | None = None
                ) -> tuple[int, int, int]:
    """Key tiles a call of ``flash_attention`` with these (host) rows
    and extents walks for ONE query head, summed over rows and query
    tiles: ``(whole, edge, dead)``. Dead tiles are the rest of the
    ``n_q x n_k`` rectangle: what a walk of every tile would have
    fetched, and this one never touches."""
    bq, bk = tiles(s_q, s_kv, d, block_q, block_kv)
    n_q, n_k = -(-s_q // bq), -(-s_kv // bk)
    lo, hi, wlo, whi = tile_ranges(
        np, np.asarray(q_offsets, np.int64), np.asarray(kv_begins, np.int64),
        np.asarray(kv_lengths, np.int64), n_q, n_k, causal=causal,
        window=window, bq=bq, bk=bk)
    live = int((hi - lo + 1).sum())
    whole = int(np.maximum(whi - wlo + 1, 0).sum())
    return whole, live - whole, lo.size * n_k - live


def _walk(lo, hi, wlo, whi, n_k: int):
    """The live tiles of every (row, query tile) end to end, rows then
    query tiles then key tiles ascending: (steps, row ``[N]``, query
    tile ``[N]``, key tile ``[N]``, kinds ``[N]``) with ``N = B n_q
    n_k``, of which the first ``steps`` (traced) are the walk. A (row,
    query tile) with nothing to see takes one step, so every output
    tile is written."""
    b, n_q = lo.shape
    lo, hi, wlo, whi = (a.reshape(-1).astype(jnp.int32)
                        for a in (lo, hi, wlo, whi))
    pairs = jnp.arange(b * n_q, dtype=jnp.int32)
    count = jnp.maximum(hi - lo + 1, 1)
    # a running sum, a search and the lookups below as compares and
    # sums over [steps, pairs], which XLA fuses (a cumsum, a
    # searchsorted and gathers are a loop and a dozen small programs a
    # call site)
    ends = jnp.sum(jnp.where(pairs[None, :] <= pairs[:, None],
                             count[None, :], 0), axis=1)
    t = jnp.arange(b * n_q * n_k, dtype=jnp.int32)
    pair = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1),
                       b * n_q - 1).astype(jnp.int32)
    mine = pair[:, None] == pairs[None, :]
    lo, hi, wlo, whi, first, last = (
        jnp.sum(jnp.where(mine, a[None, :], 0), axis=1)
        for a in (lo, hi, wlo, whi, ends - count, ends - 1))
    kt = lo + t - first
    live = kt <= hi
    whole = live & (kt >= wlo) & (kt <= whi)
    kinds = (FIRST * (t == first) + LAST * (t == last)
             + jnp.where(whole, WHOLE, jnp.where(live, EDGE, 0)))
    return (ends[-1], pair // n_q, pair % n_q,
            jnp.clip(kt, 0, n_k - 1), kinds.astype(jnp.int32))


def _flash_kernel(
    row_ref,      # SMEM [N]            the walk: row,
    qt_ref,       # SMEM [N]            query tile,
    kt_ref,       # SMEM [N]            key tile,
    kind_ref,     # SMEM [N]            and kind of each step
    len_ref,      # SMEM [B]            valid kv length per batch row
    off_ref,      # SMEM [B]            query position offset per row
    begin_ref,    # SMEM [B]            first valid kv position per row
    q_ref,        # VMEM [1, 1, bq, d]
    k_ref,        # VMEM [1, 1, bk, d]
    v_ref,        # VMEM [1, 1, bk, d]
    o_ref,        # VMEM [1, 1, bq, d]
    m_scr,        # VMEM [bq, 1] f32    running row max
    l_scr,        # VMEM [bq, 1] f32    running denominator
    acc_scr,      # VMEM [bq, d] f32    running numerator
    *,
    causal: bool,
    window: int,
    bq: int,
    bk: int,
    scale: float,
):
    t = pl.program_id(1)
    kind = kind_ref[t]

    @pl.when(kind & FIRST != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def seen():
        """The tile's mask ``[bq, bk]``. q_offsets place the query
        block inside the kv timeline (a piece of a prompt: its fresh
        queries at the end of a growing kv run); kv_begins exclude a kv
        PREFIX (eva.piece_attention: the summary columns a row has not
        filled yet). Dynamic (SMEM) because both differ row by row and
        piece by piece."""
        r = row_ref[t]
        k_start = kt_ref[t] * bk
        col = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = (col < len_ref[r]) & (col >= begin_ref[r])
        if not causal and window <= 0:
            return jnp.broadcast_to(mask, (bq, bk))
        # column j of the tile less query i of the tile, against the
        # distance between the tile's first query and first column
        ahead = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) \
            - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        reach = qt_ref[t] * bq + off_ref[r] - k_start
        if causal:
            mask = mask & (ahead <= reach)
        if window > 0:
            mask = mask & (ahead > reach - window)
        return mask

    def fold(edge: bool):
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], _NT,
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        if edge:
            s = jnp.where(seen(), s, NEG_INF)
        m_prev = m_scr[:]                                   # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a query that has seen nothing yet stands at NEG_INF: the
        # subtrahend is pinned, and exp(NEG_INF - 0) is the 0 it wants
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(m_prev - m_safe)                     # [bq, 1]
        l_scr[:] = corr * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = corr * acc_scr[:] + jax.lax.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    pl.when(kind & WHOLE != 0)(lambda: fold(False))
    pl.when(kind & EDGE != 0)(lambda: fold(True))

    @pl.when(kind & LAST != 0)
    def _finalize():
        # Fully-masked rows (query in padding) produce l == 0 → emit 0.
        l = l_scr[:]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    kv_lengths: jax.Array | None = None,
    q_offsets: jax.Array | None = None,
    kv_begins: jax.Array | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """q: [B, Hq, Sq, D], k/v: [B, Hkv, Skv, D] → [B, Hq, Sq, D].

    ``Sq`` and ``Skv`` may differ; ``q_offsets`` [B] (dynamic) places
    each row's query block at an offset in the kv timeline — query i is
    position ``q_offsets[b] + i`` for causal/window masking. This is
    what lets a piece of a prompt run its fresh queries against the
    full run of already-written kv with flash tiling instead of a
    materialized [C, Skv] score tensor. ``kv_begins`` [B] (dynamic)
    masks a kv PREFIX per row (positions < begin never attend).
    ``models/eva.py:piece_attention`` is the caller of both: a row's
    timeline is its summaries, then its window; ``models/mixed.py:
    piece_attention`` of both and the window: a ring in timeline order.
    ``block_q`` / ``block_kv``: the tile, from the shapes (``tiles``)
    where the caller names none.
    """
    b, hq, s_q_in, d = q.shape
    hkv, s_kv_in = k.shape[1], k.shape[2]
    group = hq // hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bq, bk = tiles(s_q_in, s_kv_in, d, block_q, block_kv)
    pad_q = (-s_q_in) % bq
    pad_k = (-s_kv_in) % bk
    s_q, s_kv = s_q_in + pad_q, s_kv_in + pad_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if kv_lengths is None:
        kv_lengths = jnp.full((b,), s_kv_in, dtype=jnp.int32)
    kv_lengths = kv_lengths.astype(jnp.int32)
    if q_offsets is None:
        q_offsets = jnp.zeros((b,), dtype=jnp.int32)
    q_offsets = q_offsets.astype(jnp.int32)
    if kv_begins is None:
        kv_begins = jnp.zeros((b,), dtype=jnp.int32)
    kv_begins = kv_begins.astype(jnp.int32)

    n_q, n_k = s_q // bq, s_kv // bk
    steps, *walk = _walk(
        *tile_ranges(jnp, q_offsets, kv_begins, kv_lengths, n_q, n_k,
                     causal=causal, window=window, bq=bq, bk=bk), n_k)

    def at_q(hi, t, row, qt, *_):
        return row[t], hi, qt[t], 0

    def at_kv(hi, t, row, qt, kt, *_):
        return row[t], hi // group, kt[t], 0

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, window=window, bq=bq, bk=bk,
            scale=d ** -0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the walk and the rows' lengths, offsets and begin bounds
            num_scalar_prefetch=7,
            grid=(hq, steps),
            in_specs=[pl.BlockSpec((1, 1, bq, d), at_q),
                      pl.BlockSpec((1, 1, bk, d), at_kv),
                      pl.BlockSpec((1, 1, bk, d), at_kv)],
            out_specs=pl.BlockSpec((1, 1, bq, d), at_q),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, s_q, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(*walk, kv_lengths, q_offsets, kv_begins, q, k, v)
    return out[:, :, :s_q_in, :]
