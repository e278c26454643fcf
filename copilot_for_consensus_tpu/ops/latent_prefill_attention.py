"""One round of an admission piece's expanded latent attention, folded
into the running softmax without its scores leaving the chip.

``models/xing.py:piece_attention`` walks the live part of a slot's
latent cache a round of ``KV_BLOCK`` columns at a time: it expands the
round's latents into every head's keys and values and folds them into a
running ``(acc, max, sum)``. In XLA the fold is ``dot → mask → max → exp
→ sum → dot`` as separate fusions over a float32 score block ``[n, H, S,
columns]`` (half a gigabyte to a gigabyte at the served shapes), which
crosses HBM some five times a round. The kernel here does the fold a
tile of ``[TQ, TK]`` scores at a time in VMEM: bfloat16 operands into
the MXU with float32 accumulation, float32 scores, max, exp and sums,
the probabilities rounded to the operands' type before they meet the
values: the XLA rounds' arithmetic in another order of sums. Only the
carry crosses HBM, updated in place.

What it takes is all in its operands: keys and values of UNEQUAL width
(192 / 128 and 256 / 256 are served), any number of heads, and one of
two masks. Without a selection the causal-and-length mask is made in
the kernel from the last column each query sees; a tile that every
query of it sees whole skips the compare, and a tile that none sees is
neither computed nor fetched (the index maps hold the operands of the
step before). With a selection (``keep``, shared by all heads) the mask
is one more operand, fetched once a (row, query tile); a query of which
a round holds nothing keeps its max at ``-inf`` and its sums untouched.

The carry between rounds is ``acc [n, H, S, dv]`` and the row maxima
and sums as ROWS ``[n, H, 8, S]`` (the first two of eight used: a
sublane tile): held as columns ``[.., S, 1]`` they would be padded to a
lane tile in HBM, 128 times their size and more than ``acc`` itself.
The kernel turns them into columns when a tile's first column tile
begins and back when its last ends.

On a non-TPU backend the kernel runs through the Pallas interpreter:
the tests' route. ``piece_attention`` goes through it only on a TPU
(``serves``); elsewhere its XLA rounds serve, and are what the tests
hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: queries a grid step scores (a shorter piece is one tile)
TQ = 1024

#: columns a grid step scores them against; a round narrower than this
#: is one tile. A ``[TQ, TK]`` float32 score tile is 4 MB of VMEM. Read
#: on the chip (``PERF.md`` §6, PR 38): ``[1024, 1024]`` is 4-7% ahead
#: of ``[512, 1024]`` at both served shapes, a column tile of 512
#: behind by a third (Xing) and a tenth (GLM)
TK = 1024

#: the narrowest round the kernel is served with (a lane tile of
#: columns): ``serves``
MIN_BLOCK = 128

#: rows of the carried maxima and sums ``[n, H, STAT_ROWS, S]``: the
#: maxima, the sums, and what fills a sublane tile
STAT_ROWS = 8

_NT = (((1,), (1,)), ((), ()))     # contract both operands' last axis


def serves(block: int) -> bool:
    """Does the fold of an admission round of ``block`` columns go
    through this kernel? On a TPU, when the round is whole lane tiles
    (``latent_attention.serves``'s rule); elsewhere the XLA rounds
    serve."""
    return jax.default_backend() == "tpu" and block % MIN_BLOCK == 0


def _tile(extent: int, most: int) -> int:
    """The widest tile of at most ``most`` that divides ``extent``."""
    tile = min(extent, most)
    while extent % tile:
        tile //= 2
    return tile


def plan_queries(q_pos: jax.Array, kv_len: jax.Array
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """What the kernel needs of a piece's positions, once for all its
    rounds: ``q_pos [n, S]`` and ``kv_len [n]`` → (the last column each
    query sees ``[n, S, 1]``: up to its own, below its row's length;
    ``-1`` where it sees none; the least and the largest of them a
    query tile ``[n * tiles]`` each, which tell a tile seen whole and a
    tile not seen at all without looking at it)."""
    n, s = q_pos.shape
    last = jnp.minimum(q_pos, kv_len[:, None] - 1).astype(jnp.int32)
    tiles = last.reshape(n, s // _tile(s, TQ), -1)
    return last[..., None], jnp.min(tiles, axis=-1).reshape(-1), \
        jnp.max(tiles, axis=-1).reshape(-1)


def empty_carry(n: int, h: int, s: int, dv: int
                ) -> tuple[jax.Array, jax.Array]:
    """The carry before the first round: nothing summed, every maximum
    at ``-inf``. (From an iota, which the compiler computes where it is
    used: zeros with a row set came out as a literal of the whole
    array, 4-8 MB a call site in every admission program's binary.)"""
    row = jax.lax.broadcasted_iota(jnp.int32, (n, h, STAT_ROWS, s), 2)
    return jnp.zeros((n, h, s, dv), jnp.float32), \
        jnp.where(row == 0, -jnp.inf, 0.0).astype(jnp.float32)


def _fold_kernel(col0_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, mask_ref,
                 acc_in, st_in, acc_out, st_out, st_ref, *, tk: int,
                 kept: bool):
    """One (row, query tile, head, column tile): fold the tile's
    scores into the (row, head, query tile)'s running sums, which lie
    in ``acc_out`` and ``st_ref`` while its column tiles pass."""
    r, i, kv = pl.program_id(0), pl.program_id(1), pl.program_id(3)
    tile = r * pl.num_programs(1) + i
    c0 = col0_ref[0] + kv * tk                # the tile's first column

    @pl.when(kv == 0)
    def _load():
        acc_out[...] = acc_in[...]
        st_ref[...] = st_in[0, 0].T           # [tq, STAT_ROWS]

    def fold(seen):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        if seen is not None:
            s = jnp.where(seen(), s, -jnp.inf)
        m_prev, l_prev = st_ref[:, 0:1], st_ref[:, 1:2]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a query that has seen nothing yet stands at -inf: the
        # subtrahend is pinned, and exp(-inf - 0) is the 0 it wants
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        st_ref[:, 1:2] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        st_ref[:, 0:1] = m_new
        acc_out[0, 0] = acc_out[0, 0] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    live = c0 <= hi_ref[tile]                 # some query sees a column
    if kept:
        # (the selection's columns are among those a query sees)
        pl.when(live)(lambda: fold(lambda: mask_ref[0, kv] != 0))
    else:
        whole = c0 + tk - 1 <= lo_ref[tile]   # every query sees them all
        col = c0 + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        pl.when(live & whole)(lambda: fold(None))
        pl.when(live & ~whole)(lambda: fold(lambda: col <= mask_ref[0]))

    @pl.when(kv == pl.num_programs(3) - 1)
    def _store():
        st_out[0, 0] = st_ref[...].T


def fold_round(q: jax.Array, k: jax.Array, v: jax.Array,
               carry: tuple[jax.Array, jax.Array], col0: jax.Array,
               plan: tuple[jax.Array, jax.Array, jax.Array],
               keep: jax.Array | None = None, *,
               interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Fold one round into the carry: queries ``q [n, H, S, dk]``
    (scaled), the round's keys ``k [n, H, T, dk]`` and values ``v [n, H,
    T, dv]``, whose first column is column ``col0`` (traced) of the
    rows' caches; ``plan`` from ``plan_queries``. ``keep [n, S, T]``
    booleans (a selection): of the round's columns each query reads
    those it marks, which lie among the columns it sees; without it
    every column it sees. → the carry ``(acc [n, H, S, dv], maxima and
    sums [n, H, STAT_ROWS, S])`` float32 after the round, written where
    the one handed in lay."""
    n, h, s, dk = q.shape
    t, dv = k.shape[2], v.shape[3]
    tq, tk = _tile(s, TQ), _tile(t, TK)
    n_qt, n_kv = s // tq, t // tk
    last, lo, hi = plan
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kept = keep is not None
    if kept:
        # a column tile's marks as one leading index: [n, tiles, S, tk]
        mask = keep.astype(jnp.int32).reshape(n, s, n_kv, tk
                                              ).transpose(0, 2, 1, 3)
        mask_spec = pl.BlockSpec((1, n_kv, tq, tk),
                                 lambda r, i, hd, kv, *_: (r, 0, i, 0))
    else:
        mask = last
        mask_spec = pl.BlockSpec((1, tq, 1),
                                 lambda r, i, hd, kv, *_: (r, i, 0))

    def seen_tiles(r, i, col0, hi):
        """Column tiles of the round that some query of the tile sees:
        the first so many."""
        reach = hi[r * n_qt + i] - col0[0]
        return jnp.where(reach < 0, 0,
                         jnp.minimum(jax.lax.div(reach, tk) + 1, n_kv))

    # a step with nothing to see fetches nothing: its operands' blocks
    # are the step's before
    def at_q(r, i, hd, kv, col0, lo, hi):
        return r, jnp.where(seen_tiles(r, i, col0, hi) > 0, hd, 0), i, 0

    def at_kv(r, i, hd, kv, col0, lo, hi):
        seen = seen_tiles(r, i, col0, hi)
        return r, jnp.where(seen > 0, hd, 0), \
            jnp.minimum(kv, jnp.maximum(seen - 1, 0)), 0

    def at_acc(r, i, hd, kv, *_):
        return r, hd, i, 0

    def at_stats(r, i, hd, kv, *_):
        return r, hd, 0, i

    acc_spec = pl.BlockSpec((1, 1, tq, dv), at_acc)
    st_spec = pl.BlockSpec((1, 1, STAT_ROWS, tq), at_stats)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # first column, tiles' least, largest
        grid=(n, n_qt, h, n_kv),
        in_specs=[pl.BlockSpec((1, 1, tq, dk), at_q),
                  pl.BlockSpec((1, 1, tk, dk), at_kv),
                  pl.BlockSpec((1, 1, tk, dv), at_kv),
                  mask_spec, acc_spec, st_spec],
        out_specs=[acc_spec, st_spec],
        scratch_shapes=[pltpu.VMEM((tq, STAT_ROWS), jnp.float32)],
    )
    acc, stats = carry
    return tuple(pl.pallas_call(
        functools.partial(_fold_kernel, tk=tk, kept=kept),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(acc.shape, jnp.float32),
                   jax.ShapeDtypeStruct(stats.shape, jnp.float32)],
        # operands count from the scalars on: the carry is 7 and 8
        input_output_aliases={7: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="mla_prefill_attention",
    )(jnp.reshape(col0, (1,)).astype(jnp.int32), lo, hi, q, k, v, mask,
      acc, stats))


def finish(carry: tuple[jax.Array, jax.Array], dtype) -> jax.Array:
    """The carry after the last round → ``[n, S, H dv]`` in ``dtype``;
    a query that saw nothing (a sum of 0) comes out 0."""
    acc, stats = carry
    n, h, s, dv = acc.shape
    l = stats[:, :, 1, :, None]
    o = acc / jnp.where(l > 0, l, 1.0)
    return o.transpose(0, 2, 1, 3).reshape(n, s, h * dv).astype(dtype)
