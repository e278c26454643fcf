"""The exact top-k of a row of float32 scores as a THRESHOLD, not a
sort: which ``k`` of a query's cached positions a learned sparse
attention (``models/xing.py``, ``cfg.index_topk``) reads.

``jax.lax.top_k`` with k = 2,048 over 32,768 columns is a sort a row,
and an admission piece has 2,048 rows a layer. What attention needs is
not the order, only the set. The set is ``score > t`` plus the first
few columns of ``score == t``, where ``t`` is the k-th largest score:
and ``t`` is found by COUNTING, ``BITS`` bits of the score a round, in
the scores' order-preserving integer form (``sort_keys``): a round
counts, in one pass over the columns, how many keys stand at or above
each of the ``2 ** BITS - 1`` candidates that extend the bits found so
far, and keeps the largest candidate that still has ``k``. Eight passes
of compares and row sums, whatever ``k`` is. The result is the exact
top-k with ties going to the lower position, the set ``jax.lax.top_k``
gives (the tests hold it to that).

The counting is handed in (``count``): a decode step counts over one
array, an admission piece block by block over the blocks of its score
buffer that hold something live, so its rounds cost what is live and
not the cache's extent.

Which route counts where. In XLA a round is a pass over HBM, which is
why a round here settles four bits with fifteen compares. A decode
step's threshold (``models/xing.py:select_step``) counts here on every
backend, and so does an admission piece's off a TPU (the CPU's route,
and the oracle of the tests). On a TPU an admission piece's rounds run
in ``ops/select_threshold.py``, over a query tile's keys held in VMEM,
where a round costs its compares and settles one bit; that kernel
returns ``threshold``'s own ``thr``, and only ``tie_cut`` still walks
the buffer here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: the key of a column that cannot be chosen: below every score's key
#: (no float32 maps to it: ``sort_keys``)
NEVER = np.int32(-2 ** 31)

_TOP = np.uint32(1 << 31)

#: bits of the threshold a round of counting settles (a pass over the
#: columns a round, ``2 ** BITS - 1`` compares a column in it)
BITS = 4

#: passes over the columns that ``threshold`` makes where no k-th score
#: is tied: the rounds, then the keys above the threshold and at it
PASSES = 32 // BITS + 2


def sort_keys(scores: jax.Array, valid: jax.Array) -> jax.Array:
    """float32 scores → int32 keys in the same order (``a < b`` iff
    ``key(a) < key(b)``, ``-0.0`` and ``0.0`` one key), ``NEVER`` where
    not ``valid``. A non-negative float's bits are its key; a negative
    one's magnitude bits flipped, which lies above ``NEVER``."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    keys = jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)
    return jnp.where(valid, jnp.where(scores == 0, 0, keys), NEVER)


def _unsigned(keys: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(keys, jnp.uint32) ^ _TOP


def threshold(count, k: jax.Array, n_cols: int
              ) -> tuple[jax.Array, jax.Array]:
    """``count(test)`` → per row, how many columns ``test(keys, col)``
    holds for: its sum over the last axis (``keys`` int32 of
    ``sort_keys`` ``[..., cols]``, ``col`` their column numbers, blocks
    of the caller's choosing; ``test`` may put an axis of candidates
    before the columns; a column that must never be chosen carries
    ``NEVER``). ``k`` ``[...]`` int32, at least 1 and at most the row's
    valid columns. → (``thr``, ``cut``) ``[...]`` int32: the row's chosen columns are exactly
    ``chosen(keys, col, thr, cut)``, ``k`` of them: every key above the
    k-th largest, and of the columns AT it the lowest-numbered."""
    digits = jnp.arange(1, 1 << BITS, dtype=jnp.uint32)

    def digit(i, thr):
        shift = (32 - BITS) - BITS * i.astype(jnp.uint32)
        cands = thr[..., None] | (digits << shift)         # [..., 15]
        n = count(lambda keys, col: _unsigned(keys)[..., None, :]
                  >= cands[..., :, None])
        # the counts fall as the candidates rise: those that still have
        # k are the lowest ones, and their number is the digit
        found = jnp.sum(n >= k[..., None], axis=-1).astype(jnp.uint32)
        return thr | (found << shift)

    thr_u = jax.lax.fori_loop(0, 32 // BITS, digit,
                              jnp.zeros(k.shape, jnp.uint32))
    thr = jax.lax.bitcast_convert_type(thr_u ^ _TOP, jnp.int32)
    above = count(lambda keys, col: keys > thr[..., None])
    at = count(lambda keys, col: keys == thr[..., None])
    return thr, tie_cut(count, thr, k - above, at, n_cols)


def tie_cut(count, thr: jax.Array, need: jax.Array, at: jax.Array,
            n_cols: int) -> jax.Array:
    """``threshold``'s ``cut``: of the ``at`` columns at ``thr`` a row
    takes the ``need`` lowest-numbered, those below column ``cut``
    (``n_cols`` where it takes them all)."""

    def first_of_the_ties(_):
        # the largest c with fewer than ``need`` ties below column c:
        # the need-th tie stands AT c, so the cut is c + 1
        def bit(i, c):
            cand = c | (jnp.int32(1) << (n_bits - 1 - i))
            n = count(lambda keys, col: (keys == thr[..., None])
                      & (col < cand[..., None]))
            return jnp.where((n < need) & (cand < n_cols), cand, c)

        n_bits = max(int(n_cols - 1).bit_length(), 1)
        return jax.lax.fori_loop(0, n_bits, bit,
                                 jnp.zeros(thr.shape, jnp.int32)) + 1

    # (scores are sums of float32 products: two columns at the k-th
    # score is the rare case, and its rounds are skipped without it)
    return jax.lax.cond(jnp.any(at > need), first_of_the_ties,
                        lambda _: jnp.full(thr.shape, n_cols, jnp.int32),
                        None)


def chosen(keys: jax.Array, col: jax.Array, thr: jax.Array,
           cut: jax.Array) -> jax.Array:
    """The columns of ``threshold``'s set among ``keys`` (a block of a
    row's keys and its column numbers; ``thr``, ``cut`` broadcast
    against them)."""
    return (keys > thr) | ((keys == thr) & (col < cut))


def count_over(keys: jax.Array):
    """``threshold``'s ``count`` for keys held as one array
    ``[..., T]``, column = index."""
    col = jnp.arange(keys.shape[-1], dtype=jnp.int32)

    def count(test):
        return jnp.sum(test(keys, col), axis=-1, dtype=jnp.int32)

    return count
