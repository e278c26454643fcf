"""``sparse_select.threshold``'s counting for an admission piece, with
the keys held on the chip.

An admission piece's index scores lie, as int32 sort keys, in one HBM
buffer ``[n, S, T]`` (``models/xing.py:select_piece``). In XLA every
round of the threshold's counting is a pass over that buffer's live
blocks and a loop of small fusions: ten reads of the live keys, and a
round costs a pass whatever it compares. Here a grid step copies the
keys of ONE tile of ``TQ`` queries, of the row's live blocks alone, into
VMEM once, and every round runs over that copy: a load, a compare and
an add a vreg, lane-wise partial counts, one cross-lane sum a round and
query. With a round at the price of its compares the cheapest round
settles ONE bit: 32 rounds of one compare, not 8 of 15 (read on the
chip, ``PERF.md`` §6, PR 47: two bits a round a fifth behind, four at
more than twice the time).

While a tile's rounds run, the next tile's keys are on their way into
the scratch's other half (the grid runs in order on the one core).

The kernel returns the integers ``sparse_select.threshold`` computes:
the k-th largest key ``thr`` of every query, and how many keys stand
above it and at it, from which ``sparse_select.tie_cut`` makes the
``cut`` (in XLA, over the buffer: a branch that is rare a query and
taken in most waves of 4,096, ``PERF.md`` §6, PR 47). A row's blocks past
its length hold ``NEVER`` alone, which no count of a live threshold
sees, so reading the row's own blocks gives the counts of the wave's.

On a non-TPU backend the kernel runs through the Pallas interpreter:
the tests' route. ``select_piece`` goes through it where the admission
kernel serves (``latent_prefill_attention.serves``); elsewhere
``sparse_select.threshold``'s XLA rounds serve, and are what the tests
hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: queries a grid step finds the threshold of (a shorter piece is one
#: tile): two halves of ``[TQ, T]`` int32 are the kernel's VMEM, 16.8
#: MB at 32,768 columns. Read on the chip (``PERF.md`` §6, PR 47): 128
#: is 4-8% ahead at twice the VMEM, 32 is 18-22% behind
TQ = 64

#: the widest slab of columns a count is kept for, lane by lane
LANES = 128

_TOP = np.int32(-2 ** 31)


def _threshold_kernel(blocks_ref, k_ref, buf_ref, thr_ref, above_ref,
                      at_ref, keys, sems, *, tq: int, blk: int):
    """One (row, query tile): its live keys into ``keys[slot]``, the
    rounds over them, the next tile's keys meanwhile into the other
    slot."""
    r, i = pl.program_id(0), pl.program_id(1)
    n_qt = pl.num_programs(1)
    step = r * n_qt + i
    slot = step % 2
    lanes = min(blk, LANES)

    def copies(r, i, slot, do):
        """``do`` (start or wait) the copy of every live block of tile
        (r, i): block j of the buffer's columns → ``keys[slot, j]``."""
        def one(j, carry):
            do(pltpu.make_async_copy(
                buf_ref.at[r, pl.ds(i * tq, tq), pl.ds(j * blk, blk)],
                keys.at[slot, j], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, blocks_ref[r], one, None)

    @pl.when(step == 0)
    def _first():
        copies(r, i, slot, lambda c: c.start())

    @pl.when(step + 1 < pl.num_programs(0) * n_qt)
    def _next():
        nxt = step + 1
        copies(nxt // n_qt, nxt % n_qt, 1 - slot, lambda c: c.start())

    copies(r, i, slot, lambda c: c.wait())
    live = blocks_ref[r]
    k = k_ref[0]                                           # [tq, 1]

    def count(test):
        """Per query ``[tq, 1]``, over the tile's live keys, how many
        ``test`` holds for."""
        def block(j, acc):
            for u in range(blk // lanes):
                acc = acc + test(
                    keys[slot, j, :, u * lanes:(u + 1) * lanes]
                ).astype(jnp.int32)
            return acc

        return jnp.sum(jax.lax.fori_loop(
            0, live, block, jnp.zeros((tq, lanes), jnp.int32)),
            axis=-1, keepdims=True)

    def bit(b, carry):
        # ``thr``: the bits found so far, of the keys' UNSIGNED form
        # (``key ^ _TOP``, as ``sparse_select.threshold`` counts);
        # ``n_ge``: the keys at or above it
        thr, n_ge = carry
        cand = thr | (jnp.int32(1) << (31 - b))
        # unsigned u >= cand  ⇔  signed key >= cand ^ _TOP
        edge = jnp.broadcast_to(cand ^ _TOP, (tq, lanes))
        n = count(lambda x: x >= edge)
        has = n >= k
        return jnp.where(has, cand, thr), jnp.where(has, n, n_ge)

    thr, n_ge = jax.lax.fori_loop(
        0, 32, bit, (jnp.zeros((tq, 1), jnp.int32),
                     jnp.full((tq, 1), live * blk, jnp.int32)))
    thr = thr ^ _TOP
    edge = jnp.broadcast_to(thr, (tq, lanes))
    at = count(lambda x: x == edge)
    thr_ref[0], above_ref[0], at_ref[0] = thr, n_ge - at, at


def piece_threshold(buf: jax.Array, k: jax.Array, blocks: jax.Array,
                    blk: int, *, interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The k-th largest key of every query of an admission piece: sort
    keys ``buf [n, S, T]`` int32 (``sparse_select.sort_keys``; left in
    HBM), ``k [n, S]`` int32 at least 1, ``blocks [n]`` int32 the
    blocks of ``blk`` columns that row r's counts run over (the rest of
    its columns hold ``NEVER``). → (``thr``, ``above``, ``at``) ``[n,
    S]`` int32: ``sparse_select.threshold``'s threshold, and the keys
    of those blocks above it and at it."""
    n, s, t = buf.shape
    tq = min(s, TQ)
    while s % tq:
        tq //= 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    col_spec = pl.BlockSpec((1, tq, 1), lambda r, i, *_: (r, i, 0))
    out = jax.ShapeDtypeStruct((n, s, 1), jnp.int32)
    thr, above, at = pl.pallas_call(
        functools.partial(_threshold_kernel, tq=tq, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # the rows' live blocks
            grid=(n, s // tq),
            in_specs=[col_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[col_spec] * 3,
            scratch_shapes=[pltpu.VMEM((2, t // blk, tq, blk), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[out] * 3,
        compiler_params=pltpu.CompilerParams(
            # in order: a step starts the next step's copies
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="select_threshold",
    )(blocks.astype(jnp.int32), k[..., None].astype(jnp.int32), buf)
    return thr[..., 0], above[..., 0], at[..., 0]
