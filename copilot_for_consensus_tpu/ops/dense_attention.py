"""Decode attention over the live part of the dense stacked cache.

A slot of a contiguous-cache engine holds its keys and values in
``k``/``v`` ``[L, slots, Hkv, max_len, Dh]`` (``models/decoder.py:
init_cache``), live in ``[lo, hi)``: ``hi`` the position its dispatch
began at, ``lo`` the left edge of a sliding window (0 without one).
One decoded token's grouped queries score those columns. The kernel
here reads the two halves WHOLE and in place — the traced layer index
and a plan of which block to read at which grid step ride the
scalar-prefetch lane, as in ``ops/eva_attention.py``, whose shape this
module follows — and fetches only the blocks that hold a live column:
``BLOCK`` columns of all ``Hkv`` heads a grid step. It hands back flash
partials ``(acc, m, l)`` in the convention of
``ops.attention.combine_partials``, which folds them with the
dispatch's own columns (``ops.attention.decode_window_partial``) under
the one softmax.

The plan (``plan_blocks``) is a list of steps, a slot after the other:
the slot's live blocks ``[lo // BLOCK, ceil(hi / BLOCK))``; a slot with
nothing live (free, or parked at ``max_len``) takes one step that reads
nothing, so that its partial is written (``m = -inf, l = 0``). A step
that reads nothing keeps the block index of the step before it, which
the pipeline does not fetch again, so each live step's fetch of the
NEXT live block runs under its own arithmetic whatever slot that block
belongs to. On a TPU the grid is as long as the plan (a dynamic bound).
The interpreter takes no dynamic bound: there the grid is the worst
case, every slot full, and the steps past the plan's end hold every
index: neither a copy nor arithmetic.

On a non-TPU backend the kernel runs through the Pallas interpreter:
the tests' route. The engine serves through it only on a TPU
(``serves``, ``GenerationEngine._reads_live_blocks``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from copilot_for_consensus_tpu.obs.profile import scope

#: columns of a slot that one grid step reads for all kv heads; a live
#: extent is rounded out to these. At Mistral's widths (8 kv heads of
#: 128, bf16) a column is 2 KB a half, a quarter of EvaByte's, so the
#: block that keeps a step bound by HBM and not by the grid is wider
#: than ``eva_attention.WIN_BLOCK``; chosen on a v5e among 128, 256 and
#: 512 by what reads the served mix of lengths fastest (PERF.md section
#: 6, PR 32). A constant of the kernel: an engine or a config does not
#: set it (an extent it does not divide takes their greatest common
#: divisor).
BLOCK = 256

#: the narrowest block the kernel is served with: below a lane tile of
#: columns a step moves too little to be worth its launch, and Mosaic
#: wants whole tiles. An extent with a smaller common divisor keeps the
#: XLA route (``serves``).
MIN_BLOCK = 128

# rows of the plan's steps
_READS, _SLOT, _FIRST, _LAST, _KSLOT, _KBLK = range(6)


def block_size(extent: int) -> int:
    """Columns a grid step reads of a cache of ``extent`` columns."""
    return math.gcd(extent, BLOCK)


def serves(extent: int) -> bool:
    """Does decode attention over a cache of ``extent`` columns a slot,
    held on one device, go through this kernel? On a TPU, as EvaByte's
    route is chosen (``models/eva.py:_reads_live_blocks``), when the
    extent leaves a block of ``MIN_BLOCK`` columns or more; elsewhere
    the XLA route over a cut prefix serves (and is what the tests hold
    the kernel to)."""
    return jax.default_backend() == "tpu" \
        and block_size(extent) >= MIN_BLOCK


def live_range(positions0, q_pos, window: int, extent: int):
    """``[lo, hi)`` ``[B]`` of cache columns that a query at ``q_pos``
    sees in a slot whose dispatch began at ``positions0``: below the
    dispatch's start and, under a sliding ``window``, within it
    (``ops.attention._piece_mask``'s rule). A slot at or past the
    extent (free, parked) has nothing live. Works on numpy and on
    traced arrays alike."""
    hi = positions0 * (positions0 < extent)
    if not 0 < window < extent:          # no query is a window from 0
        return hi * 0, hi
    return (q_pos + 1 - window).clip(0, None).clip(None, hi), hi


def blocks_read(lo: int, hi: int, extent: int) -> int:
    """Columns the kernel's blocks cover for a slot live in
    ``[lo, hi)`` (host arithmetic: the flight recorder's
    ``state_tokens_read``)."""
    blk = block_size(extent)
    return (-(-hi // blk) - lo // blk) * blk if hi > lo else 0


@scope("attn")
def plan_blocks(lo: jax.Array, hi: jax.Array, *, extent: int
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The kernel's plan for slots live in ``[lo, hi)`` ``[B]`` of
    ``extent`` columns: (steps int32 ``[6, G]``, ``G = B * extent /
    block``; how many of the G are steps of the plan; the two bounds
    ``[2, B]``). A step, row by row: does it read a block, its slot, is
    it the slot's first step, its last, and the (slot, block) the
    cache input points at. It does not depend on the layer: made once a
    token."""
    blk = block_size(extent)
    b, nb = lo.shape[0], extent // blk
    g = jnp.arange(b * nb, dtype=jnp.int32)
    first = lo // blk
    n_live = jnp.where(hi > lo, -(-hi // blk) - first, 0)
    count = jnp.maximum(n_live, 1)
    end = jnp.cumsum(count)
    slot = jnp.minimum(jnp.sum(g[:, None] >= end[None, :], axis=1), b - 1)
    live = g < end[-1]
    r = g - (end - count)[slot]
    reads = live & (r < n_live[slot])
    # (slot, block) at the latest step that read; before the first such
    # step, that step's (fetched ahead of its time)
    at = jax.lax.cummax(jnp.where(reads, g, -1))
    at = jnp.where(at >= 0, at, jnp.argmax(reads))
    steps = jnp.stack([
        reads, slot, live & (r == 0), live & (r == count[slot] - 1),
        slot[at], jnp.clip((first[slot] + r)[at], 0, nb - 1),
    ]).astype(jnp.int32)
    return steps, end[-1].astype(jnp.int32), \
        jnp.stack([lo, hi]).astype(jnp.int32)


def _live_kernel(li_ref, steps_ref, range_ref, q_ref, k_ref, v_ref,
                 acc_out, m_out, l_out, m_ref, l_ref, acc_ref, *,
                 block: int, scale: float):
    """One step of the plan: fold one block of one slot, all kv heads,
    into the slot's running (max, sum, acc)."""
    del li_ref
    g = pl.program_id(0)
    slot = steps_ref[_SLOT, g]

    @pl.when(steps_ref[_FIRST, g] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(steps_ref[_READS, g] == 1)
    def _fold():
        # columns ``col0 + i`` of the block are live in ``[lo, hi)``; a
        # dead one may hold anything, NaN included
        col0 = steps_ref[_KBLK, g] * block
        lo, hi = range_ref[0, slot], range_ref[1, slot]
        q = q_ref[0]                                     # [Hkv, G, Dh]
        dt = q.dtype
        k, v = k_ref[0, 0].astype(dt), v_ref[0, 0].astype(dt)
        _, t, dh = k.shape
        s = jnp.einsum("hgd,htd->hgt", q, k,
                       preferred_element_type=jnp.float32) * scale
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
        s = jnp.where((col >= lo) & (col < hi), s, -jnp.inf)
        row = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, t, dh), 1)
        v = jnp.where((row >= lo) & (row < hi), v, 0)
        m_prev = m_ref[...]                              # [Hkv, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with nothing live yet keeps m = -inf: the subtrahend is
        # pinned finite there (paged_attention._paged_partial_kernel)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - m_safe), 0.0)
        p = jnp.exp(s - m_safe)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hgt,htd->hgd", p.astype(dt), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(steps_ref[_LAST, g] == 1)
    def _emit():
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def live_partial(qg: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 li: jax.Array, plan: tuple, *,
                 interpret: bool | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials of one token's grouped queries ``qg``
    ``[B, Hkv, G, Dh]`` over the live columns of layer ``li`` (traced)
    of the stacked cache halves ``[L, B, Hkv, extent, Dh]``, read in
    place by ``plan`` (``plan_blocks`` for the same extent).

    Returns f32 (acc ``[B, Hkv, G, Dh]``, m ``[B, Hkv, G, 1]``,
    l ``[B, Hkv, G, 1]``); a slot with nothing live carries
    ``m = -inf``, ``l = 0``. Scores and sums accumulate in float32,
    the probabilities are rounded to the queries' type before they meet
    the values (as the XLA route rounds them); the cache is read in the
    type it is stored in."""
    b, hkv, grp, dh = qg.shape
    block = block_size(k_cache.shape[3])
    steps, n_steps, bounds = plan
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    li = jnp.reshape(li, (1,)).astype(jnp.int32)

    def at_slot(g, li, steps, bounds):
        return steps[_SLOT, g], 0, 0, 0

    def at_block(g, li, steps, bounds):
        return li[0], steps[_KSLOT, g], 0, steps[_KBLK, g], 0

    kv_spec = pl.BlockSpec((1, 1, hkv, block, dh), at_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # layer index, steps, bounds
        # the interpreter takes no dynamic bound
        grid=(steps.shape[1] if interpret else n_steps,),
        in_specs=[pl.BlockSpec((1, hkv, grp, dh), at_slot),
                  kv_spec, kv_spec],
        out_specs=[pl.BlockSpec((1, hkv, grp, dh), at_slot),
                   pl.BlockSpec((1, hkv, grp, 1), at_slot),
                   pl.BlockSpec((1, hkv, grp, 1), at_slot)],
        scratch_shapes=[pltpu.VMEM((hkv, grp, 1), jnp.float32),
                        pltpu.VMEM((hkv, grp, 1), jnp.float32),
                        pltpu.VMEM((hkv, grp, dh), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_live_kernel, block=block, scale=dh ** -0.5),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, grp, dh), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, grp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, grp, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="dense_decode_attention",
    )(li, steps, bounds, qg, k_cache, v_cache)
