"""Absorbed latent attention of a decode step over the live part of the
stacked latent cache.

A slot of an ``attention="mla"`` engine holds one latent row a position
and layer in ``[La, slots, R, max_len]`` (``models/xing.py:init_cache``;
``R = kv_lora_rank + qk_rope_head_dim``, positions along the minor
axis), live in ``[0, hi)``: ``hi`` the position its dispatch began at.
One decoded token's absorbed queries, all heads of them, score those
columns, and the columns' first ``kv_lora_rank`` values are also what
the probabilities weigh. The kernel here reads a stack WHOLE and in
place — the traced layer index and a plan of which block to read at
which grid step ride the scalar-prefetch lane, as in
``ops/dense_attention.py``, whose shape this module follows and whose
plan it shares — and fetches only the blocks that hold a live column:
``BLOCK`` columns of all ``R`` values a grid step, ONCE for both dots.
It hands back flash partials ``(acc, m, l)`` in the convention of
``ops.attention.combine_partials``, which folds them with the
dispatch's own rows (``ops.attention.decode_window_partial``) under the
one softmax.

The kernel's body is its own and not ``dense_attention._live_kernel``:
there a block is ``[Hkv, columns, Dh]`` twice (keys, values), here ONE
``[R, columns]`` that is both, positions on the lanes, so the score dot
contracts its rows and the value dot its columns. The plan is the same
algorithm at another block width (``plan_blocks``).

On a non-TPU backend the kernel runs through the Pallas interpreter:
the tests' route. The engine serves through it only on a TPU
(``serves``, ``GenerationEngine._reads_latent_blocks``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from copilot_for_consensus_tpu.obs.profile import scope
from copilot_for_consensus_tpu.ops import dense_attention
from copilot_for_consensus_tpu.ops.dense_attention import (
    _FIRST,
    _KBLK,
    _KSLOT,
    _LAST,
    _READS,
    _SLOT,
)

#: columns of a slot that one grid step reads, all ``R`` values of
#: them; a live extent is rounded out to these. A latent column is
#: 1,152 B at Xing4.0's widths against Mistral's 2 x 2,048, so the
#: block that keeps a step's arithmetic above the grid's own cost is
#: wider than ``dense_attention.BLOCK``; chosen on a v5e among 256, 512
#: and 1,024 by what reads the served mix of lengths fastest (2.84,
#: 1.99 and 1.62 ms a token of 12 layer calls: PERF.md section 6,
#: PR 34). A constant of the kernel: an engine or a config does not set
#: it (an extent it does not divide takes their greatest common
#: divisor).
BLOCK = 1024

#: the narrowest block the kernel is served with (a lane tile of
#: columns); an extent with a smaller common divisor keeps the XLA
#: route (``serves``)
MIN_BLOCK = 128


def block_size(extent: int) -> int:
    """Columns a grid step reads of a cache of ``extent`` columns."""
    return math.gcd(extent, BLOCK)


def serves(extent: int) -> bool:
    """Does absorbed decode attention over a latent cache of ``extent``
    columns a slot, held on one device, go through this kernel? On a
    TPU, when the extent leaves a block of ``MIN_BLOCK`` columns or
    more (``dense_attention.serves``'s rule); elsewhere the XLA route
    over the whole extent serves (and is what the tests hold the
    kernel to)."""
    return jax.default_backend() == "tpu" \
        and block_size(extent) >= MIN_BLOCK


def blocks_read(hi: int, extent: int) -> int:
    """Columns the kernel's blocks cover for a slot live in ``[0,
    hi)`` (host arithmetic: the flight recorder's
    ``state_tokens_read``)."""
    blk = block_size(extent)
    return -(-hi // blk) * blk


@scope("attn")
def plan_blocks(pos0: jax.Array, *, extent: int
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The kernel's plan for slots whose dispatch began at ``pos0``
    ``[B]``: ``dense_attention.plan_blocks`` over ``[0, hi)``, counted
    in this kernel's blocks (its own block width is a constant of that
    module: it is handed the live blocks, one of its blocks each).
    → (steps int32 ``[6, G]``, how many of the G are steps of the plan,
    ``hi [B]``). With no sliding window the live range does not move
    inside a dispatch: made once a dispatch, outside the token loop."""
    blk, unit = block_size(extent), dense_attention.BLOCK
    _, hi = dense_attention.live_range(pos0, pos0, 0, extent)
    steps, n_steps, _ = dense_attention.plan_blocks(
        jnp.zeros_like(hi), -(-hi // blk) * unit,
        extent=extent // blk * unit)
    return steps, n_steps, hi.astype(jnp.int32)


def _live_kernel(li_ref, steps_ref, hi_ref, q_ref, c_ref, *rest,
                 block: int, rank: int, scale: float, kept: bool):
    """One step of the plan: fold one block of one slot, fetched once,
    into the slot's running (max, sum, acc) for all heads. ``kept``: a
    block of the slot's ``keep`` row comes with the latent block, and a
    column counts only where it is non-zero."""
    del li_ref
    keep_ref = rest[0] if kept else None
    acc_out, m_out, l_out, m_ref, l_ref, acc_ref = rest[kept:]
    g = pl.program_id(0)

    @pl.when(steps_ref[_FIRST, g] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(steps_ref[_READS, g] == 1)
    def _fold():
        # columns ``col0 + i`` of the block are live below ``hi``; a
        # dead one may hold anything, NaN included
        q = q_ref[0]                                     # [H, R]
        dt = q.dtype
        c = c_ref[0, 0].astype(dt)                       # [R, block]
        col = steps_ref[_KBLK, g] * block \
            + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        live = col < hi_ref[steps_ref[_SLOT, g]]
        if kept:
            live = live & (keep_ref[0] != 0)
        s = jnp.dot(q, c, preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, -jnp.inf)
        v = jnp.where(live, c[:rank], 0)                 # [rank, block]
        m_prev = m_ref[...]                              # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a slot's first block has column 0 live, so m_new is finite
        # (not so under ``keep``, which may choose none of a block);
        # the subtrahends are pinned (dense_attention._live_kernel)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - m_safe), 0.0)
        p = jnp.exp(s - m_safe)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(dt), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [H, rank]
        m_ref[...] = m_new

    @pl.when(steps_ref[_LAST, g] == 1)
    def _emit():
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def live_partial(q_abs: jax.Array, cache_a: jax.Array, li: jax.Array,
                 plan: tuple, *, rank: int, keep: jax.Array | None = None,
                 interpret: bool | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials of one token's absorbed queries ``q_abs``
    ``[B, H, R]`` (scaled as ``xing.absorbed_attention`` scales them:
    the scores take ``R ** -0.5`` here, as the XLA route's do) over the
    live columns of layer ``li`` (traced) of a stack of the latent
    cache ``[La, B, R, extent]``, read in place by ``plan``
    (``plan_blocks`` for the same extent). A column's first ``rank``
    values are its value. ``keep`` ``[B, 1, extent]`` int32 (a learned
    selection: ``models/xing.py``): of the live columns only those it
    marks non-zero are scored; its blocks ride beside the cache's.

    Returns f32 (acc ``[B, H, rank]``, m ``[B, H, 1]``, l ``[B, H,
    1]``); a slot with nothing live carries ``m = -inf``, ``l = 0``.
    Scores and sums accumulate in float32, the probabilities are
    rounded to the queries' type before they meet the values (as the
    XLA route rounds them); the cache is read in the type it is stored
    in."""
    b, h, width = q_abs.shape
    block = block_size(cache_a.shape[3])
    steps, n_steps, hi = plan
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    li = jnp.reshape(li, (1,)).astype(jnp.int32)

    def at_slot(g, li, steps, hi):
        return steps[_SLOT, g], 0, 0

    def at_block(g, li, steps, hi):
        return li[0], steps[_KSLOT, g], 0, steps[_KBLK, g]

    def at_keep(g, li, steps, hi):
        return steps[_KSLOT, g], 0, steps[_KBLK, g]

    kept = keep is not None

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # layer index, steps, bounds
        # the interpreter takes no dynamic bound
        grid=(steps.shape[1] if interpret else n_steps,),
        in_specs=[pl.BlockSpec((1, h, width), at_slot),
                  pl.BlockSpec((1, 1, width, block), at_block)]
        + [pl.BlockSpec((1, 1, block), at_keep)] * kept,
        out_specs=[pl.BlockSpec((1, h, rank), at_slot),
                   pl.BlockSpec((1, h, 1), at_slot),
                   pl.BlockSpec((1, h, 1), at_slot)],
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, rank), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_live_kernel, block=block, rank=rank,
                          scale=width ** -0.5, kept=kept),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="mla_decode_attention",
    )(li, steps, hi, q_abs, cache_a, *([keep] if kept else []))
