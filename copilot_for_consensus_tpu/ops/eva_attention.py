"""EVA decode attention over the live part of the stacked cache.

A slot of an ``attention="eva"`` engine (``models/eva.py``) holds an
exact window ``k``/``v`` ``[L, slots, H, W + margin, Dh]`` whose live
columns are ``[0, fill)`` and a summary store ``ks``/``vs``
``[L, slots, H, R, Dh]`` whose live columns are ``[R - n, R)``. One
decoded token's queries score both. The kernel here reads the four
halves WHOLE and in place — the traced layer index and a plan of which
block to read at which grid step ride the scalar-prefetch lane, as the
paged kernel's block table does (``ops/paged_attention.py``) — and
fetches only the blocks that hold a live column: ``WIN_BLOCK`` columns
of a window or ``SUM_BLOCK`` of a summary store, all heads, a grid
step. It hands back flash partials ``(acc, m, l)`` over both pieces
together, in the convention of ``ops.attention.combine_partials``,
which folds them with the dispatch's own columns under the one
softmax.

The plan (``plan_blocks``) is a list of steps, a slot after the other:
the slot's live window blocks, then its live summary blocks; a slot
with nothing live (parked, or a window that filled earlier in this
dispatch behind no summaries) takes one step that reads nothing, so
that its partial is written (``m = -inf, l = 0``). An input that a
step does not use keeps the block index it had, which the pipeline
does not fetch again, so each live step's fetch of the NEXT live block
runs under its own arithmetic whatever slot that block belongs to. On
a TPU the grid is as long as the plan (a dynamic bound). The
interpreter takes no dynamic bound: there the grid is the worst case,
every slot full of both, and the steps past the plan's end hold every
index: neither a copy nor arithmetic.

On a non-TPU backend the kernel runs through the Pallas interpreter:
the tests' route. ``models/eva.py`` serves through it only on a TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: columns of a slot's window, and of its summary store, that one grid
#: step reads for all heads. A live extent is rounded up to these. At
#: EvaByte's widths (32 heads of 128, bf16) a block is 1 MB a half,
#: large enough that a step is bound by HBM and not by the grid: on a
#: v5e 128, 256 and 512 columns read at the same 600-660 GB/s, so the
#: smallest rounds least (PERF.md section 6, PR 30); a window closes
#: into 128 summaries there, so the store rounds to nothing. Constants
#: of the kernel: an engine or a config does not set them (a store or
#: a window they do not divide takes their greatest common divisor).
WIN_BLOCK = 128
SUM_BLOCK = 128

# rows of the plan's steps
_KIND, _SLOT, _FIRST, _LAST, _WSLOT, _WBLK, _SSLOT, _SBLK = range(8)
_WINDOW, _SUMMARY, _NOTHING = 0, 1, 2


def block_sizes(window: int, store: int) -> tuple[int, int]:
    """(window block, summary block) for a window of ``window`` columns
    and a summary store of ``store``."""
    return math.gcd(window, WIN_BLOCK), math.gcd(store, SUM_BLOCK)


def blocks_read(fill: int, n_sum: int, window: int, store: int
                ) -> tuple[int, int]:
    """Columns the kernel's blocks cover for a slot with ``fill`` live
    window columns and ``n_sum`` live summaries (host arithmetic: the
    flight recorder's ``state_tokens_read``)."""
    wb, sb = block_sizes(window, store)
    return -(-fill // wb) * wb, store - (store - n_sum) // sb * sb


def plan_blocks(win_len: jax.Array, sum_n: jax.Array, *, window: int,
                store: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The kernel's plan for slots with ``win_len`` ``[B]`` live window
    columns (of ``window``) and ``sum_n`` ``[B]`` live summaries (of
    ``store``): (steps int32 ``[8, G]``,
    ``G = B * (window / window block + store / summary block)``; how
    many of the G are steps of the plan; the two lengths ``[2, B]``).
    A step, row by row: what it reads (window block, summary block,
    nothing), its slot, is it the slot's first step, its last, and the
    (slot, block) each of the two inputs points at. It does not depend
    on the layer: made once a token."""
    win_block, sum_block = block_sizes(window, store)
    b = win_len.shape[0]
    nw, ns = window // win_block, store // sum_block
    g = jnp.arange(b * (nw + ns), dtype=jnp.int32)
    n_win = -(-win_len // win_block)
    s_first = (store - sum_n) // sum_block
    n_all = n_win + ns - s_first
    count = jnp.maximum(n_all, 1)
    end = jnp.cumsum(count)
    slot = jnp.minimum(jnp.sum(g[:, None] >= end[None, :], axis=1), b - 1)
    live = g < end[-1]
    r = g - (end - count)[slot]
    is_win = live & (r < n_win[slot])
    is_sum = live & ~is_win & (r < n_all[slot])
    kind = jnp.where(is_win, _WINDOW, jnp.where(is_sum, _SUMMARY, _NOTHING))

    def held(used, blk, n_blocks):
        """(slot, block) at the latest step that used the input; before
        the first such step, that step's (fetched ahead of its time)."""
        at = jax.lax.cummax(jnp.where(used, g, -1))
        at = jnp.where(at >= 0, at, jnp.argmax(used))
        return slot[at], jnp.clip(blk[at], 0, n_blocks - 1)

    w_slot, w_blk = held(is_win, r, nw)
    s_slot, s_blk = held(is_sum, s_first[slot] + r - n_win[slot], ns)
    steps = jnp.stack([
        kind, slot, live & (r == 0), live & (r == count[slot] - 1),
        w_slot, w_blk, s_slot, s_blk]).astype(jnp.int32)
    return steps, end[-1].astype(jnp.int32), \
        jnp.stack([win_len, sum_n]).astype(jnp.int32)


def _state_kernel(li_ref, steps_ref, lens_ref, q_ref, kw_ref, vw_ref,
                  ks_ref, vs_ref, acc_out, m_out, l_out, m_ref, l_ref,
                  acc_ref, *, win_block: int, sum_block: int, store: int,
                  scale: float):
    """One step of the plan: fold one block of one slot's window or
    summaries, all heads, into the slot's running (max, sum, acc)."""
    del li_ref
    g = pl.program_id(0)
    kind, slot = steps_ref[_KIND, g], steps_ref[_SLOT, g]

    @pl.when(steps_ref[_FIRST, g] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(k, v, col0, lo, hi):
        """Columns ``col0 + i`` of the block are live in ``[lo, hi)``;
        a dead one may hold anything, NaN included."""
        q = q_ref[0]                                     # [H, 1, Dh]
        dt = q.dtype
        h, t, dh = k.shape
        s = jnp.einsum("hqd,htd->hqt", q, k.astype(dt),
                       preferred_element_type=jnp.float32) * scale
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
        s = jnp.where((col >= lo) & (col < hi), s, -jnp.inf)
        row = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, t, dh), 1)
        v = jnp.where((row >= lo) & (row < hi), v.astype(dt), 0)
        m_prev = m_ref[...]                              # [H, 1, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with nothing live yet keeps m = -inf: the subtrahend is
        # pinned finite there (paged_attention._paged_partial_kernel)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - m_safe), 0.0)
        p = jnp.exp(s - m_safe)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hqt,htd->hqd", p.astype(dt), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kind == _WINDOW)
    def _window():
        fold(kw_ref[0, 0], vw_ref[0, 0], steps_ref[_WBLK, g] * win_block,
             0, lens_ref[0, slot])

    @pl.when(kind == _SUMMARY)
    def _summary():
        fold(ks_ref[0, 0], vs_ref[0, 0], steps_ref[_SBLK, g] * sum_block,
             store - lens_ref[1, slot], store)

    @pl.when(steps_ref[_LAST, g] == 1)
    def _emit():
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def state_partial(q: jax.Array, cache: dict, li: jax.Array, plan: tuple,
                  *, window: int, interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials of one token's queries ``q`` ``[B, H, Dh]`` over
    the live window columns and summaries of layer ``li`` (traced) of
    the stacked ``cache`` (``models/eva.py:init_cache``; windows of
    ``window`` columns before the margin), read in place by ``plan``
    (``plan_blocks`` for the same ``window`` and the cache's store).

    Returns f32 (acc ``[B, H, 1, Dh]``, m ``[B, H, 1, 1]``,
    l ``[B, H, 1, 1]``); a slot with nothing live carries ``m = -inf``,
    ``l = 0``. Scores and sums accumulate in float32; the state is read
    in the type it is stored in."""
    b, h, dh = q.shape
    store = cache["ks"].shape[3]
    win_block, sum_block = block_sizes(window, store)
    steps, n_steps, lens = plan
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    li = jnp.reshape(li, (1,)).astype(jnp.int32)

    def at_slot(g, li, steps, lens):
        return steps[_SLOT, g], 0, 0, 0

    def at_window(g, li, steps, lens):
        return li[0], steps[_WSLOT, g], 0, steps[_WBLK, g], 0

    def at_summary(g, li, steps, lens):
        return li[0], steps[_SSLOT, g], 0, steps[_SBLK, g], 0

    win_spec = pl.BlockSpec((1, 1, h, win_block, dh), at_window)
    sum_spec = pl.BlockSpec((1, 1, h, sum_block, dh), at_summary)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # layer index, steps, lengths
        # the interpreter takes no dynamic bound
        grid=(steps.shape[1] if interpret else n_steps,),
        in_specs=[pl.BlockSpec((1, h, 1, dh), at_slot),
                  win_spec, win_spec, sum_spec, sum_spec],
        out_specs=[pl.BlockSpec((1, h, 1, dh), at_slot),
                   pl.BlockSpec((1, h, 1, 1), at_slot),
                   pl.BlockSpec((1, h, 1, 1), at_slot)],
        scratch_shapes=[pltpu.VMEM((h, 1, 1), jnp.float32),
                        pltpu.VMEM((h, 1, 1), jnp.float32),
                        pltpu.VMEM((h, 1, dh), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_state_kernel, win_block=win_block,
                          sum_block=sum_block, store=store,
                          scale=dh ** -0.5),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, 1, dh), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="eva_decode_attention",
    )(li, steps, lens, q[:, :, None, :], cache["k"], cache["v"],
      cache["ks"], cache["vs"])
