"""Grouped matmul over int8 expert matrices, for dropless sparse experts.

Rows of ``lhs`` ``[m, k]`` are sorted by group (an expert): group g
owns the rows ``[off[g], off[g] + sizes[g])`` and they meet that
group's matrix ``q[g]`` ``[k, n]`` (int8, one float32 scale per output
channel, ``scale[g]`` ``[1, n]``). Rows past the last group's belong to
none; what comes back for them is undefined and the caller masks it.

The metadata is megablox's
(``jax.experimental.pallas.ops.tpu.megablox``: an m-tile is visited
once by every group that has a row in it, a group with no row is not
visited, so its matrix is never fetched): what a decode step reads of a
layer's experts is the experts its rows chose. That kernel takes
float matrices only, and XLA's own ``ragged_dot`` meets an int8 stack
by writing all of it out in bfloat16 first; here the int8 tile is
widened in VMEM, after the fetch, and the scale is applied once to the
finished accumulator, as ``layers.qmatmul`` applies it. The matrices
may come as the stack of all layers with a traced layer index, which
rides the scalar-prefetch lane (as in ``ops/dense_attention.py``): a
layer scan then closes over the stack and nothing cuts a layer's
experts out of it; cut out by the scan, a layer's experts would be
copied whole every step, chosen or not, for the kernel to take by
pointer.

A visit multiplies ALL ``tm`` rows of its m-tile by the visiting
group's matrix and keeps that group's own (``_keep``), so what the MXU
is asked for is visits x ``tm`` rows whatever the groups hold of them.
``tiling`` therefore has two regimes, told apart by the rows a group
has on average, ``m // groups``, a static shape:

* **a handful of rows in all** (under 16 a group: a decode step's 32
  or 64 pairs): one short m-tile and deep k-tiles (``deep_tiling``), so
  a grid step moves megabytes of weights and the HBM sets the time; the
  weight tile is widened as it is multiplied and partial sums wait in
  an accumulator (``_kernel``).
* **rows enough to fill tiles** (an admission wave: 16 to 1,024 rows a
  group): the row tile follows the rows an expert has, up to the MXU's
  128, and the expert's block is held whole in k (``tk = k``), as wide
  in n as ``RESIDENT`` lets it be. Megablox's order keeps a group's
  visits adjacent, so they present the same block index and the block
  is fetched once a group and n-tile; a visit is one dot and needs no
  accumulator (``_whole_k_kernel``). Until PR 42 every ``m`` that 512
  divides took ``tm = 512`` and ``tk = 512``: at 128 rows a group 79
  visits of 512 rows for 8,192 pairs, a fifth of the rows multiplied
  kept, and the int8 tile fetched and widened anew at every grid step.
  Timed on the chip (PERF.md section 6, PRs 41 and 42; PR 41 made this
  change first, and PR 42 is the same change with the claim the
  driver's pairs bore out): a visit costs 2.4 us and 0.037 us a row
  (the MXU's peak), so 64-row tiles buy nothing for their higher fill
  and 256-row tiles lose; the block is widened at every visit, within
  the dot, where the widening overlaps the MXU's work: widened once a
  group into a scratch, in a step of its own, the same matmuls took
  10-17% longer.

``tile_counts`` says how far that goes: the rows that have a group
over the rows the visits multiply (``expert_group_rows`` /
``expert_tile_rows`` on the step records).

On a non-TPU backend the kernel runs through the Pallas interpreter:
``models/xing.py`` serves int8 experts through it on every backend, so
the CPU tests and the rehearsal run the code the chip serves with.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata


#: the MXU's height: a taller row tile multiplies no row faster and
#: more rows of other groups at each group's edges
MXU_ROWS = 128
#: the most int8 elements of a group's block ``[k, tn]`` held whole
#: (GLM-5's 6144 x 1024): the pipeline holds two of it and the dot one
#: more, widened: 25 MB of the 64 the call asks for
RESIDENT = 6144 * 1024


class Tiles(NamedTuple):
    tm: int
    tk: int
    tn: int
    resident: bool      # a group's block, whole in k, serves its visits


def deep_tiling(m: int, k: int, n: int) -> Tiles:
    """The widest tiles of a short list that divide the sizes. A
    handful of rows (a decode step) takes deep k-tiles, so a grid step
    moves megabytes of weights; many rows take tall m-tiles and
    shallower k-tiles, which keep the accumulator and both operands
    within the scoped VMEM."""
    def widest(x, options):
        return next((t for t in options if x % t == 0), x)

    tm = widest(m, (512, 256, 128, 64, 32, 16, 8))
    tk = widest(k, (1792, 1024, 512, 256, 128) if tm <= 64
                else (512, 256, 128))
    return Tiles(tm, tk, widest(n, (1024, 896, 512, 256, 128)), False)


def tiling(m: int, groups: int, k: int, n: int) -> Tiles:
    """The tiles for ``[m, k]`` rows over ``groups`` matrices ``[k,
    n]``. With 16 rows a group or more (an admission wave) the row tile
    follows the rows a group has, ``MXU_ROWS`` at most, and the group's
    block is whole in k and the widest in n that ``RESIDENT`` holds.
    With fewer (a decode step), or a block that no cut in n makes fit,
    ``deep_tiling``."""
    tm = next((t for t in (MXU_ROWS, 64, 32, 16)
               if t <= m // groups and m % t == 0), None)
    tn = next((n // d for d in range(1, max(n // 128, 1) + 1)
               if n % d == 0 and k * (n // d) <= RESIDENT
               and (d == 1 or n // d % 128 == 0)), None)
    if tm and tn:
        return Tiles(tm, k, tn, True)
    return deep_tiling(m, k, n)


def _keep(offs_ref, gids_ref, mtid_ref, out_ref, g, product, tm: int):
    """Of a visit's finished ``product`` the visiting group's rows; the
    others stay what earlier visits of the m-tile left."""
    gid = gids_ref[g]
    rows = mtid_ref[g] * tm + jax.lax.broadcasted_iota(
        jnp.int32, product.shape, 0)
    mine = (rows >= offs_ref[gid]) & (rows < offs_ref[gid + 1])
    out_ref[...] = jnp.where(
        mine, product, out_ref[...].astype(jnp.float32)
    ).astype(out_ref.dtype)


def _kernel(offs_ref, gids_ref, mtid_ref, ntiles_ref, layer_ref, lhs_ref,
            q_ref, scale_ref, out_ref, acc_ref, *, tm: int, tiles_k: int):
    del layer_ref
    g, ki = pl.program_id(1), pl.program_id(2)
    live = g < ntiles_ref[0]       # the interpreter's grid is the bound

    @pl.when(live & (ki == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        lhs = lhs_ref[...]
        acc_ref[...] += jax.lax.dot(
            lhs, q_ref[...].astype(lhs.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(live & (ki == tiles_k - 1))
    def _store():
        _keep(offs_ref, gids_ref, mtid_ref, out_ref, g,
              acc_ref[...] * scale_ref[...], tm)


def _whole_k_kernel(offs_ref, gids_ref, mtid_ref, ntiles_ref, layer_ref,
                    lhs_ref, q_ref, scale_ref, out_ref, *, tm: int):
    """One k-tile: the product is finished in one dot, and nothing
    waits in an accumulator."""
    del layer_ref
    g = pl.program_id(1)

    @pl.when(g < ntiles_ref[0])    # the interpreter's grid is the bound
    def _visit():
        lhs = lhs_ref[...]
        _keep(offs_ref, gids_ref, mtid_ref, out_ref, g,
              jax.lax.dot(lhs, q_ref[...].astype(lhs.dtype),
                          preferred_element_type=jnp.float32)
              * scale_ref[...], tm)


def _metadata(group_sizes: jax.Array, m: int, tm: int):
    return make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=group_sizes.shape[0], visit_empty_groups=False)


def tile_counts(group_sizes: jax.Array, m: int, k: int, n: int
                ) -> jax.Array:
    """``[rows that have a group, rows the kernel multiplies for them]``
    (int32) of ``grouped_qmatmul`` over ``[m, k] x [G, k, n]``: the
    second is its visits times its row tile."""
    tm = tiling(m, group_sizes.shape[0], k, n).tm
    _, visits = _metadata(group_sizes, m, tm)
    return jnp.stack([jnp.sum(group_sizes), visits * tm]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def grouped_qmatmul(lhs: jax.Array, q: jax.Array, scale: jax.Array,
                    group_sizes: jax.Array, layer: jax.Array | None = None,
                    *, out_dtype=jnp.float32,
                    interpret: bool | None = None) -> jax.Array:
    """``lhs[rows of g] @ (q[g] * scale[g])`` for every group g with a
    row: lhs ``[m, k]`` sorted by group, q ``[G, k, n]`` int8, scale
    ``[G, 1, n]`` float32, group_sizes ``[G]`` int32 → ``[m, n]``.
    With ``layer`` (traced): q ``[L, G, k, n]`` and scale ``[L, G, 1,
    n]`` hold every layer's, read in place at that layer."""
    if layer is None:
        q, scale, layer = q[None], scale[None], 0
    m, k = lhs.shape
    _, groups, _, n = q.shape
    tm, tk, tn, resident = tiling(m, groups, k, n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (offs, gids, mtid), n_tiles = _metadata(group_sizes, m, tm)
    tiles_k = k // tk

    def at_lhs(ni, g, ki, offs, gids, mtid, nt, li):
        return mtid[g], ki

    def at_q(ni, g, ki, offs, gids, mtid, nt, li):
        return li[0], gids[g], ki, ni

    def at_scale(ni, g, ki, offs, gids, mtid, nt, li):
        return li[0], gids[g], 0, ni

    def at_out(ni, g, ki, offs, gids, mtid, nt, li):
        return mtid[g], ni

    if resident:
        kernel, scratch = functools.partial(_whole_k_kernel, tm=tm), []
    else:
        kernel = functools.partial(_kernel, tm=tm, tiles_k=tiles_k)
        scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # offsets, group ids, m-tile ids, their count, the layer
        num_scalar_prefetch=5,
        # the interpreter takes no dynamic bound
        grid=(n // tn, gids.shape[0] if interpret else n_tiles, tiles_k),
        in_specs=[pl.BlockSpec((tm, tk), at_lhs),
                  pl.BlockSpec((None, None, tk, tn), at_q),
                  pl.BlockSpec((None, None, 1, tn), at_scale)],
        out_specs=pl.BlockSpec((tm, tn), at_out),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="grouped_qmatmul",
    )(offs, gids, mtid, jnp.reshape(n_tiles, (1,)).astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), lhs, q,
      scale.astype(jnp.float32))
