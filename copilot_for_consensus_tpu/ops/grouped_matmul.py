"""Grouped matmul over int8 expert matrices, for dropless sparse experts.

Rows of ``lhs`` ``[m, k]`` are sorted by group (an expert): group g
owns the rows ``[off[g], off[g] + sizes[g])`` and they meet that
group's matrix ``q[g]`` ``[k, n]`` (int8, one float32 scale per output
channel, ``scale[g]`` ``[1, n]``). Rows past the last group's belong to
none; what comes back for them is undefined and the caller masks it.

The tiling and its metadata are megablox's
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

On a non-TPU backend the kernel runs through the Pallas interpreter:
``models/xing.py`` serves int8 experts through it on every backend, so
the CPU tests and the rehearsal run the code the chip serves with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn) for ``[m, k] x [k, n]``: the widest tiles of a short
    list that divide the sizes. A handful of rows (a decode step) takes
    deep k-tiles, so a grid step moves megabytes of weights; many rows
    (an admission wave) take tall m-tiles and shallower k-tiles, which
    keep the accumulator and both operands within the scoped VMEM."""
    def widest(x, options):
        return next((t for t in options if x % t == 0), x)

    tm = widest(m, (512, 256, 128, 64, 32, 16, 8))
    tk = widest(k, (1792, 1024, 512, 256, 128) if tm <= 64
                else (512, 256, 128))
    return tm, tk, widest(n, (1024, 896, 512, 256, 128))


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
        gid = gids_ref[g]
        rows = mtid_ref[g] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (rows >= offs_ref[gid]) & (rows < offs_ref[gid + 1])
        out_ref[...] = jnp.where(
            mine, acc_ref[...] * scale_ref[...],
            out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


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
    tm, tk, tn = tiling(m, k, n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (offs, gids, mtid), n_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=False)
    tiles_k = k // tk

    def at_lhs(ni, g, ki, offs, gids, mtid, nt, li):
        return mtid[g], ki

    def at_q(ni, g, ki, offs, gids, mtid, nt, li):
        return li[0], gids[g], ki, ni

    def at_scale(ni, g, ki, offs, gids, mtid, nt, li):
        return li[0], gids[g], 0, ni

    def at_out(ni, g, ki, offs, gids, mtid, nt, li):
        return mtid[g], ni

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # offsets, group ids, m-tile ids, their count, the layer
        num_scalar_prefetch=5,
        # the interpreter takes no dynamic bound
        grid=(n // tn, gids.shape[0] if interpret else n_tiles, tiles_k),
        in_specs=[pl.BlockSpec((tm, tk), at_lhs),
                  pl.BlockSpec((None, None, tk, tn), at_q),
                  pl.BlockSpec((None, None, 1, tn), at_scale)],
        out_specs=pl.BlockSpec((tm, tn), at_out),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
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
