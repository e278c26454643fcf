"""Pallas flash attention for TPU.

Online-softmax tiling (Flash-Attention-2 style): grid is
``(batch, q_head, q_blocks, kv_blocks)`` with the kv dimension innermost —
TPU executes innermost grid steps sequentially on-core, so the running
max / denominator / accumulator live in VMEM scratch across kv steps.
Supports causal masking, Mistral sliding-window, GQA (kv head indexed as
``q_head // group``), and padded kv via per-batch lengths in SMEM.

Numerics oracle: ``ops.attention.attention_xla`` (tested to ≤2e-2 bf16 /
1e-5 fp32 in ``tests/test_ops_attention.py``). On non-TPU backends the
kernel runs in interpret mode, so the same code path is exercised in CI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
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
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # q_offsets place the query block inside the kv timeline (a piece
    # of a prompt: its fresh queries at the end of a growing kv run);
    # kv_begins exclude a kv PREFIX (eva.piece_attention: the summary
    # columns a row has not filled yet). Dynamic (SMEM) because both
    # differ row by row and piece by piece.
    q_off = off_ref[bi]
    kv_begin = begin_ref[bi]
    q_start = qi * bq + q_off
    k_start = ki * bk
    # Whole kv block beyond the causal frontier, before the begin
    # bound, or before the window is skipped — with kv innermost this
    # prunes the dead work.
    in_range = k_start + bk - 1 >= kv_begin
    if causal:
        in_range = jnp.logical_and(in_range,
                                   k_start <= q_start + bq - 1)
    if window > 0:
        in_range = jnp.logical_and(
            in_range, k_start + bk - 1 > q_start - window
        )

    @pl.when(in_range)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # [bq, bk]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (k_pos < len_ref[bi]) & (k_pos >= kv_begin)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]                                   # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                      # [bq, 1]
        l_scr[:] = corr * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[:] = corr * acc_scr[:] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new

    @pl.when(ki == nk - 1)
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
    block_q: int = 256,
    block_kv: int = 256,
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
    timeline is its summaries, then its window.
    """
    b, hq, s_q_in, d = q.shape
    hkv, s_kv_in = k.shape[1], k.shape[2]
    group = hq // hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bq = min(block_q, s_q_in)
    bk = min(block_kv, s_kv_in)
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

    grid = (b, hq, s_q // bq, s_kv // bk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, window=window, bq=bq, bk=bk,
            scale=d ** -0.5,
        ),
        grid=grid,
        in_specs=[
            # whole lengths/offsets vectors in SMEM; indexed by
            # program_id(0) in the kernel (a rank-1 block of 1 over [B]
            # is rejected by the TPU lowering's tiling rules when B > 1)
            pl.BlockSpec((b,), lambda bi, hi, qi, ki: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((b,), lambda bi, hi, qi, ki: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((b,), lambda bi, hi, qi, ki: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(kv_lengths, q_offsets, kv_begins, q, k, v)
    return out[:, :, :s_q_in, :]
