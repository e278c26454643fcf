"""Paged decode attention: block-table indirection into the KV pool.

The paged engine (``GenerationEngine(kv_pool_blocks=...)``) stores KV
in one bounded block pool ``[L, num_blocks, Hkv, block, Dh]``
(``engine/kv_pool.py``) and addresses it through per-slot block tables
``[B, max_blocks]`` int32 — position ``p`` of slot ``b`` lives at pool
block ``tables[b, p // block]``, offset ``p % block``. Two routes serve
attention over that layout:

* **Pallas TPU kernel** (``impl="pallas"``): the block table rides the
  scalar-prefetch lane of a ``PrefetchScalarGridSpec``, so each grid
  step DMAs exactly the physical block the table names — the pool is
  read by POINTER, no gathered contiguous copy ever materializes.
  Flash-style online softmax across the block axis; GQA (grouped
  queries per kv head), sliding-window masking, and fp8 pools
  (dequantized on load) all supported, matching ``decode_attention``'s
  contract.
* **XLA reference** (``impl="xla"``, the CPU/e2e-gate route): gather
  the tables' blocks into the contiguous view the block table DESCRIBES
  and run the unified ``ops.attention.decode_attention`` over it. A
  gather is a pure reordering, so this path is bit-identical to the
  contiguous engine at f32 — which is what lets the existing e2e suites
  gate the paged refactor on CPU.

``paged_gather_kv`` is the same reference materialization at the
stacked-cache level; the engine's REFERENCE route
(``kv_kernel="reference"``) uses it to build the per-dispatch
working-set view its (unchanged) decoder programs read. The KERNEL
route (``kv_kernel="pallas"``, the TPU default) never materializes
that view: the engine's windowed decode joins up to FOUR KV pieces in
one softmax, so :func:`paged_attention_partial_pallas` exposes the
kernel's flash (acc, max, sum) accumulators over the pool piece and
``ops.attention.combine_partials`` folds them with the dispatch-local
pieces — one joint softmax, no gathered copy. The same partial kernel
scores R = G·S seeded-prefill query rows per kv head, which is how
admission, chunked prefill, and spec-decode verify ride the
no-materialization path too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from copilot_for_consensus_tpu.analysis.contracts import checkable
from copilot_for_consensus_tpu.obs.profile import scope
from copilot_for_consensus_tpu.ops.attention import decode_attention

# TPU lane width the kernel's block axis packs against: pool blocks
# must divide it so a block never straddles a lane boundary. The pool
# layout (engine/kv_pool.py POOL_BLOCK_PACK) and the engine's
# dispatch-side declaration commit to the same value — shardcheck's
# ``engine.generation-kv-pack`` group trips if either drifts.
KERNEL_BLOCK_PACK = 128


@scope("kv_prefix")
def paged_gather_layer(pool_k_l: jax.Array, pool_v_l: jax.Array,
                       tables: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """Materialize the contiguous per-slot KV view one layer's block
    table describes: ``[NBtot, Hkv, blk, D]`` pool + ``[B, NB]`` table
    → ``[B, Hkv, NB*blk, D]``. Out-of-range (pad) table entries clamp;
    their garbage columns sit at positions the caller's length mask
    already excludes."""
    b, nb = tables.shape
    hkv, blk, d = pool_k_l.shape[1], pool_k_l.shape[2], pool_k_l.shape[3]
    k = pool_k_l[tables]                       # [B, NB, Hkv, blk, D]
    v = pool_v_l[tables]
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * blk, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * blk, d)
    return k, v


@scope("kv_prefix")
def paged_gather_kv(pool_k: jax.Array, pool_v: jax.Array,
                    tables: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Stacked-cache variant of :func:`paged_gather_layer`:
    ``[L, NBtot, Hkv, blk, D]`` pool + ``[B, NB]`` table →
    ``[L, B, Hkv, NB*blk, D]`` — exactly the slot-cache slice the
    contiguous engine's decoder programs read, which is why the paged
    dispatches can reuse them unchanged (and why greedy decode is
    bit-identical between the two layouts at f32)."""
    n_l = pool_k.shape[0]
    b, nb = tables.shape
    hkv, blk, d = pool_k.shape[2], pool_k.shape[3], pool_k.shape[4]
    k = pool_k[:, tables]                      # [L, B, NB, Hkv, blk, D]
    v = pool_v[:, tables]
    k = k.transpose(0, 1, 3, 2, 4, 5).reshape(n_l, b, hkv, nb * blk, d)
    v = v.transpose(0, 1, 3, 2, 4, 5).reshape(n_l, b, hkv, nb * blk, d)
    return k, v


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _paged_partial_kernel(li_ref, tables_ref, lengths_ref, qpos_ref,
                          q_ref, k_ref, v_ref,
                          acc_out_ref, m_out_ref, l_out_ref,
                          m_ref, l_ref, acc_ref, *,
                          block: int, window: int, scale: float):
    """One (slot, kv-head, table-entry) grid step: score the slot's R
    query rows against ONE physical pool block and fold it into the
    flash-style running (max, sum, acc) accumulators. The block to
    read was chosen by the BlockSpec index map from the
    scalar-prefetched (layer index, block table) — the kernel body
    only ever sees the block the table named. Instead of normalizing,
    the final step EMITS the raw accumulators so the caller can
    combine this piece with dispatch-local KV pieces in one joint
    softmax (``ops.attention.combine_partials``)."""
    b_i = pl.program_id(0)
    i = pl.program_id(2)
    n_i = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # [R, D]
    k = k_ref[0, 0, 0].astype(jnp.float32)           # [blk, D]
    v = v_ref[0, 0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [R, blk]

    length = lengths_ref[b_i]
    pos = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < length
    if window > 0:
        mask &= pos > qpos_ref[b_i] - window
    s = jnp.where(mask, s, -jnp.inf)

    m_prev = m_ref[:]                                # [R, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # fully-masked rows keep m = -inf; exp(-inf - -inf) is NaN, so the
    # subtrahend is pinned finite there (l and acc stay 0 regardless).
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev),
                      jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(s - m_safe)                          # exp(-inf)=0 pads
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(i == n_i - 1)
    def _emit():
        acc_out_ref[0, 0] = acc_ref[:]
        m_out_ref[0, 0] = m_ref[:]
        l_out_ref[0, 0] = l_ref[:]


def paged_attention_partial_pallas(
    q_rows: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    li: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    q_pos: jax.Array,
    *,
    window: int = 0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials of R query rows per kv head against ONE layer of
    the STACKED block pool, read in place.

    q_rows: [B, Hkv, R, D] — R is ``group`` for decode (the grouped
    queries of one token) or ``group * S`` for a seeded suffix pass
    (rows flattened (g, s) row-major); pool halves: [L, NBtot, Hkv,
    blk, D] (any KV dtype — fp8 dequantizes on load); ``li``: traced
    layer index (rides the scalar-prefetch lane next to the table, so
    the pool is indexed by POINTER — no per-layer slice of the pool
    ever materializes, which is what lets the decoder's layer scan
    close over the whole pool); tables: [B, NB] (pad entries >= NBtot
    clamp and must be length-masked); lengths: [B] valid bound of the
    pool piece; q_pos: [B] absolute query position (sliding-window
    masking only — ignored when ``window`` == 0).

    Returns f32 (acc [B, Hkv, R, D], m [B, Hkv, R, 1], l [B, Hkv, R,
    1]) with the usual flash convention: fully-masked rows carry
    m = -inf, l = 0 (``combine_partials`` zeroes their output)."""
    b, hkv, r, d = q_rows.shape
    nbtot, blk = pool_k.shape[1], pool_k.shape[3]
    nb = tables.shape[1]
    if KERNEL_BLOCK_PACK % blk:
        raise ValueError(
            f"pool block {blk} must divide KERNEL_BLOCK_PACK "
            f"{KERNEL_BLOCK_PACK}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # pad table ids into range for the index map (OOB blocks carry
    # garbage that the length mask already excludes)
    tables = jnp.minimum(tables.astype(jnp.int32), nbtot - 1)
    li = jnp.reshape(li, (1,)).astype(jnp.int32)

    grid = (b, hkv, nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # layer index, block table
        grid=grid,
        in_specs=[
            pl.BlockSpec((b,), lambda bi, hi, i, li, tbl: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((b,), lambda bi, hi, i, li, tbl: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, r, d),
                         lambda bi, hi, i, li, tbl: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, blk, d),
                         lambda bi, hi, i, li, tbl:
                         (li[0], tbl[bi, i], hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, blk, d),
                         lambda bi, hi, i, li, tbl:
                         (li[0], tbl[bi, i], hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, r, d),
                         lambda bi, hi, i, li, tbl: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, r, 1),
                         lambda bi, hi, i, li, tbl: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, r, 1),
                         lambda bi, hi, i, li, tbl: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, d), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_paged_partial_kernel, block=blk,
                          window=window, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, r, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, r, 1), jnp.float32),
        ],
        interpret=interpret,
    )(li, tables, lengths.astype(jnp.int32), q_pos.astype(jnp.int32),
      q_rows, pool_k, pool_v)
    return acc, m, l


def paged_decode_attention_pallas(
    q: jax.Array,
    pool_k_l: jax.Array,
    pool_v_l: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """The Pallas route: single-token paged decode attention for one
    layer. q: [B, Hq, D]; pool halves: [NBtot, Hkv, blk, D] (any KV
    dtype — fp8 dequantizes on load); tables: [B, NB] int32 (pad
    entries >= NBtot clamp and must be length-masked); lengths: [B]
    committed positions per slot. Returns [B, Hq, D] in q's dtype.

    This is the single-piece instance of the partial kernel: one
    pool piece, normalized right after — the same IEEE ops the old
    in-kernel finalize ran, so results are unchanged bit for bit."""
    b, hq, d = q.shape
    hkv = pool_k_l.shape[1]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d)
    acc, m, l = paged_attention_partial_pallas(
        qg, pool_k_l[None], pool_v_l[None], jnp.zeros((1,), jnp.int32),
        tables, lengths, lengths - 1, window=window,
        interpret=interpret)
    out = acc / jnp.where(l > 0, l, 1.0)
    # fully-masked rows (parked slots, length 0) emit exact zeros —
    # the same value the XLA reference's NaN guard produces
    out = jnp.where(l > 0, out, 0.0).astype(q.dtype)
    return out.reshape(b, hq, d)


def paged_decode_attention(
    q: jax.Array,
    pool_k_l: jax.Array,
    pool_v_l: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    window: int = 0,
    impl: str = "auto",
) -> jax.Array:
    """Single-token decode attention through a block table.

    Semantics are EXACTLY ``decode_attention(q, view_k, view_v,
    lengths, window)`` where ``view_*`` is the contiguous per-slot
    cache the table describes (``paged_gather_layer``) — GQA grouping,
    sliding-window masking relative to ``lengths - 1``, fp8 dequant,
    fully-masked rows emitting zeros. ``impl="xla"`` IS that
    composition (bit-identical at f32, the CPU e2e gate's route);
    ``impl="pallas"`` reads the pool by pointer instead of gathering
    (TPU serving route; parity-tested against the reference)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return paged_decode_attention_pallas(
            q, pool_k_l, pool_v_l, tables, lengths, window=window)
    k, v = paged_gather_layer(pool_k_l, pool_v_l, tables)
    return decode_attention(q, k, v, lengths, window=window)


# ---------------------------------------------------------------------------
# hlocheck contracts (analysis/hlocheck.py)
# ---------------------------------------------------------------------------


@checkable("paged-attention-kernel")
def _hlocheck_paged_attention():
    """The two attention routes, verified at the op level against
    their own lowered artifacts (the engine-level contracts in
    engine/generation.py verify whole dispatches; this pins the claim
    where it is made — module docstring: "the pool is read by POINTER,
    no gathered contiguous copy ever materializes"):

    * ``partial-pallas``: the flash-partial kernel must lower with NO
      gather at/above the per-layer working-set size
      (B × Hkv × NB·blk × D result elements). On CPU the kernel runs
      in interpret mode, which lowers the block walk to
      dynamic-slice-driven loops — pointer reads either way; a gather
      showing up here means someone re-routed the kernel through the
      reference materialization.
    * ``decode-xla-reference``: the reference route gathers that exact
      view BY DESIGN (it is the bit-identity anchor for the CPU e2e
      gates), so it declares only a compiled-peak budget — the cost of
      the materialization stays bounded and measured
      (docs/artifacts/HLO_BUDGETS.json) instead of forbidden.
    """
    from copilot_for_consensus_tpu.analysis.contracts import (
        ContractCase,
        HloSpec,
    )

    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    f32 = jnp.float32
    b, hq, hkv, d, blk, nbtot, nb, n_l = 4, 4, 2, 8, 8, 16, 8, 2
    r = hq // hkv                # grouped query rows per kv head
    # one slot's view of the layer pool: the materialization the
    # kernel route must never emit
    ws_elems = b * hkv * nb * blk * d
    pool = S((n_l, nbtot, hkv, blk, d), jnp.bfloat16)
    pool_l = S((nbtot, hkv, blk, d), jnp.bfloat16)
    # deliberate non-donation, twice over: these jits exist only to be
    # LOWERED by hlocheck (never executed), and both routes are pure
    # READS of the live pool — the engine's scatter dispatches own the
    # pool update and its donation aliases (engine/generation.py).
    # jaxlint: disable=donation
    partial_fn = jax.jit(functools.partial(
        paged_attention_partial_pallas, window=0, interpret=True))
    # jaxlint: disable=donation
    xla_fn = jax.jit(functools.partial(
        paged_decode_attention, window=0, impl="xla"))
    return [
        ContractCase(
            label="partial-pallas", fn=partial_fn,
            args=(S((b, hkv, r, d), f32), pool, pool,
                  S((1,), i32), S((b, nb), i32), S((b,), i32),
                  S((b,), i32)),
            hlo=HloSpec(forbid_ops=(("gather", ws_elems),),
                        peak_bytes=90_000)),
        ContractCase(
            label="decode-xla-reference", fn=xla_fn,
            args=(S((b, hq, d), f32), pool_l, pool_l,
                  S((b, nb), i32), S((b,), i32)),
            hlo=HloSpec(peak_bytes=60_000)),
    ]
