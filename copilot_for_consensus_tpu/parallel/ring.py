"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Blockwise attention with online-softmax accumulation while KV blocks
rotate around the ring via ``ppermute`` (one ICI hop per step, compute
overlapping communication at the XLA level). The sequence axis of q/k/v
is sharded over ``sp``; each device holds S/n query positions and visits
every KV block after n-1 rotations.

This is a NEW capability relative to the reference, which avoids long
context by top-k truncation to a 3000-token budget
(``orchestrator/app/context_selectors.py:94-107``; SURVEY.md §5
"Long-context / sequence parallelism: Absent"). With CP, whole
threads/archives fit in context instead of being truncated — the
BASELINE.json v5p "long multi-thread consensus" configuration.

Numerics: identical accumulation scheme to the flash kernel
(``ops/flash_attention.py``); oracle-tested against ``attention_xla`` on
the virtual mesh in ``tests/test_parallel_ring.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from copilot_for_consensus_tpu.analysis.contracts import checkable

NEG_INF = -1e30


def _ring_shard(q, k, v, kv_lengths, *, axis_name: str, causal: bool,
                scale: float, window: int):
    """Per-shard body. q/k/v: [B, H, S_loc, D] (this shard's blocks);
    kv_lengths: [B] valid-length mask (replicated), or None."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape

    qf = q.astype(jnp.float32)
    q_pos = idx * s_loc + jnp.arange(s_loc)              # global positions

    # pcast: constants are "unvarying" over the mesh axis; the loop carry
    # becomes varying after the first ppermute, so types must match.
    vary = lambda x: jax.lax.pcast(
        x, (axis_name,), to="varying")  # noqa: E731
    m0 = vary(jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32))
    l0 = vary(jnp.zeros((b, h, s_loc, 1), jnp.float32))
    acc0 = vary(jnp.zeros((b, h, s_loc, d), jnp.float32))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        m, l, acc, k_blk, v_blk = carry
        # After i rotations we hold the kv block originally on shard
        # (idx - i) mod n.
        src = (idx - i) % n
        k_pos = src * s_loc + jnp.arange(s_loc)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        # Build the [B?, s_loc, s_loc] validity mask exactly as the
        # non-ring paths do (ops.attention.make_attention_mask), with
        # k_pos expressed in global coordinates so rotation is invisible.
        mask = jnp.ones((s_loc, s_loc), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        mask = mask[None, None]                          # [1, 1, q, k]
        if kv_lengths is not None:
            mask = mask & (k_pos[None, None, None, :]
                           < kv_lengths[:, None, None, None])
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m_new, l, acc, k_blk, v_blk

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, acc0, k, v))
    safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    window: int = 0,
    kv_lengths=None,
    impl: str | None = None,     # accepted for attention-impl interface
) -> jax.Array:
    """Drop-in attention impl (same [B, H, S, D] contract as
    ``ops.attention.attention``) with the sequence axis sharded over
    ``axis``. GQA kv heads are expanded before sharding (kv replication
    across the ring would defeat the rotation). ``window`` applies
    Mistral-style sliding-window masking and ``kv_lengths`` masks padded
    kv positions — both in global coordinates, matching
    ``ops.attention.make_attention_mask``."""
    from copilot_for_consensus_tpu.ops.attention import _gqa_expand

    hq = q.shape[1]
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(
            f"sequence {q.shape[2]} not divisible by {axis}={n}")
    spec = P(None, None, axis, None)
    fn = shard_map(
        functools.partial(_ring_shard, axis_name=axis, causal=causal,
                          scale=q.shape[-1] ** -0.5, window=int(window)),
        # kv_lengths rides replicated (P()); a None is an empty pytree and
        # its spec is simply unused.
        mesh=mesh, in_specs=(spec, spec, spec, P()), out_specs=spec,
    )
    return fn(q, k, v, kv_lengths)


def make_ring_attention(mesh: Mesh, axis: str = "sp"):
    """Bind mesh/axis → a callable usable as ``attn_impl`` in the model
    forward passes (``models.decoder.forward(..., attn_impl=fn)``)."""
    return functools.partial(ring_attention, mesh=mesh, axis=axis)


# ---------------------------------------------------------------------------
# shardcheck contracts (analysis/shardcheck.py)
# ---------------------------------------------------------------------------


@checkable("ring-attention")
def _shardcheck_ring_attention():
    """Trace the shard_map'd ring under the real sp mesh: the psum /
    axis_index / ppermute collectives inside ``_ring_shard`` must bind
    the module's default axis on a mesh that actually has it, with the
    sequence divisible by the ring size. Uses the module defaults on
    purpose — an axis-name typo here IS the bug this catches."""
    from copilot_for_consensus_tpu.analysis.contracts import (
        ContractCase,
        require_devices,
    )
    from copilot_for_consensus_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    require_devices(8)
    mesh = build_mesh(MeshConfig(sp=4), devices=jax.devices()[:8])
    S = jax.ShapeDtypeStruct
    b, hq, hkv, s, d = 1, 8, 4, 256, 64
    q = S((b, hq, s, d), jnp.bfloat16)
    kv = S((b, hkv, s, d), jnp.bfloat16)
    return ContractCase(
        fn=functools.partial(ring_attention, mesh=mesh),
        args=(q, kv, kv),
        kwargs={"kv_lengths": S((b,), jnp.int32)},
        mesh=mesh,
    )
