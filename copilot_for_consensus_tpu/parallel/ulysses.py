"""Ulysses sequence parallelism: all-to-all head↔sequence resharding.

The alternative to ring attention (SURVEY.md §2.3 "Ring attention /
Ulysses") for long-context forwards: instead of rotating KV blocks
around the ring (n-1 ppermute hops), ONE all-to-all reshards q/k/v from
sequence-sharded [B, H, S/n, D] to head-sharded [B, H/n, S, D], each
device runs ordinary full-sequence attention over its head group, and a
second all-to-all reshards back. Preferable when n is large (2 ICI
collectives instead of n-1 hops) and H is divisible by the axis; ring
wins when heads are scarce or memory for the full-S KV per device is
tight — which is why both ship.

Same drop-in ``attn_impl`` contract as ``parallel.ring.ring_attention``;
oracle-tested against ``attention_xla`` on the virtual mesh.
"""

from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from copilot_for_consensus_tpu.analysis.contracts import checkable
from copilot_for_consensus_tpu.ops.attention import attention_xla


def _ulysses_shard(q, k, v, kv_lengths, *, axis_name: str, causal: bool,
                   window: int):
    """Per-shard body. q/k/v: [B, H, S_loc, D] → attention over the full
    sequence for H/n of the heads, resharded back."""
    # seq-sharded → head-sharded: split heads (axis 1) across the mesh
    # axis, concatenate the gathered sequence blocks (axis 2).
    def to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    # Full sequence is local now: the standard masked attention applies
    # (global positions are just 0..S-1).
    out = attention_xla(qh, kh, vh, causal=causal, window=window,
                        kv_lengths=kv_lengths)
    return to_seq(out)


def ulysses_attention(
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
    ``axis``. Heads must divide by the axis size; GQA kv heads are
    expanded first (head groups must align across q/k/v for the
    all-to-all to pair them)."""
    from copilot_for_consensus_tpu.ops.attention import _gqa_expand

    hq = q.shape[1]
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(
            f"sequence {q.shape[2]} not divisible by {axis}={n}")
    if hq % n:
        raise ValueError(
            f"heads {hq} not divisible by {axis}={n}; use ring attention")
    spec = P(None, None, axis, None)
    fn = shard_map(
        functools.partial(_ulysses_shard, axis_name=axis, causal=causal,
                          window=int(window)),
        mesh=mesh, in_specs=(spec, spec, spec, P()), out_specs=spec,
    )
    return fn(q, k, v, kv_lengths)


def make_ulysses_attention(mesh: Mesh, axis: str = "sp"):
    """Bind mesh/axis → a callable usable as ``attn_impl`` in the model
    forward passes, interchangeable with ``make_ring_attention``."""
    return functools.partial(ulysses_attention, mesh=mesh, axis=axis)


# ---------------------------------------------------------------------------
# shardcheck contracts (analysis/shardcheck.py)
# ---------------------------------------------------------------------------


@checkable("ulysses-attention")
def _shardcheck_ulysses_attention():
    """Trace the double all-to-all under the real sp mesh with the
    module's DEFAULT axis binding: the all_to_all collectives in
    ``_ulysses_shard`` must name an axis the mesh has, heads must
    divide by it (the head↔sequence reshard pairs head groups across
    ranks), and the sequence must divide for the seq-sharded specs."""
    import jax.numpy as jnp

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
        fn=functools.partial(ulysses_attention, mesh=mesh),
        args=(q, kv, kv),
        kwargs={"kv_lengths": S((b,), jnp.int32)},
        mesh=mesh,
    )
