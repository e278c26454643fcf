"""Weight-only int8 quantization for serving.

Per-output-channel symmetric scales over the contraction axis (axis -2 of
every ``x @ W`` weight), so dequantization commutes with the matmul:
``(x @ q) * scale == x @ (q * scale)`` exactly. Weights live in HBM as
int8 (half the bytes of bf16 — decode is HBM-bandwidth-bound, so this is
both the memory fix that fits Mistral-7B-class models on a single 16GB
v5e chip and a ~2× decode-throughput lever). The cast to compute dtype
happens per scan-sliced layer, so the transient is one layer, never the
stacked tensor.

A quantized leaf is the dict ``{"q": int8, "scale": f32}`` (pytree-
transparent); ``layers.qmatmul`` dispatches on it, plain arrays pass
through unchanged.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
import jax.numpy as jnp

# Decoder leaves quantized by default: every matmul weight. Embedding
# gather and norms stay bf16 (tiny); the MoE router stays full precision
# (routing decisions are precision-sensitive and the weight is small).
DECODER_QUANT_LEAVES = (
    ("layers", "wq"), ("layers", "wk"), ("layers", "wv"), ("layers", "wo"),
    ("layers", "w_gate"), ("layers", "w_up"), ("layers", "w_down"),
    ("lm_head",),
)


def is_quantized(leaf: Any) -> bool:
    return (isinstance(leaf, dict) and "scale" in leaf
            and ("q" in leaf or "q4" in leaf))


def quant_kind(leaf: Any) -> str | None:
    """None for plain arrays, else "int8" / "int4"."""
    if not isinstance(leaf, dict) or "scale" not in leaf:
        return None
    if "q4" in leaf:
        return "int4"
    if "q" in leaf:
        return "int8"
    return None


# Process-wide switch for the fused Pallas int8 matmul. Sharded engines
# disable it (the kernel is not GSPMD-partitionable; the XLA dequant
# expression partitions naturally over tp). Process-global because model
# forwards are traced lazily from engine internals.
_PALLAS_QMATMUL = True

# Activation quantization mode for the decode matmuls. "weight_only"
# keeps activations bf16 (dequant-style matmuls); "a8" dynamically
# quantizes activations to int8 per row and uses the MXU's native
# int8×int8 path (W8A8/W4A8 kernels in ops/quant_matmul.py) — the
# weight bytes then go HBM → VMEM → MXU without a VPU widening pass.
_ACT_QUANT = "weight_only"


def set_pallas_qmatmul(enabled: bool) -> None:
    global _PALLAS_QMATMUL
    _PALLAS_QMATMUL = enabled


# Thread-local override so ONE engine can re-route ONE of its programs
# (e.g. long-extent int4 decode → XLA dequant) without flipping the
# process-wide flag under other engines: the flag is read at TRACE
# time, so holding the override around a jitted call bakes the route
# into that program only.
_PALLAS_TLS = threading.local()


@contextlib.contextmanager
def pallas_qmatmul_override(enabled: bool | None):
    """Force (or, with None, don't touch) the Pallas-qmatmul route for
    model code traced on this thread inside the block."""
    if enabled is None:
        yield
        return
    prev = getattr(_PALLAS_TLS, "value", None)
    _PALLAS_TLS.value = enabled
    try:
        yield
    finally:
        _PALLAS_TLS.value = prev


def pallas_qmatmul_enabled() -> bool:
    override = getattr(_PALLAS_TLS, "value", None)
    return _PALLAS_QMATMUL if override is None else override


def set_act_quant(mode: str) -> None:
    """"weight_only" (default) or "a8" (dynamic per-row int8
    activations into native int8 MXU dots — W8A8-class accuracy)."""
    if mode not in ("weight_only", "a8"):
        raise ValueError(f"unknown act-quant mode {mode!r}")
    global _ACT_QUANT
    _ACT_QUANT = mode


def act_quant_mode() -> str:
    return _ACT_QUANT


def quantize_tensor(w: jax.Array) -> dict[str, jax.Array]:
    """Symmetric int8 over axis -2 (the contraction axis of ``x @ W``)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


INT4_GROUP = 256   # rows per scale group; multiple of 256 (TPU lane tiling)


def dequant_int4(leaf: dict, dtype) -> jax.Array:
    """Materialize an int4 leaf back to ``dtype`` — the XLA fallback and
    einsum (MoE) path. Handles any leading batch/layer/expert dims:
    the contraction axis is -2 of the unpacked tensor and the group
    axis is -2 of the scale."""
    from copilot_for_consensus_tpu.ops.quant_matmul import unpack_int4

    q = unpack_int4(leaf["q4"])                     # [..., D, F]
    scale = leaf["scale"]                           # [..., G, F]
    d, g = q.shape[-2], scale.shape[-2]
    s = jnp.repeat(scale, d // g, axis=-2)
    return q.astype(dtype) * s.astype(dtype)


def quantize_tensor_int4(w: jax.Array,
                         group: int = INT4_GROUP) -> dict[str, jax.Array]:
    """Symmetric int4 with group-wise scales over the contraction axis.

    Four bits is too coarse for one scale per output channel, so each
    ``group`` rows of the contraction axis get their own scale row —
    the standard accuracy recovery for 4-bit weight-only quantization.
    Nibbles are packed two-per-int8-byte (``ops.quant_matmul.pack_int4``)
    so the serving dtype works around this JAX build's broken int4
    arrays and halves weight HBM again over int8."""
    from copilot_for_consensus_tpu.ops.quant_matmul import pack_int4

    *lead, d, f = w.shape
    group = min(group, d)          # small models: one group spans D
    if d % group:
        raise ValueError(f"contraction dim {d} not divisible by "
                         f"group {group}")
    wf = w.astype(jnp.float32).reshape(*lead, d // group, group, f)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 7.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -8, 7)
    q = q.reshape(*lead, d, f).astype(jnp.int8)
    return {"q4": pack_int4(q),
            "scale": scale.reshape(*lead, d // group, f)}


def fuse_int4_projections(params: dict) -> dict:
    """Fuse the int4 qkv and gate/up leaves into single wide leaves.

    Decode through the Pallas int4 kernels pays ~65 µs per kernel call
    (measured r3); 7 calls/layer lose the format's halved-bytes
    advantage. q/k/v share the input x, as do gate/up, so their packed
    nibbles and group scales concatenate along the OUTPUT axis into one
    ``wqkv`` [L, D/2, (Hq+2Hkv)·dh] and one ``w_gu`` [L, D/2, 2F] —
    4 calls/layer. ``layers._project_qkv`` / ``layers.swiglu`` split the
    fused product by column; packing along D is untouched, so per-group
    scales stay exact. Single-device serving only (the fused leaves have
    no sharding rules); callers gate on ``mesh is None``."""
    layers_t = params.get("layers", {})
    if "wqkv" in layers_t or "wq" not in layers_t:
        return params
    if quant_kind(layers_t["wq"]) != "int4" or \
            quant_kind(layers_t.get("w_gate")) != "int4":
        raise ValueError("fuse_int4_projections needs int4 leaves")
    if layers_t["w_gate"]["q4"].ndim != 3:
        # MoE expert leaves are [L, E, D/2, F]: moe_ffn dispatches per
        # expert by name and must keep w_gate/w_up — fusing (and
        # deleting) them breaks every MoE forward.
        raise ValueError(
            "fuse_int4_projections supports dense FFN leaves only; "
            "gate fusion on cfg.is_moe at the call site")

    def cat(*leaves):
        return {"q4": jnp.concatenate([l["q4"] for l in leaves], axis=-1),
                "scale": jnp.concatenate([l["scale"] for l in leaves],
                                         axis=-1)}

    fused = dict(layers_t)
    fused["wqkv"] = cat(layers_t["wq"], layers_t["wk"], layers_t["wv"])
    fused["w_gu"] = cat(layers_t["w_gate"], layers_t["w_up"])
    for k in ("wq", "wk", "wv", "w_gate", "w_up"):
        del fused[k]
    return {**params, "layers": fused}


def _get_path(tree: dict, path: tuple[str, ...]):
    node = tree
    for p in path:
        if not isinstance(node, dict) or p not in node:
            return None
        node = node[p]
    return node


def _set_path(tree: dict, path: tuple[str, ...], value) -> None:
    node = tree
    for p in path[:-1]:
        node = node[p]
    node[path[-1]] = value


def quantize_params(params: dict,
                    leaves: tuple[tuple[str, ...], ...] = DECODER_QUANT_LEAVES,
                    mode: str = "int8",
                    group: int = INT4_GROUP) -> dict:
    """Returns a copy of the param tree with the given leaves quantized
    (``mode``: "int8" per-channel or "int4" group-wise packed)."""
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    out = jax.tree.map(lambda x: x, params)  # shallow-ish structural copy
    for path in leaves:
        w = _get_path(params, path)
        if w is not None:
            _set_path(out, path,
                      quantize_tensor(w) if mode == "int8"
                      else quantize_tensor_int4(w, group))
    return out


def init_random_quantized(rng: jax.Array, cfg, dtype=jnp.bfloat16,
                          leaves: tuple[tuple[str, ...], ...] = DECODER_QUANT_LEAVES,
                          mode: str = "int8",
                          group: int = INT4_GROUP) -> dict:
    """Random decoder params with quantized leaves born int8 on-device.

    Serving benches need weights with the right shapes/dtypes, not trained
    values; materializing bf16 first and quantizing would transiently need
    2-3× the final HBM (what OOMs a 7B on a 16GB chip). Real checkpoints
    are quantized offline on the host (``quantize_params``) where RAM is
    plentiful. Shapes come from ``jax.eval_shape`` over the real init, so
    there is exactly one source of truth for the param tree.

    The whole tree is generated by ONE jitted program: per-leaf dispatch
    costs a full XLA compile each (13 leaves).
    """
    from copilot_for_consensus_tpu.models import decoder

    shapes = jax.eval_shape(
        lambda k: decoder.init_params(k, cfg, dtype=dtype), rng)
    quant_set = set(leaves)
    flat: list[tuple[tuple, Any]] = jax.tree_util.tree_flatten_with_path(
        shapes)[0]

    def build(path, aval, key):
        names = tuple(p.key for p in path)
        shape = aval.shape
        if names in quant_set:
            fan_in = shape[-2]
            if mode == "int4":
                # Random packed bytes: each nibble uniform in [-8, 7],
                # std ≈ 4.61; scale to ~1/sqrt(fan_in).
                g = min(group, fan_in)
                packed_shape = shape[:-2] + (shape[-2] // 2,) + shape[-1:]
                q4 = jax.random.randint(key, packed_shape, -128, 128,
                                        dtype=jnp.int32).astype(jnp.int8)
                scale_shape = shape[:-2] + (fan_in // g,) + shape[-1:]
                scale = jnp.full(scale_shape, fan_in ** -0.5 / 4.61,
                                 jnp.float32)
                return {"q4": q4, "scale": scale}
            q = jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)
            # uniform int8 has std ≈ 73.3; scale to ~1/sqrt(fan_in)
            scale_shape = shape[:-2] + (1,) + shape[-1:]
            scale = jnp.full(scale_shape, fan_in ** -0.5 / 73.3,
                             jnp.float32)
            return {"q": q, "scale": scale}
        if "norm" in names[-1]:
            return jnp.ones(shape, aval.dtype)
        if names[-1] == "tok_emb":
            fan_in = shape[-1]        # init_params scales embeds by d_model
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return (jax.random.truncated_normal(key, -2, 2, shape,
                                            jnp.float32)
                * fan_in ** -0.5).astype(aval.dtype)

    def build_all(key):
        keys = jax.random.split(key, len(flat))
        out: dict = {}
        for i, (path, aval) in enumerate(flat):
            names = tuple(p.key for p in path)
            node = out
            for n in names[:-1]:
                node = node.setdefault(n, {})
            node[names[-1]] = build(path, aval, keys[i])
        return out

    return jax.jit(build_all)(rng)


def quantize_logical_axes(axes: dict,
                          leaves: tuple[tuple[str, ...], ...] = DECODER_QUANT_LEAVES,
                          mode: str = "int8") -> dict:
    """Transform the logical-axes tree to match a quantized param tree.

    int8: the scale keeps every axis except the (size-1) contraction
    axis, which becomes None/replicated. int4: the packed q4 keeps the
    original axes (packed rows shard like the rows they encode); the
    scale's group axis is replicated — it can be size 1 (small models
    where one group spans the contraction axis) which a tp>1 mesh can't
    divide, and at ≤G×F×4 bytes the tensor is too small to matter."""
    out = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in axes.items()}
    for path in leaves:
        t = _get_path(axes, path)
        if t is not None:
            scale_axes = tuple(
                None if i == len(t) - 2 else a for i, a in enumerate(t))
            _set_path(out, path,
                      {"q4" if mode == "int4" else "q": t,
                       "scale": scale_axes})
    return out
