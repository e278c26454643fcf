"""Plain reference for a Mistral-style decoder (pre-norm blocks, RMS
norm, rotary embeddings in the rotate-half convention, grouped-query
causal attention with an optional sliding window, SwiGLU feed-forward,
untied output head), as published for mistralai/Mistral-7B-v0.1.

``jax.numpy`` and float32 only, ``highest`` matmul precision, no cache,
no batching, no kernels; it imports nothing of the program. It reads
the benchmark's own seeded weights (``harness/weights.py``): an int8
matrix is dequantized to float32 (``q * scale``) one matrix at a time
and one layer at a time, so it fits beside a serving engine.

``lower`` computes the same forward pass in a precision below the one
the configurations state, as the control of the correctness check:
``"int4"`` re-quantizes every int8 matrix to 4 bits per weight,
``"fp8kv"`` rounds keys and values to float8_e4m3fn as a smaller
cache would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: sequences are padded (after their last token: causal, so nothing
#: before it changes) to a multiple of this; a caller that replays
#: several passes ``pad_to`` the longest, so that a run compiles the
#: layer for one length (2304 = prompt 2048 + 256 served)
PAD_TO = 768
HEAD_ROWS = 256
#: the controls ``lower`` can compute (``harness/correct.py``)
LOWERS = ("int4", "fp8kv")


def _deq(leaf, lower):
    q = leaf["q"].astype(jnp.float32)
    scale = leaf["scale"].astype(jnp.float32)
    if lower == "int4":
        q = jnp.clip(jnp.round(q * (7.0 / 127.0)), -7, 7)
        scale = scale * (127.0 / 7.0)
    return q * scale


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x: [S, H, Dh]; rotate-half pairs (i, i + Dh/2)."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims_key", "lower"))
def _layer(x, layers, li, *, dims_key, lower):
    dims = dict(dims_key)
    hq, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    dh, eps = dims["head_dim"], dims["rms_norm_eps"]
    theta, window = dims["rope_theta"], dims["sliding_window"]
    s = x.shape[0]
    take = lambda leaf: jax.tree.map(  # noqa: E731
        lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
        leaf)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, take(layers["attn_norm"]), eps)
        q = (h @ _deq(take(layers["wq"]), lower)).reshape(s, hq, dh)
        k = (h @ _deq(take(layers["wk"]), lower)).reshape(s, hkv, dh)
        v = (h @ _deq(take(layers["wv"]), lower)).reshape(s, hkv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        if lower == "fp8kv":
            k = k.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            v = v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        pos = jnp.arange(s)
        mask = pos[None, :] <= pos[:, None]
        if window:
            mask &= pos[None, :] > pos[:, None] - window
        group = hq // hkv

        def one_group(args):
            qg, kg, vg = args              # [S, group, Dh], [S, Dh] x2
            sc = jnp.einsum("sgd,td->gst", qg, kg) * dh ** -0.5
            sc = jnp.where(mask[None], sc, -jnp.inf)
            return jnp.einsum("gst,td->sgd", jax.nn.softmax(sc, -1), vg)

        o = jax.lax.map(one_group, (
            q.reshape(s, hkv, group, dh).transpose(1, 0, 2, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        o = o.transpose(1, 0, 2, 3).reshape(s, hq * dh)
        x = x + o @ _deq(take(layers["wo"]), lower)
        h = _rms(x, take(layers["ffn_norm"]), eps)
        gate = jax.nn.silu(h @ _deq(take(layers["w_gate"]), lower))
        up = h @ _deq(take(layers["w_up"]), lower)
        return x + (gate * up) @ _deq(take(layers["w_down"]), lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, lm_head, *, eps, lower):
    with jax.default_matmul_precision("highest"):
        return _rms(x, final_norm, eps) @ _deq(lm_head, lower)


def _dims_key(dims: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "sliding_window")
    return tuple((k, dims[k]) for k in keys)


def padded_len(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


def logits_at(weights: dict, dims: dict, tokens, positions,
              lower: str | None = None, pad_to: int = 0) -> np.ndarray:
    """Float32 logits [len(positions), vocab] that follow
    ``tokens[: p + 1]`` for each p in ``positions``."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = max(padded_len(n), pad_to)
    ids = np.zeros((padded,), np.int32)
    ids[:n] = tokens
    x = weights["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
    key = _dims_key(dims)
    for li in range(dims["num_hidden_layers"]):
        x = _layer(x, weights["layers"], jnp.int32(li), dims_key=key,
                   lower=lower)
    # the head too compiles for one shape: positions are padded to a
    # multiple of HEAD_ROWS (every served length would be a program)
    at = np.asarray(positions, np.int32)
    fill = -len(at) % HEAD_ROWS
    rows = x[jnp.asarray(np.concatenate([at, np.repeat(at[-1:], fill)]))]
    logits = _head(rows, weights["final_norm"], weights["lm_head"],
                   eps=dims["rms_norm_eps"], lower=lower)
    return np.asarray(logits[:len(at)])
