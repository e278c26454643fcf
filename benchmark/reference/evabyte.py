"""Plain reference for an EvaByte-style decoder (``model_type``
``evabyte``, ``attention_class`` ``eva``): pre-norm blocks whose RMS
gains are offsets from one, rotary embeddings (rotate-half) over the
whole head, EVA attention, SwiGLU, residual adds and output head in
float32, an output matrix of ``num_pred_heads`` x ``vocab_size``.

EVA attention, as the configuration file's ``assumed`` states it: with
W = ``window_size`` and C = ``chunk_size``, a query at position t sees
the positions j <= t of its own aligned window (j // W == t // W)
exactly, and every chunk c of C positions that lies wholly before that
window (C (c + 1) <= (t // W) W) through one summary key and value,

    k̄_c = Σ_m softmax_m(mu · k_m) k_m,   v̄_c = Σ_m softmax_m(phi · k_m) v_m

(m over the chunk, k the ROTATED keys, mu and phi one vector per head
and layer), exact and summary scores under one softmax.

Here that is a mask over the whole sequence: every position's key and
value, every chunk's summary, one score matrix ``[S, S + S / C]`` per
head. No cache, no window as a data structure, no batching, no
kernels; ``jax.numpy`` in float32 at ``highest`` matmul precision; it
imports nothing of the program. It reads the benchmark's own seeded
weights (an int8 matrix is dequantized ``q * scale``, one matrix of one
layer at a time; a plain float matrix is taken as it is).

``lower`` computes the same pass in a precision below the one the
configuration states, as the control of the correctness check:
``"int4"`` re-quantizes every int8 matrix to 4 bits per weight,
``"fp8state"`` rounds what a smaller cache would hold (the rotated keys
and the values that are attended exactly, and the summaries) to
float8_e4m3fn.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: sequences are padded after their last token (nothing before it
#: changes: a position sees no later one, and a chunk that holds
#: padding lies in or after the last real position's window, which no
#: real position sees summarized) to a multiple of this, itself a
#: multiple of any chunk size in use
PAD_TO = 1024
HEAD_ROWS = 256
LOWERS = ("int4", "fp8state")


def _deq(leaf, lower):
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    q = leaf["q"].astype(jnp.float32)
    scale = leaf["scale"].astype(jnp.float32)
    if lower == "int4":
        q = jnp.clip(jnp.round(q * (7.0 / 127.0)), -7, 7)
        scale = scale * (127.0 / 7.0)
    return q * scale


def _rms(x, g, eps, unit_offset):
    g = g.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g if unit_offset else g)


def _rope(x, theta):
    """x: [S, H, Dh]; rotate-half pairs (i, i + Dh/2)."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims_key", "lower"))
def _layer(x, layers, li, *, dims_key, lower):
    dims = dict(dims_key)
    h, dh, eps = dims["heads"], dims["head_dim"], dims["rms_norm_eps"]
    w, c = dims["window_size"], dims["chunk_size"]
    off = dims["norm_add_unit_offset"]
    s = x.shape[0]
    take = lambda leaf: jax.tree.map(  # noqa: E731
        lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
        leaf)
    with jax.default_matmul_precision("highest"):
        hid = _rms(x, take(layers["attn_norm"]), eps, off)
        q = (hid @ _deq(take(layers["wq"]), lower)).reshape(s, h, dh)
        k = (hid @ _deq(take(layers["wk"]), lower)).reshape(s, h, dh)
        v = (hid @ _deq(take(layers["wv"]), lower)).reshape(s, h, dh)
        q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
        mu = take(layers["mu"]).astype(jnp.float32)          # [H, Dh]
        phi = take(layers["phi"]).astype(jnp.float32)
        pos = jnp.arange(s)
        chunk = jnp.arange(s // c)
        exact = (pos[None, :] <= pos[:, None]) \
            & (pos[None, :] // w == pos[:, None] // w)          # [S, S]
        summed = (chunk[None, :] + 1) * c <= (pos[:, None] // w) * w
        mask = jnp.concatenate([exact, summed], axis=1)    # [S, S + S/C]

        def one_head(args):
            qh, kh, vh, mu_h, phi_h = args             # [S, Dh] x3, [Dh] x2
            kc = kh.reshape(s // c, c, dh)
            vc = vh.reshape(s // c, c, dh)
            k_sum = jnp.einsum("nc,ncd->nd",
                               jax.nn.softmax(kc @ mu_h, axis=-1), kc)
            v_sum = jnp.einsum("nc,ncd->nd",
                               jax.nn.softmax(kc @ phi_h, axis=-1), vc)
            if lower == "fp8state":
                kh, vh = _fp8(kh), _fp8(vh)
                k_sum, v_sum = _fp8(k_sum), _fp8(v_sum)
            sc = qh @ jnp.concatenate([kh, k_sum]).T * dh ** -0.5
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return p @ jnp.concatenate([vh, v_sum])

        o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                   k.transpose(1, 0, 2),
                                   v.transpose(1, 0, 2), mu, phi))
        o = o.transpose(1, 0, 2).reshape(s, h * dh)
        x = x + o @ _deq(take(layers["wo"]), lower)
        hid = _rms(x, take(layers["ffn_norm"]), eps, off)
        gate = jax.nn.silu(hid @ _deq(take(layers["w_gate"]), lower))
        up = hid @ _deq(take(layers["w_up"]), lower)
        return x + (gate * up) @ _deq(take(layers["w_down"]), lower)


@functools.partial(jax.jit, static_argnames=("eps", "off", "lower"))
def _head(x, final_norm, lm_head, *, eps, off, lower):
    with jax.default_matmul_precision("highest"):
        return _rms(x, final_norm, eps, off) @ _deq(lm_head, lower)


def _dims_key(dims: dict):
    heads = dims["num_attention_heads"]
    if dims["num_key_value_heads"] != heads:
        raise ValueError("evabyte reference: one key/value head per "
                         "query head")
    return (("heads", heads),
            ("head_dim", dims.get("head_dim")
             or dims["hidden_size"] // heads),
            ("rms_norm_eps", dims["rms_norm_eps"]),
            ("rope_theta", float(dims["rope_theta"])),
            ("window_size", dims["window_size"]),
            ("chunk_size", dims["chunk_size"]),
            ("norm_add_unit_offset", bool(dims["norm_add_unit_offset"])))


def padded_len(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


def all_head_logits(weights: dict, dims: dict, tokens, positions,
                    lower: str | None = None,
                    pad_to: int = 0) -> np.ndarray:
    """Float32 ``[len(positions), num_pred_heads * vocab]`` after
    ``tokens[: p + 1]`` for each p: columns ``[V i, V (i + 1))`` are
    prediction head i's, for the token i + 1 ahead."""
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
    at = np.asarray(positions, np.int32)
    fill = -len(at) % HEAD_ROWS
    rows = x[jnp.asarray(np.concatenate([at, np.repeat(at[-1:], fill)]))]
    logits = _head(rows, weights["final_norm"], weights["lm_head"],
                   eps=dims["rms_norm_eps"],
                   off=bool(dims["norm_add_unit_offset"]), lower=lower)
    return np.asarray(logits[:len(at)])


def logits_at(weights: dict, dims: dict, tokens, positions,
              lower: str | None = None, pad_to: int = 0) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of the NEXT token
    (prediction head 0, the one that is served) after
    ``tokens[: p + 1]`` for each p in ``positions``. An id the model
    does not have (a served token past the vocabulary) gets a column
    of -inf: it is never the reference's best."""
    vocab = dims["vocab_size"]
    out = all_head_logits(weights, dims, tokens, positions, lower,
                          pad_to)[:, :vocab]
    extra = int(np.max(tokens)) + 1 - vocab
    if extra > 0:
        out = np.concatenate(
            [out, np.full((len(out), extra), -np.inf, np.float32)], axis=1)
    return out
