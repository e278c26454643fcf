"""Plain reference for a ``cohere2_moe``-style decoder: window and
global layers in one model, grouped queries, a parallel attention +
experts block on one LayerNorm, sigmoid-routed experts beside averaged
shared ones, a tied head.

With d = ``hidden_size``, H query heads and H_kv key/value heads of D =
``head_dim`` (G = H / H_kv queries a group), W = ``sliding_window``, E
routed experts of which k a token, n_s shared experts, all of width f =
``intermediate_size``, eps = ``layer_norm_eps``, ``LN_g(x) = (x -
mean(x)) / sqrt(var(x) + eps) * g`` (no bias), per position t and layer
l with ``h = LN_g(x_t)`` (ONE norm a layer: ``use_parallel_block``):

* ``q_j = h W_q,j`` (j < H), ``k_i = h W_k,i``, ``v_i = h W_v,i`` (i <
  H_kv); head j reads key/value head ``j // G``; scores ``q . k /
  sqrt(D)``.
* ``layer_types[l] == "sliding_attention"``: q and k rotated at t
  (``rope_gptj``: interleaved pairs ``(2i, 2i + 1)``, ``theta ** (-2i /
  D)``, all D values), and t attends to s with ``t - W < s <= t``;
  ``"full_attention"``: no positional encoding at all, every s <= t.
  ``a = [o_1 .. o_H] W_o``.
* experts on the SAME h: ``s_e = sigmoid(h w_e)`` over the router's
  full width, the k largest chosen (of equal scores the lower expert),
  ``g_e = s_e / sum_chosen s``; ``routed = sum_{e chosen, e held} g_e
  SwiGLU_e(h)``: the weights hold the experts ``[first, first + count)``
  of the router's width (``dims["held"]``), and what the absent ones
  would add is left out. ``shared = mean_{j < n_s} SwiGLU_j(h)``
  (``shared_expert_combination_strategy`` ``average``; ``sum`` adds
  them), shared expert j in columns ``[j f, (j + 1) f)`` of the shared
  gate and up matrices and the same rows of the down matrix.
* ``x' = x + a + routed + shared``; after the last layer ``logits =
  logit_scale * LN_f(x) E^T`` with the embedding matrix E (tied), over
  the vocabulary slice the weights hold.

Here that is the whole sequence at once, one layer at a time: the
window as one boolean matrix ``[S, S]``, every position's keys and
values computed once (no cache, no ring), one masked score matrix per
query head, query block by query block; the experts by plain indexing.
``jax.numpy`` in float32 at ``highest`` matmul precision; it imports
nothing of the program, and routes by its own scores. It reads the
benchmark's seeded weights (an int8 matrix is dequantized ``q *
scale``; a plain float matrix is taken as it is). The helpers that know
nothing of an architecture (dequantizing a leaf, a SwiGLU row block by
row block, one expert's tokens by indexing, padding) are
``reference/glm_dsa.py``'s.

``lower`` computes the same pass in a precision below the one the
configuration states, as the control of the correctness check:
``"int4"`` re-quantizes every int8 matrix to 4 bits per weight,
``"fp8state"`` rounds what a smaller cache would hold (each position's
keys, after their rotation, and values) to float8_e4m3fn before
anything reads them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.glm_dsa import (
    HEAD_COLS,
    HEAD_ROWS,
    _deq,
    _rotate,
    _rows,
    _swiglu,
    _take,
    padded_len,
    routed_part,
)

LOWERS = ("int4", "fp8state")

LAYER = ("norm", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
         "router")


def _ln(x, g, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def inv_freq(dims: dict) -> np.ndarray:
    dim = dims["head_dim"]
    base = float(dims["rope_theta"])
    return (base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def held_of(dims: dict) -> tuple[int, int]:
    """(first, count) of the routed experts the weights hold, of the
    router's ``dims["held"]["router_experts"]``."""
    return int(dims["held"]["first_expert"]), int(dims["num_experts"])


def _dims_key(dims: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "layer_norm_eps", "num_experts_per_tok",
            "num_shared_experts", "shared_expert_combination_strategy")
    return tuple((k, dims[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("dims_key", "lower", "full"))
def _layer_open(x, layers, li, freq, *, dims_key, lower, full):
    """One layer up to the routed experts: its normed input, ``x + a +
    shared``, the chosen experts and their gates."""
    dims = dict(dims_key)
    h, hkv, dh = (dims["num_attention_heads"], dims["num_key_value_heads"],
                  dims["head_dim"])
    n_s = dims["num_shared_experts"]
    s, d = x.shape
    p = {k: _take(layers[k], li) for k in LAYER}
    with jax.default_matmul_precision("highest"):
        hid = _ln(x, p["norm"], dims["layer_norm_eps"])
        k = (hid @ _deq(p["wk"], lower)).reshape(s, hkv, dh)
        v = (hid @ _deq(p["wv"], lower)).reshape(s, hkv, dh)
        if not full:
            k = _rotate(k, freq)
        if lower == "fp8state":
            k, v = (a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                    for a in (k, v))
        pos = jnp.arange(s)
        sees = pos[None, :] <= pos[:, None]
        if not full:
            sees &= pos[None, :] > pos[:, None] - dims["sliding_window"]
        rows = _rows(s)
        w_q = _deq(p["wq"], lower).reshape(d, h, dh).transpose(1, 0, 2)
        w_o = _deq(p["wo"], lower).reshape(h, dh, d)
        k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

        # head by head, so that no array holds all heads' scores
        def one_head(y, mats):
            wq_h, wo_h, j = mats
            q = hid @ wq_h                                  # [S, D]
            if not full:
                q = _rotate(q, freq)
            k_h, v_h = k[j // (h // hkv)], v[j // (h // hkv)]

            def block(at):
                q_b = jax.lax.dynamic_slice_in_dim(q, at, rows)
                sees_b = jax.lax.dynamic_slice_in_dim(sees, at, rows)
                sc = q_b @ k_h.T * dh ** -0.5
                return jax.nn.softmax(jnp.where(sees_b, sc, -jnp.inf),
                                      axis=-1) @ v_h

            o = jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, dh)
            return y + o @ wo_h, None

        y, _ = jax.lax.scan(one_head, jnp.zeros((s, d), jnp.float32),
                            (w_q, w_o, jnp.arange(h)))
        gate, up, down = (_deq(p[name], lower)
                          for name in ("w_gate", "w_up", "w_down"))
        f = gate.shape[1] // n_s
        shared = sum(_swiglu(hid, gate[:, j * f:(j + 1) * f],
                             up[:, j * f:(j + 1) * f],
                             down[j * f:(j + 1) * f])
                     for j in range(n_s))
        if dims["shared_expert_combination_strategy"] == "average":
            shared = shared / n_s
        scores = jax.nn.sigmoid(hid @ p["router"].astype(jnp.float32))
        _, chosen = jax.lax.top_k(scores, dims["num_experts_per_tok"])
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = picked / picked.sum(axis=-1, keepdims=True)
        return hid, x + y + shared, chosen, gates


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(rows, final_norm, emb, at, *, eps):
    with jax.default_matmul_precision("highest"):
        part = jax.lax.dynamic_slice_in_dim(
            emb, at, min(HEAD_COLS, emb.shape[0]), axis=0)
        return _ln(rows, final_norm, eps) @ part.astype(jnp.float32).T


def hidden_states(weights: dict, dims: dict, tokens,
                  lower: str | None = None, pad_to: int = 0,
                  routed: list | None = None, held: tuple | None = None,
                  parts: list | None = None):
    """The stream after the last layer, ``[padded S, d]``. ``routed``
    collects each layer's chosen experts ``[padded S, k]``, ``parts``
    each layer's (what every chip computes alike: ``x + a + shared``;
    the routed terms of the share) in order. ``held`` overrides the
    configuration's share (the tests add the shares up)."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    ids = np.zeros((max(padded_len(n), pad_to),), np.int32)
    ids[:n] = tokens
    x = weights["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
    key = _dims_key(dims)
    freq = jnp.asarray(inv_freq(dims))
    held = held or held_of(dims)
    layers = weights["layers"]
    for li in range(dims["num_hidden_layers"]):
        hid, y, chosen, gates = _layer_open(
            x, layers, jnp.int32(li), freq, dims_key=key, lower=lower,
            full=dims["layer_types"][li] == "full_attention")
        # one stage at a time on the device (reference/glm_dsa.py)
        y.block_until_ready()
        if routed is not None:
            routed.append(np.asarray(chosen))
        x = routed_part(y if parts is None else jnp.zeros_like(y), hid,
                        chosen, gates, layers, li, lower, held, real=n)
        if parts is not None:
            parts.append((np.asarray(y), np.asarray(x)))
            x = y + x
        x = x.block_until_ready()
    return x


def logits_at(weights: dict, dims: dict, tokens, positions,
              lower: str | None = None, pad_to: int = 0,
              **more) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of the next token
    after ``tokens[: p + 1]`` for each p in ``positions``."""
    x = hidden_states(weights, dims, tokens, lower, pad_to, **more)
    at = np.asarray(positions, np.int32)
    fill = -len(at) % HEAD_ROWS
    rows = x[jnp.asarray(np.concatenate([at, np.repeat(at[-1:], fill)]))]
    vocab = dims["vocab_size"]
    width = min(HEAD_COLS, vocab)
    out = np.empty((len(at), vocab), np.float32)
    for c0 in range(0, vocab, width):
        c0 = min(c0, vocab - width)
        out[:, c0:c0 + width] = np.asarray(_head(
            rows, weights["final_norm"], weights["tok_emb"],
            jnp.int32(c0), eps=dims["layer_norm_eps"]))[:len(at)]
    return float(dims["logit_scale"]) * out
