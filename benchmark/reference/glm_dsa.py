"""Plain reference for a GLM-5-style decoder (``model_type``
``glm_moe_dsa``): latent attention (MLA) that reads a LEARNED SELECTION
of the earlier positions (a lightning indexer and a top-k: DSA, as in
DeepSeek-V3.2's published inference code), sparse experts chosen by
sigmoid scores beside a shared expert, a plain single-stream residual,
plain rotary frequencies.

With d = ``hidden_size``, H heads, r_q / r = ``q_lora_rank`` /
``kv_lora_rank``, d_n / d_r / d_v = ``qk_nope_head_dim`` /
``qk_rope_head_dim`` / ``v_head_dim``, H_I index heads of D_I =
``index_head_dim``, K = ``index_topk``, E experts of which k a token,
eps = ``rms_norm_eps``, ``RMS_g(x) = x / sqrt(mean(x^2) + eps) * g``,
per position t with ``x = RMS_g(h_t)``:

* queries: ``c_q = RMS(x W_qa)``, ``[q_n ; q_r] = c_q W_qb`` per head,
  ``q_r`` rotated at t (interleaved pairs, ``theta ** (-2i / d_r)``, no
  scaling); state: ``[c' ; k'] = x W_kva``, ``c = RMS(c')``, ``k_r =
  rope(k')``; ``[k_n ; v] = c W_kvb`` per head; scores ``(q_n k_n + q_r
  k_r) / sqrt(d_n + d_r)``.
* indexer: ``q^I_j = c_q W^I_q,j`` (D_I), its FIRST d_r values rotated;
  ``k^I = LN(x W^I_k)`` (LayerNorm with gain and bias, eps 1e-6), its
  first d_r values rotated; ``w = x W^I_w`` (H_I);
  ``I[t, s] = D_I^-1/2 H_I^-1/2 sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])``.
  ``S_t`` = the K positions s <= t of largest ``I[t, s]`` (all of them
  while t < K; of equal scores the lower position).
* attention: softmax over ``s in S_t`` only, then ``W_o``;
  ``h += W_o [o_1 .. o_H]``.
* feed-forward: a SwiGLU (the first ``first_k_dense_replace`` layers),
  else ``s_e = sigmoid(x w_e)`` over the router's full width, the k
  experts with the largest ``s_e + bias_e``, ``g_e =
  routed_scaling_factor s_e / sum_chosen s``, ``h += sum_{e chosen, e
  held} g_e SwiGLU_e(x) + SwiGLU_shared(x)``: the weights hold the
  experts ``[first, first + count)`` of the router's width (``dims
  ["held"]``), and what the absent ones would add is left out.
* output: ``RMS_f``, the head over the vocabulary slice the weights
  hold.

Here that is the whole sequence at once, one layer at a time: the
selection as one boolean matrix ``[S, S]`` (index scores query block by
query block, ``jax.lax.top_k`` a row, which keeps the lower position of
equal scores), every position's keys and values expanded (no latent
cache, no index-key cache, no absorbed form), one masked score matrix
per head, query block by query block; the experts by plain indexing.
``jax.numpy`` in float32 at ``highest`` matmul precision; it imports
nothing of the program, routes and selects by its own scores. It reads
the benchmark's seeded weights (an int8 matrix is dequantized ``q *
scale``; a plain float matrix is taken as it is).

``lower`` computes the same pass in a precision below the one the
configuration states, as the control of the correctness check:
``"int4"`` re-quantizes every int8 matrix to 4 bits per weight,
``"fp8state"`` rounds what a smaller cache would hold (each position's
``[c ; k_r]`` AND its index key) to float8_e4m3fn before anything
reads them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: sequences are padded after their last token (nothing before it
#: changes: a position sees no later one) to a multiple of this
PAD_TO = 1024
#: queries scored at once against all keys, per head
Q_ROWS = 1024
HEAD_ROWS = 256
#: columns of the output matrix dequantized at once
HEAD_COLS = 16384
#: an expert's tokens are padded (with rows of weight zero) to this
#: times a power of two, so that a handful of shapes compile
EXPERT_ROWS = 256
INDEX_NORM_EPS = 1e-6
LOWERS = ("int4", "fp8state")

ATTN = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
        "wkv_b", "wo", "wq_idx", "wk_idx", "k_idx_gain", "k_idx_bias",
        "w_idx")
FFN = ("ffn_norm", "w_gate", "w_up", "w_down")


def _deq(leaf, lower):
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    q = leaf["q"].astype(jnp.float32)
    scale = leaf["scale"].astype(jnp.float32)
    if lower == "int4":
        q = jnp.clip(jnp.round(q * (7.0 / 127.0)), -7, 7)
        scale = scale * (127.0 / 7.0)
    return q * scale


def _take(leaf, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        leaf)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _layer_norm(x, g, b):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + INDEX_NORM_EPS) \
        * g.astype(jnp.float32) + b.astype(jnp.float32)


def _swiglu(x, gate, up, down):
    """Row block by row block: the widest layer's hidden activations
    of a whole sequence are never held at once."""
    def block(rows):
        return (jax.nn.silu(rows @ gate) * (rows @ up)) @ down

    if x.shape[0] % Q_ROWS:
        return block(x)
    return jax.lax.map(block, x.reshape(-1, Q_ROWS, x.shape[1])).reshape(
        x.shape[0], -1)


def inv_freq(dims: dict) -> np.ndarray:
    dim = dims["qk_rope_head_dim"]
    base = float(dims["rope_parameters"]["rope_theta"])
    return (base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def _rotate(x, freq):
    """x ``[S, ..., d_r]``, position = row: pairs ``(2i, 2i + 1)``."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape(s, *([1] * (x.ndim - 2)), -1)
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _rows(s: int) -> int:
    return Q_ROWS if s % Q_ROWS == 0 else s


def _selection(c_q, hid, p, freq, dims, lower, margins=False):
    """``sel[t, s]``: does position t read position s? ``[S, S]``
    bool. ``margins``: also ``[S, S]`` float32, how far each score
    stands from its row's k-th largest, in units of the row's spread
    (the standard deviation of the scores the row sees): what a tool
    holds a differing choice against."""
    hi, di = dims["index_n_heads"], dims["index_head_dim"]
    dr, topk = dims["qk_rope_head_dim"], dims["index_topk"]
    s = hid.shape[0]
    q = (c_q @ _deq(p["wq_idx"], lower)).reshape(s, hi, di)
    q = jnp.concatenate([_rotate(q[..., :dr], freq), q[..., dr:]], axis=-1)
    k = _layer_norm(hid @ _deq(p["wk_idx"], lower), p["k_idx_gain"],
                    p["k_idx_bias"])
    k = jnp.concatenate([_rotate(k[:, :dr], freq), k[:, dr:]], axis=-1)
    if lower == "fp8state":
        k = k.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    w = hid @ p["w_idx"].astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
    pos = jnp.arange(s)
    rows = _rows(s)
    kk = min(topk, s)

    def block(at):
        q_b = jax.lax.dynamic_slice_in_dim(q, at, rows)      # [Q, hi, di]
        w_b = jax.lax.dynamic_slice_in_dim(w, at, rows)

        def head(acc, j):
            dots = q_b[:, j] @ k.T                           # [Q, S]
            return acc + w_b[:, j, None] * jax.nn.relu(dots), None

        score, _ = jax.lax.scan(head, jnp.zeros((rows, s), jnp.float32),
                                jnp.arange(hi))
        t = at + jnp.arange(rows)
        seen = pos[None, :] <= t[:, None]
        vals, idx = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), kk)
        # top_k keeps the lower column of equal values: the set is all
        # above the k-th value and, at it, the columns up to the last
        # one top_k took
        thr = vals[:, -1:]
        last = jnp.max(jnp.where(vals == thr, idx, -1), axis=-1,
                       keepdims=True)
        took = (score > thr) | ((score == thr) & (pos[None, :] <= last))
        sel = seen & (took | (t[:, None] < kk))
        if not margins:
            return sel, None
        n_seen = jnp.sum(seen, axis=-1, keepdims=True)
        mean = jnp.sum(jnp.where(seen, score, 0.0), axis=-1,
                       keepdims=True) / n_seen
        var = jnp.sum(jnp.where(seen, (score - mean) ** 2, 0.0), axis=-1,
                      keepdims=True) / n_seen
        return sel, (score - thr) * jax.lax.rsqrt(var + 1e-30)

    sel, margin = jax.lax.map(block, jnp.arange(0, s, rows))
    return sel.reshape(s, s), None if margin is None \
        else margin.reshape(s, s)


@functools.partial(jax.jit, static_argnames=("dims_key", "lower", "dense",
                                             "keep", "margins"),
                   donate_argnums=(0,))
def _attention(x, layer, li, freq, *, dims_key, lower, dense=False,
               keep=False, margins=False):
    """The attention sublayer: ``x + W_o [o_1 .. o_H]`` and, ``keep``,
    the selection it read (else nothing: at 32,768 positions the
    matrix is a gigabyte that the feed-forward part has no room
    beside)."""
    dims = dict(dims_key)
    h, r = dims["num_attention_heads"], dims["kv_lora_rank"]
    dn, dr = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
    dv, eps = dims["v_head_dim"], dims["rms_norm_eps"]
    s = x.shape[0]
    p = {k: _take(layer[k], li) for k in ATTN}
    with jax.default_matmul_precision("highest"):
        hid = _rms(x, p["attn_norm"], eps)
        c_q = _rms(hid @ _deq(p["wq_a"], lower), p["q_norm"], eps)
        kv = hid @ _deq(p["wkv_a"], lower)
        c = _rms(kv[:, :r], p["kv_norm"], eps)
        k_r = _rotate(kv[:, r:], freq)
        if lower == "fp8state":
            c, k_r = (a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                      for a in (c, k_r))
        pos = jnp.arange(s)
        sel, margin = (pos[None, :] <= pos[:, None], None) if dense \
            else _selection(c_q, hid, p, freq, dims, lower, margins)
        scale = (dn + dr) ** -0.5
        rows = _rows(s)
        # head by head, so that no array holds all heads' keys
        w_q = _deq(p["wq_b"], lower).reshape(-1, h, dn + dr)
        w_kv = _deq(p["wkv_b"], lower).reshape(r, h, dn + dv)
        w_o = _deq(p["wo"], lower).reshape(h, dv, -1)

        def one_head(y, mats):
            wq_h, wkv_h, wo_h = mats
            q = c_q @ wq_h                                  # [S, dn + dr]
            q = jnp.concatenate(
                [q[:, :dn], _rotate(q[:, dn:], freq)], axis=-1)
            wide = c @ wkv_h                                # [S, dn + dv]
            k = jnp.concatenate([wide[:, :dn], k_r], axis=-1)
            v = wide[:, dn:]

            def block(at):
                q_b = jax.lax.dynamic_slice_in_dim(q, at, rows)
                sel_b = jax.lax.dynamic_slice_in_dim(sel, at, rows)
                sc = q_b @ k.T * scale
                return jax.nn.softmax(jnp.where(sel_b, sc, -jnp.inf),
                                      axis=-1) @ v

            o = jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, dv)
            return y + o @ wo_h, None

        y, _ = jax.lax.scan(
            one_head, jnp.zeros((s, x.shape[-1]), jnp.float32),
            (w_q.transpose(1, 0, 2), w_kv.transpose(1, 0, 2), w_o))
        return x + y, sel if keep else None, margin


@functools.partial(jax.jit, static_argnames=("dims_key", "lower", "moe"))
def _ffn_open(x, layer, li, *, dims_key, lower, moe):
    """The feed-forward sublayer up to the routed experts: its input,
    the SwiGLU (dense or shared) and, ``moe``, the chosen experts and
    their gates."""
    dims = dict(dims_key)
    p = {k: _take(layer[k], li) for k in FFN}
    with jax.default_matmul_precision("highest"):
        hid = _rms(x, p["ffn_norm"], dims["rms_norm_eps"])
        y = _swiglu(hid, _deq(p["w_gate"], lower), _deq(p["w_up"], lower),
                    _deq(p["w_down"], lower))
        if not moe:
            return hid, y, None, None
        scores = jax.nn.sigmoid(
            hid @ _take(layer["router"], li).astype(jnp.float32))
        _, chosen = jax.lax.top_k(
            scores + _take(layer["e_bias"], li).astype(jnp.float32),
            dims["num_experts_per_tok"])
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = dims["routed_scaling_factor"] * picked \
            / picked.sum(axis=-1, keepdims=True)
        return hid, y, chosen, gates


@functools.partial(jax.jit, static_argnames=("lower",),
                   donate_argnums=(0,))
def _one_expert(y, hid, rows, weights, layer, li, e, *, lower):
    """``y[rows] += weights * SwiGLU_e(hid[rows])``, e counted among
    the experts the weights hold; padding rows carry weight zero."""
    mats = [_deq(_take(_take(layer[k], li), e), lower)
            for k in ("we_gate", "we_up", "we_down")]
    with jax.default_matmul_precision("highest"):
        out = _swiglu(hid[rows], *mats)
    return y.at[rows].add(weights[:, None] * out)


def routed_part(y, hid, chosen, gates, layer, li, lower, held, real=None):
    """Add to ``y`` ``[S, d]`` the routed experts' terms for ``hid``,
    expert by expert: the tokens that chose it, found on the host.
    ``held = (first, count)``: the experts the weights hold, whose
    terms alone exist; ``real``: only the first so many tokens' (what
    follows is padding, which no real position sees)."""
    chosen_h, gates_h = np.asarray(chosen)[:real], np.asarray(gates)[:real]
    for e in np.unique(chosen_h):
        if not held[0] <= e < held[0] + held[1]:
            continue
        tok, which = np.nonzero(chosen_h == e)
        size = EXPERT_ROWS
        while size < len(tok):
            size *= 2
        pad = size - len(tok)
        rows = np.concatenate([tok, np.zeros(pad, tok.dtype)])
        wts = np.concatenate([gates_h[tok, which],
                              np.zeros(pad, gates_h.dtype)])
        y = _one_expert(y, hid, jnp.asarray(rows, jnp.int32),
                        jnp.asarray(wts, jnp.float32), layer,
                        jnp.int32(li), jnp.int32(e - held[0]), lower=lower)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(rows, final_norm, lm_head, at, *, eps, lower):
    with jax.default_matmul_precision("highest"):
        cols = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(
                a, at, min(HEAD_COLS, a.shape[-1]), axis=-1), lm_head)
        return _rms(rows, final_norm, eps) @ _deq(cols, lower)


def _dims_key(dims: dict):
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
            "index_n_heads", "index_head_dim", "index_topk",
            "num_experts_per_tok", "routed_scaling_factor")
    return tuple((k, dims[k]) for k in keys)


def held_of(dims: dict) -> tuple[int, int]:
    """(first, count) of the routed experts the weights hold, of the
    router's ``dims["held"]["router_experts"]``."""
    return int(dims["held"]["first_expert"]), int(dims["n_routed_experts"])


def padded_len(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


def hidden_states(weights: dict, dims: dict, tokens,
                  lower: str | None = None, pad_to: int = 0,
                  routed: list | None = None, selected: list | None = None,
                  held: tuple | None = None, dense: bool = False,
                  margins: list | None = None):
    """The stream after the last layer, ``[padded S, d]``. ``routed``
    collects each expert layer's chosen experts ``[padded S, k]``,
    ``selected`` each layer's selection ``[padded S, padded S]``, in
    order. ``held`` overrides the configuration's share (the tests add
    the shares up); ``dense``: every position reads every earlier one
    (what ``index_topk`` >= the length must equal); ``margins``
    collects each layer's ``_selection`` margins beside ``selected``."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    ids = np.zeros((max(padded_len(n), pad_to),), np.int32)
    ids[:n] = tokens
    x = weights["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
    key = _dims_key(dims)
    freq = jnp.asarray(inv_freq(dims))
    held = held or held_of(dims)
    for name in ("dense", "moe"):
        stack = weights.get(name)
        if stack is None:
            continue
        for li in range(stack["attn_norm"].shape[0]):
            at = jnp.int32(li)
            x, sel, margin = _attention(
                x, stack, at, freq, dims_key=key, lower=lower, dense=dense,
                keep=selected is not None, margins=margins is not None)
            # one stage at a time on the device: a stage dispatched while
            # the last still runs is given its buffers beside the last
            # one's, and a 32,768-position pass beside a live engine
            # (tools/multi.py) has no room for both
            x.block_until_ready()
            if selected is not None:
                selected.append(np.asarray(sel))
            if margins is not None:
                margins.append(np.asarray(margin))
            hid, y, chosen, gates = _ffn_open(
                x, stack, at, dims_key=key, lower=lower,
                moe=name == "moe")
            if name == "moe":
                y = routed_part(y, hid, chosen, gates, stack, li, lower,
                                held, real=n)
                if routed is not None:
                    routed.append(np.asarray(chosen))
            x = (x + y).block_until_ready()
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
            rows, weights["final_norm"], weights["lm_head"],
            jnp.int32(c0), eps=dims["rms_norm_eps"],
            lower=lower))[:len(at)]
    return out
