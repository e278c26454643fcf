"""Plain reference for a Xing4.0-style decoder (``model_type``
``xing4_0``): latent attention (MLA), sparse experts chosen by sigmoid
scores beside a shared expert, a residual of ``hc_mult`` streams
(manifold-constrained hyper-connections), YaRN rotary frequencies.

With d = ``hidden_size``, n = ``hc_mult``, H heads, r = ``kv_lora_rank``,
d_n / d_r / d_v = ``qk_nope_head_dim`` / ``qk_rope_head_dim`` /
``v_head_dim``, E experts of which k a token, eps = ``rms_norm_eps``,
``RMS_g(x) = x / sqrt(mean(x^2) + eps) * g``, per token:

* the state between sublayers is ``X`` ``[n, d]`` (at the input the
  embedding n times; at the output the streams are summed, then
  ``RMS_f``, then the head). Around a sublayer F with its own ``Phi``
  ``[n d, n + n + n n]``, ``alpha`` ``[3]`` and ``b``:
  ``xt = vec(X) / sqrt(mean(vec(X)^2) + eps)``; ``m = xt Phi``;
  ``H_pre = sigmoid(alpha_0 m[:n] + b[:n])``;
  ``H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])``;
  ``H_res = SK(clip(alpha_2 mat(m[2n:]) + mat(b[2n:]), lo, hi))``, SK:
  ``A = exp(M)``, then ``hc_sinkhorn_iters`` times rows
  ``A / (rowsum + hc_eps)`` then columns ``A / (colsum + hc_eps)``;
  ``u = H_pre X``, ``y = F(RMS_g(u))``, ``X' = H_res X + H_post^T y``.
* attention: ``c_q = RMS(x W_qa)``, ``[q_n ; q_r] = c_q W_qb`` per head;
  ``[c ; k_r] = x W_kva``, ``c = RMS(c)``; ``[k_n ; v] = c W_kvb`` per
  head; ``q_r`` and the one ``k_r`` rotated at the absolute position
  (interleaved pairs, YaRN); scores ``s (q_n k_n + q_r k_r)``,
  ``s = (d_n + d_r)^-1/2 mscale^2``; causal softmax; ``W_o``.
* feed-forward: a SwiGLU (the first ``first_k_dense_replace`` layers),
  else ``s_e = sigmoid(x w_e)``, the k experts with the largest
  ``s_e + bias_e``, ``g_e = routed_scaling_factor s_e / sum_chosen s``,
  ``sum g_e SwiGLU_e(x) + SwiGLU_shared(x)``.

Here that is the whole sequence at once, one layer at a time: every
position's keys and values expanded (no latent cache, no absorbed
form), one causal score matrix per head, query block by query block;
the experts by plain indexing, the tokens that chose an expert gathered
on the host's say-so and multiplied by that expert's matrices, so a
pass costs k experts a token and not E. ``jax.numpy`` in float32 at
``highest`` matmul precision; it imports nothing of the program and
routes by its own scores. It reads the benchmark's seeded weights (an
int8 matrix is dequantized ``q * scale``, one matrix of one layer or
one expert at a time; a plain float matrix is taken as it is).

``lower`` computes the same pass in a precision below the one the
configuration states, as the control of the correctness check:
``"int4"`` re-quantizes every int8 matrix to 4 bits per weight,
``"fp8state"`` rounds what a smaller cache would hold (each position's
``[c ; k_r]``) to float8_e4m3fn before keys and values are expanded.
"""

from __future__ import annotations

import functools
import math

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
LOWERS = ("int4", "fp8state")

ATTN = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
        "wkv_b", "wo", "hc_attn_phi", "hc_attn_alpha", "hc_attn_bias")
FFN = ("ffn_norm", "w_gate", "w_up", "w_down", "hc_ffn_phi",
       "hc_ffn_alpha", "hc_ffn_bias")


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
    x = x * jax.lax.rsqrt(var + eps)
    return x if g is None else x * g.astype(jnp.float32)


def _swiglu(x, gate, up, down):
    """Row block by row block: the widest layer's hidden activations
    of a whole sequence are never held at once."""
    def block(rows):
        return (jax.nn.silu(rows @ gate) * (rows @ up)) @ down

    if x.shape[0] % Q_ROWS:
        return block(x)
    return jax.lax.map(block, x.reshape(-1, Q_ROWS, x.shape[1])).reshape(
        x.shape[0], -1)


def yarn_inv_freq(dims: dict) -> np.ndarray:
    dim, base = dims["qk_rope_head_dim"], float(dims["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = dims.get("rope_scaling") or {}
    if rs.get("type") != "yarn":
        return plain.astype(np.float32)
    orig = rs["original_max_position_embeddings"]
    # the dimension at which ``turns`` full turns fit the original
    # context: frequencies faster than beta_fast's keep their own
    # value, slower than beta_slow's are divided by the factor
    at = lambda turns: dim * math.log(orig / (turns * 2 * math.pi)) \
        / (2 * math.log(base))  # noqa: E731
    lo = max(math.floor(at(rs["beta_fast"])), 0)
    hi = min(math.ceil(at(rs["beta_slow"])), dim - 1)
    blend = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (plain / rs["factor"] * blend
            + plain * (1 - blend)).astype(np.float32)


def score_scale(dims: dict) -> float:
    s = (dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]) ** -0.5
    rs = dims.get("rope_scaling") or {}
    if rs.get("type") == "yarn" and rs.get("mscale_all_dim"):
        s *= (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1) ** 2
    return s


def _rotate(x, inv_freq):
    """x ``[S, ..., d_r]``, position = row: pairs ``(2i, 2i + 1)``."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape(s, *([1] * (x.ndim - 2)), -1)
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _hyper(x, phi, alpha, bias, dims):
    """x ``[S, n, d]`` → (H_pre ``[S, n]``, H_post ``[S, n]``, H_res
    ``[S, n, n]``)."""
    s, n, d = x.shape
    flat = x.reshape(s, n * d)
    m = _rms(flat, None, dims["rms_norm_eps"]) \
        @ phi.astype(jnp.float32).reshape(n * d, -1)
    m_pre, m_post, m_res = m[:, :n], m[:, n:2 * n], m[:, 2 * n:]
    pre = jax.nn.sigmoid(alpha[0] * m_pre + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m_post + bias[n:2 * n])
    a = jnp.exp(jnp.clip(
        (alpha[2] * m_res + bias[2 * n:]).reshape(s, n, n),
        dims["mhc_h_res_clamp_min"], dims["mhc_h_res_clamp_max"]))
    for _ in range(dims["hc_sinkhorn_iters"]):
        a = a / (a.sum(axis=2, keepdims=True) + dims["hc_eps"])
        a = a / (a.sum(axis=1, keepdims=True) + dims["hc_eps"])
    return pre, post, a


def _mix(x, y, post, res):
    return jnp.einsum("sij,sjd->sid", res, x) \
        + post[:, :, None] * y[:, None, :]


@functools.partial(jax.jit, static_argnames=("dims_key", "lower"),
                   donate_argnums=(0,))
def _attention(x, layer, li, inv_freq, *, dims_key, lower):
    dims = _key_dims(dims_key)
    h, r = dims["num_attention_heads"], dims["kv_lora_rank"]
    dn, dr = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
    dv, eps = dims["v_head_dim"], dims["rms_norm_eps"]
    s = x.shape[0]
    p = {k: _take(layer[k], li) for k in ATTN}
    with jax.default_matmul_precision("highest"):
        pre, post, res = _hyper(x, p["hc_attn_phi"], p["hc_attn_alpha"],
                                p["hc_attn_bias"], dims)
        hid = _rms(jnp.einsum("sn,snd->sd", pre, x), p["attn_norm"], eps)
        c_q = _rms(hid @ _deq(p["wq_a"], lower), p["q_norm"], eps)
        kv = hid @ _deq(p["wkv_a"], lower)
        c = _rms(kv[:, :r], p["kv_norm"], eps)
        k_r = _rotate(kv[:, r:], inv_freq)
        if lower == "fp8state":
            c, k_r = (a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                      for a in (c, k_r))
        pos = jnp.arange(s)
        scale = score_scale(dims)
        # head by head, so that no array holds all heads' keys: each
        # head's queries, keys and values from its own columns
        w_q = _deq(p["wq_b"], lower).reshape(-1, h, dn + dr)
        w_kv = _deq(p["wkv_b"], lower).reshape(r, h, dn + dv)
        w_o = _deq(p["wo"], lower).reshape(h, dv, -1)

        def one_head(y, mats):
            wq_h, wkv_h, wo_h = mats
            q = c_q @ wq_h                                  # [S, dn + dr]
            q = jnp.concatenate(
                [q[:, :dn], _rotate(q[:, dn:], inv_freq)], axis=-1)
            wide = c @ wkv_h                                # [S, dn + dv]
            k = jnp.concatenate([wide[:, :dn], k_r], axis=-1)
            v = wide[:, dn:]

            def block(at):
                rows = jax.lax.dynamic_slice_in_dim(q, at, Q_ROWS)
                sc = rows @ k.T * scale
                seen = pos[None, :] <= (at + jnp.arange(Q_ROWS))[:, None]
                return jax.nn.softmax(jnp.where(seen, sc, -jnp.inf),
                                      axis=-1) @ v

            o = jax.lax.map(block, jnp.arange(0, s, Q_ROWS)).reshape(s, dv)
            return y + o @ wo_h, None

        y, _ = jax.lax.scan(
            one_head, jnp.zeros((s, x.shape[-1]), jnp.float32),
            (w_q.transpose(1, 0, 2), w_kv.transpose(1, 0, 2), w_o))
        return _mix(x, y, post, res)


@functools.partial(jax.jit, static_argnames=("dims_key", "lower", "moe"))
def _ffn_open(x, layer, li, *, dims_key, lower, moe):
    """The feed-forward sublayer up to the routed experts: the maps,
    the sublayer's input, the SwiGLU (dense or shared) and, ``moe``,
    the chosen experts and their gates."""
    dims = _key_dims(dims_key)
    eps = dims["rms_norm_eps"]
    p = {k: _take(layer[k], li) for k in FFN}
    with jax.default_matmul_precision("highest"):
        pre, post, res = _hyper(x, p["hc_ffn_phi"], p["hc_ffn_alpha"],
                                p["hc_ffn_bias"], dims)
        hid = _rms(jnp.einsum("sn,snd->sd", pre, x), p["ffn_norm"], eps)
        y = _swiglu(hid, _deq(p["w_gate"], lower), _deq(p["w_up"], lower),
                    _deq(p["w_down"], lower))
        if not moe:
            return hid, y, post, res, None, None
        scores = jax.nn.sigmoid(
            hid @ _take(layer["router"], li).astype(jnp.float32))
        _, chosen = jax.lax.top_k(
            scores + _take(layer["e_bias"], li).astype(jnp.float32),
            dims["num_experts_per_tok"])
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = dims["routed_scaling_factor"] * picked \
            / picked.sum(axis=-1, keepdims=True)
        return hid, y, post, res, chosen, gates


@functools.partial(jax.jit, static_argnames=("lower",),
                   donate_argnums=(0,))
def _one_expert(y, hid, rows, weights, layer, li, e, *, lower):
    """``y[rows] += weights * SwiGLU_e(hid[rows])``; padding rows carry
    weight zero."""
    mats = [_deq(_take(_take(layer[k], li), e), lower)
            for k in ("we_gate", "we_up", "we_down")]
    with jax.default_matmul_precision("highest"):
        out = _swiglu(hid[rows], *mats)
    return y.at[rows].add(weights[:, None] * out)


@functools.partial(jax.jit, donate_argnums=(0,))
def _ffn_close(x, y, post, res):
    return _mix(x, y, post, res)


def routed_part(y, hid, chosen, gates, layer, li, lower, held=None,
                real=None):
    """Add to ``y`` ``[S, d]`` the routed experts' terms for ``hid``,
    expert by expert: the tokens that chose it, found on the host.
    ``held = (first, count)``: only those experts' terms; ``real``:
    only the first so many tokens' (what follows is padding, which no
    real position sees)."""
    chosen_h, gates_h = np.asarray(chosen)[:real], np.asarray(gates)[:real]
    for e in np.unique(chosen_h):
        if held and not held[0] <= e < held[0] + held[1]:
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
                        jnp.int32(li), jnp.int32(e), lower=lower)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(rows, final_norm, lm_head, at, *, eps, lower):
    with jax.default_matmul_precision("highest"):
        cols = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(
                a, at, min(HEAD_COLS, a.shape[-1]), axis=-1), lm_head)
        return _rms(rows.sum(axis=1), final_norm, eps) @ _deq(cols, lower)


def _dims_key(dims: dict):
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
            "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "num_experts_per_tok",
            "routed_scaling_factor", "rope_theta")
    rs = dims.get("rope_scaling") or {}
    return tuple((k, dims[k]) for k in keys) \
        + (("rope_scaling", tuple(sorted(rs.items()))),)


def _key_dims(key) -> dict:
    d = dict(key)
    d["rope_scaling"] = dict(d["rope_scaling"])
    return d


def padded_len(n: int) -> int:
    return -(-n // PAD_TO) * PAD_TO


def hidden_states(weights: dict, dims: dict, tokens,
                  lower: str | None = None, pad_to: int = 0,
                  routed: list | None = None):
    """The streams after the last layer, ``[padded S, n, d]``.
    ``routed`` collects each expert layer's chosen experts ``[padded
    S, k]``, in order."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    ids = np.zeros((max(padded_len(n), pad_to),), np.int32)
    ids[:n] = tokens
    emb = weights["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
    x = jnp.repeat(emb[:, None, :], dims["hc_mult"], axis=1)
    key = _dims_key(dims)
    inv_freq = jnp.asarray(yarn_inv_freq(dims))
    for name in ("dense", "moe"):
        stack = weights.get(name)
        if stack is None:
            continue
        for li in range(stack["attn_norm"].shape[0]):
            at = jnp.int32(li)
            x = _attention(x, stack, at, inv_freq, dims_key=key,
                           lower=lower)
            hid, y, post, res, chosen, gates = _ffn_open(
                x, stack, at, dims_key=key, lower=lower,
                moe=name == "moe")
            if name == "moe":
                y = routed_part(y, hid, chosen, gates, stack, li, lower,
                                real=n)
                if routed is not None:
                    routed.append(np.asarray(chosen))
            x = _ffn_close(x, y, post, res)
    return x


def logits_at(weights: dict, dims: dict, tokens, positions,
              lower: str | None = None, pad_to: int = 0) -> np.ndarray:
    """Float32 logits ``[len(positions), vocab]`` of the next token
    after ``tokens[: p + 1]`` for each p in ``positions``."""
    x = hidden_states(weights, dims, tokens, lower, pad_to)
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
