"""Shared transformer building blocks (functional, pytree params).

Conventions:
* params are plain dicts of ``jnp`` arrays; a parallel tree of logical-axis
  tuples (see ``parallel/sharding.py``) describes how each leaf shards.
* activations flow in the compute dtype (bf16 by default); norms and
  softmax statistics accumulate in fp32 — the standard TPU recipe.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.models.configs import DecoderConfig
from copilot_for_consensus_tpu.obs.profile import scope
from copilot_for_consensus_tpu.ops.attention import attention, decode_attention

# ---------------------------------------------------------------------------
# Matmul with transparent int8 weight dequantization
# ---------------------------------------------------------------------------


def qmatmul(x: jax.Array, w) -> jax.Array:
    """``x @ w`` where ``w`` is a plain array or a quantized leaf
    (``models.quant``: int8 per-channel or packed-int4 group-wise).
    On TPU the quantized paths run the fused Pallas kernels
    (``ops/quant_matmul.py``) so the bf16 dequantized weight never
    touches HBM — decode streams the int8/int4 bytes, once."""
    from copilot_for_consensus_tpu.models.quant import (
        act_quant_mode,
        pallas_qmatmul_enabled,
        quant_kind,
    )

    kind = quant_kind(w)
    on_tpu = jax.default_backend() == "tpu"
    # Activation quantization pays only where the matmul is MXU-bound:
    # the int8×int8 MXU path doubles the FLOPs rate, so a batched
    # prefill wave (m ≥ 1024 rows) halves its dominant cost. At decode
    # widths (m = slots) the step is weight-bandwidth-bound and the
    # dequant-fused XLA expression wins — measured 3225 vs 2662 tok/s
    # with a8 forced on decode.
    m = 1
    for s in x.shape[:-1]:
        m *= s
    a8 = act_quant_mode() == "a8" and on_tpu
    if kind == "int4":
        from copilot_for_consensus_tpu.ops.quant_matmul import (
            int4_matmul,
            int4_matmul_xla,
            w4a8_matmul,
        )
        if w["q4"].ndim == 2 and pallas_qmatmul_enabled() and on_tpu:
            # int4 in a8 mode takes the int8-MXU kernel at EVERY width:
            # the bf16 group dots of the weight-only kernel lose to it
            # at decode shapes too (harness: 31.2 vs 33.7 ms/pass).
            if a8:
                return w4a8_matmul(x, w["q4"], w["scale"])
            return int4_matmul(x, w["q4"], w["scale"])
        return int4_matmul_xla(x, w["q4"], w["scale"])
    if kind == "int8":
        # int8 a8 pays only where the matmul is MXU-bound (m ≥ 1024,
        # prefill waves); at decode widths the dequant-fused XLA
        # expression wins (3225 vs 2662 tok/s forced).
        if (a8 and m >= 1024 and w["q"].ndim == 2
                and pallas_qmatmul_enabled()):
            from copilot_for_consensus_tpu.ops.quant_matmul import (
                w8a8_matmul,
            )
            return w8a8_matmul(x, w["q"], w["scale"])
        # Measured on v5e: XLA's own dequant-fused matmul streams int8
        # weights faster than the Pallas kernel at serving shapes
        # (engine decode 2778 vs 2146 tok/s), and it partitions under
        # GSPMD — so the XLA expression is the weight-only int8 path.
        # The Pallas int8 kernel stays for reference/experiments
        # (ops/quant_matmul.int8_matmul).
        return (x @ w["q"].astype(x.dtype)) * w["scale"].astype(x.dtype)
    return x @ w


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


@scope("norm_rope")
def rms_norm(x: jax.Array, weight: jax.Array, eps: float, *,
             unit_offset: bool = False, dtype=None) -> jax.Array:
    """``unit_offset``: the gain is stored as an offset from one
    (``x̂ · (1 + g)``). ``dtype``: the result's (default: ``x``'s) — a
    float32 residual stream hands its blocks bf16 inputs."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    gain = weight.astype(jnp.float32)
    if unit_offset:
        gain = 1.0 + gain
    return (normed * gain).astype(dtype or x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = normed * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding (GPT-NeoX rotate-half convention, as used by
# Llama / Mistral / Mixtral)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)                     # [head_dim/2]


@scope("norm_rope")
def apply_rope(x: jax.Array, positions: jax.Array,
               inv_freq: jax.Array) -> jax.Array:
    """x: [B, H, S, D]; positions: [B, S] (int) → same shape, rotated."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,S,D/2]
    cos = jnp.cos(angles)[:, None, :, :]                  # [B,1,S,D/2]
    sin = jnp.sin(angles)[:, None, :, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention block (GQA) — prefill and decode variants share projections
# ---------------------------------------------------------------------------


def _project_qkv(x: jax.Array, layer: dict, cfg: DecoderConfig,
                 positions: jax.Array):
    b, s, _ = x.shape
    dh = cfg.head_dim
    with scope("qkv"):
        if "wqkv" in layer:
            # Fused int4 projection (quant.fuse_int4_projections): one
            # kernel call; split the product by column.
            nq, nkv = cfg.n_heads * dh, cfg.n_kv_heads * dh
            qkv = qmatmul(x, layer["wqkv"])
            q, k, v = (qkv[..., :nq], qkv[..., nq:nq + nkv],
                       qkv[..., nq + nkv:])
        else:
            q = qmatmul(x, layer["wq"])
            k = qmatmul(x, layer["wk"])
            v = qmatmul(x, layer["wv"])
        q = q.reshape(b, s, cfg.n_heads, dh).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, cfg.n_kv_heads, dh).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, cfg.n_kv_heads, dh).transpose(0, 2, 1, 3)
    inv_freq = rope_frequencies(dh, cfg.rope_theta)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    return q, k, v


@scope("attn_out")
def attn_out(o: jax.Array, layer: dict) -> jax.Array:
    return qmatmul(o, layer["wo"])


def attn_prefill(x: jax.Array, layer: dict, cfg: DecoderConfig,
                 lengths: jax.Array | None = None, impl: str = "auto"):
    """Full-sequence causal attention. Returns (out [B,S,D_model], k, v)
    with k/v in [B, Hkv, S, Dh] for cache insertion."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(x, layer, cfg, positions)
    o = attention(q, k, v, causal=True, window=cfg.sliding_window,
                  kv_lengths=lengths, impl=impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return attn_out(o, layer), k, v


def attn_prefill_seeded(x: jax.Array, layer: dict, cfg: DecoderConfig,
                        k_pref: jax.Array, v_pref: jax.Array,
                        prefix_lens: jax.Array,
                        lengths: jax.Array | None = None):
    """Suffix-prefill attention against a seeded prefix (prefix KV
    cache admission). Row b's tokens sit at absolute positions
    ``prefix_lens[b] + i`` — RoPE rotates with that offset — and attend
    (reused prefix KV ++ fresh causal suffix) in one joint softmax
    (``ops.attention.prefill_attention_seeded``). k_pref/v_pref:
    [B, Hkv, P, Dh]; rows with prefix_lens 0 reduce exactly to
    ``attn_prefill``. Returns (out [B,S,D_model], k, v) with fresh
    SUFFIX k/v in [B, Hkv, S, Dh] for cache insertion at the offset.

    Sliding-window models are routed away by the engine (a reused
    prefix inside the window would need window masking against the
    absolute timeline, which this path doesn't implement)."""
    from copilot_for_consensus_tpu.ops.attention import (
        prefill_attention_seeded,
    )

    b, s, _ = x.shape
    positions = prefix_lens[:, None] + jnp.arange(s)[None, :]
    q, k, v = _project_qkv(x, layer, cfg, positions)
    o = prefill_attention_seeded(q, k, v, k_pref, v_pref,
                                 prefix_lens, kv_lengths=lengths)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return attn_out(o, layer), k, v


def attn_decode_stacked(x: jax.Array, layer: dict, cfg: DecoderConfig,
                        positions: jax.Array, k_cache: jax.Array,
                        v_cache: jax.Array, li: jax.Array,
                        kv_len: int | None = None):
    """Decode attention against the FULL stacked cache [L,B,Hkv,S,Dh].

    Writes one kv column per slot into layer ``li`` via scatter (touches
    only B columns, not a whole layer slice) and reads only the
    ``kv_len`` prefix. This lets the layer loop carry the stacked cache
    — the alternative (cache as scan xs/ys) re-materializes every layer's
    full cache slice per token step, which at serving shapes costs more
    HBM traffic than the weights themselves."""
    b = x.shape[0]
    q, k, v = _project_qkv(x, layer, cfg, positions[:, None])
    bidx = jnp.arange(b)
    with scope("kv_write"):
        k_cache = k_cache.at[li, bidx, :, positions, :].set(
            k[:, :, 0, :].astype(k_cache.dtype), mode="drop")
        v_cache = v_cache.at[li, bidx, :, positions, :].set(
            v[:, :, 0, :].astype(v_cache.dtype), mode="drop")
    k_l = jax.lax.dynamic_index_in_dim(k_cache, li, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(v_cache, li, 0, keepdims=False)
    o = decode_attention(q[:, :, 0, :], k_l, v_l,
                         lengths=positions + 1,
                         window=cfg.sliding_window,
                         kv_len=kv_len)                   # [B, Hq, Dh]
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return attn_out(o, layer), k_cache, v_cache


def attn_decode_windowed(x: jax.Array, layer: dict, cfg: DecoderConfig,
                         positions0: jax.Array, w: jax.Array,
                         k_pref_l: jax.Array, v_pref_l: jax.Array,
                         k_win_l: jax.Array, v_win_l: jax.Array,
                         kv_len: int | None = None,
                         k_done_l: jax.Array | None = None,
                         v_done_l: jax.Array | None = None):
    """Decode attention for one layer against (read-only prefix cache,
    completed-window buffers, current window buffer, self). Returns
    (out, k_cur, v_cur) — the caller stacks the per-layer k/v columns
    into the window buffer; nothing here writes the big cache, which is
    what keeps it out of the decode scan carry (see
    ``decoder.decode_step_windowed``).

    positions0: [B] DISPATCH-start positions; ``w``: traced step index
    within the current window; ``k_done_l`` [B, Hkv, Wd, Dh] holds the
    dispatch's already-completed windows (absolute position =
    positions0 + Wd + w, used for RoPE and sliding-window masking).
    """
    from copilot_for_consensus_tpu.ops.attention import (
        decode_attention_prefix_window,
    )

    b = x.shape[0]
    n_done = 0 if k_done_l is None else k_done_l.shape[2]
    pos = (positions0 + n_done + w)[:, None]
    q, k, v = _project_qkv(x, layer, cfg, pos)
    k_cur = k[:, :, 0, :]
    v_cur = v[:, :, 0, :]
    o = decode_attention_prefix_window(
        q[:, :, 0, :], k_pref_l, v_pref_l, k_win_l, v_win_l,
        k_cur, v_cur, prefix_lengths=positions0, w=w,
        window=cfg.sliding_window, kv_len=kv_len,
        k_done=k_done_l, v_done=v_done_l)                   # [B, Hq, Dh]
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return attn_out(o, layer), k_cur, v_cur


def attn_decode_windowed_paged(x: jax.Array, layer: dict,
                               cfg: DecoderConfig,
                               positions0: jax.Array, w: jax.Array,
                               partial_fn, k_win_l: jax.Array,
                               v_win_l: jax.Array,
                               k_done_l: jax.Array | None = None,
                               v_done_l: jax.Array | None = None):
    """Kernel-route twin of :func:`attn_decode_windowed`: the big
    prefix piece never materializes — ``partial_fn(qg, lengths,
    q_pos)`` returns its flash (acc, m, l) straight off the paged
    block pool (the Pallas kernel reading blocks by pointer), and the
    dispatch-local pieces (done windows, current window, self) fold in
    through one ``combine_partials`` — the same joint softmax the
    reference computes over its gathered view. Projections, RoPE and
    the output matmul are shared with the reference twin byte for
    byte."""
    from copilot_for_consensus_tpu.ops.attention import (
        combine_partials,
        decode_window_partial,
    )

    b = x.shape[0]
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    n_done = 0 if k_done_l is None else k_done_l.shape[2]
    q_pos = positions0 + n_done + w
    q, k, v = _project_qkv(x, layer, cfg, q_pos[:, None])
    k_cur = k[:, :, 0, :]
    v_cur = v[:, :, 0, :]
    qg = q[:, :, 0, :].reshape(b, hkv, cfg.n_heads // hkv, dh)
    with scope("attn"):
        pool_part = partial_fn(qg, positions0, q_pos)
    local_part = decode_window_partial(
        qg, k_win_l, v_win_l, k_cur, v_cur, positions0, w,
        window=cfg.sliding_window, k_done=k_done_l, v_done=v_done_l)
    o = combine_partials([pool_part, local_part], x.dtype)
    o = o.reshape(b, 1, cfg.n_heads * dh)
    return attn_out(o, layer), k_cur, v_cur


def attn_prefill_seeded_paged(x: jax.Array, layer: dict,
                              cfg: DecoderConfig, partial_fn,
                              prefix_lens: jax.Array,
                              lengths: jax.Array | None = None):
    """Kernel-route twin of :func:`attn_prefill_seeded`: the seeded
    prefix KV is scored in place in the paged block pool —
    ``partial_fn`` runs the Pallas partial kernel over R = G·S query
    rows (rows (g, s) flattened row-major) — and the fresh causal
    suffix joins through ``combine_partials``. Sliding-window models
    are routed away by the engine exactly as on the reference seeded
    path. Returns (out [B,S,D_model], k, v) with fresh SUFFIX k/v in
    [B, Hkv, S, Dh] for the pool scatter at the per-row offset."""
    from copilot_for_consensus_tpu.ops.attention import (
        causal_suffix_partial,
        combine_partials,
    )

    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = prefix_lens[:, None] + jnp.arange(s)[None, :]
    q, k, v = _project_qkv(x, layer, cfg, positions)
    q_rows = q.reshape(b, hkv, hq // hkv, s, dh).reshape(
        b, hkv, (hq // hkv) * s, dh)
    with scope("attn"):
        pool_part = partial_fn(q_rows, prefix_lens, prefix_lens)
    suffix_part = causal_suffix_partial(q, k, v, kv_lengths=lengths)
    o = combine_partials([pool_part, suffix_part], x.dtype)
    o = o.reshape(b, hq, s, dh).transpose(0, 2, 1, 3).reshape(
        b, s, hq * dh)
    return attn_out(o, layer), k, v


# ---------------------------------------------------------------------------
# Feed-forward
# ---------------------------------------------------------------------------


@scope("ffn")
def swiglu(x: jax.Array, layer: dict) -> jax.Array:
    """SwiGLU MLP: silu(x·Wg) ⊙ (x·Wu) · Wd — Llama/Mistral family FFN."""
    if "w_gu" in layer:
        # Fused int4 gate+up (quant.fuse_int4_projections): one kernel
        # call, split by column.
        gu = qmatmul(x, layer["w_gu"]).astype(jnp.float32)
        f = gu.shape[-1] // 2
        gate, up = jax.nn.silu(gu[..., :f]), gu[..., f:]
    else:
        gate = jax.nn.silu(qmatmul(x, layer["w_gate"]).astype(jnp.float32))
        up = qmatmul(x, layer["w_up"]).astype(jnp.float32)
    return qmatmul((gate * up).astype(x.dtype), layer["w_down"])


@scope("ffn")
def gelu_mlp(x: jax.Array, layer: dict) -> jax.Array:
    """BERT-style 2-layer GELU MLP (encoder FFN). Exact (erf) GELU —
    the BERT family's ``hidden_act="gelu"``; tanh-approximate would
    break checkpoint parity."""
    h = jax.nn.gelu((x @ layer["w_in"] + layer["b_in"]).astype(jnp.float32),
                    approximate=False)
    return h.astype(x.dtype) @ layer["w_out"] + layer["b_out"]
