"""Latent-attention decoder with dropless sparse experts (Xing4.0
class, with its multi-stream residual; GLM-5 class, with its learned
sparse attention; ``cfg.attention == "mla"``).

Three things set the block apart from the dense decoder's, each under
the published config's own keys (``models/configs.py``), and a fourth
that one of the two classes adds:

* **Attention keeps one latent row per position** (MLA, DeepSeek-V2/V3):
  ``[c ; k_r]``, ``kv_lora_rank`` values from which every head's key and
  value are expanded by ``wkv_b``, and one rotary key of
  ``qk_rope_head_dim`` shared by all heads (interleaved pairs, YaRN
  frequencies, rotated at the absolute position). Queries pass through a
  ``q_lora_rank`` bottleneck. The same function is computed two ways:
  EXPANDED (an admission piece: keys of ``qk_nope + qk_rope`` and values
  of ``v_head_dim`` a head, expanded again from the cache block by block
  for every piece, online softmax over the blocks that hold something
  live) and ABSORBED (a decode step: ``wkv_b``'s key half folded into the
  query, its value half applied after the softmax, so all heads score
  the one cached row as grouped queries score a shared kv head:
  ``ops.attention.decode_attention_prefix_window`` with one kv head of
  ``kv_lora_rank + qk_rope_head_dim``, whose first ``kv_lora_rank``
  values are also the "value"; on a TPU the cached rows go through
  ``ops/latent_attention.py`` instead, which reads each slot's live
  blocks of them in place, once for both uses, and folds with the
  dispatch's own rows under the same softmax).
* **The feed-forward part is sparse after ``first_k_dense_replace``
  layers**: sigmoid scores over ``n_routed_experts``, the
  ``experts_per_token`` largest of ``score + bias`` chosen, gates
  normalised over the chosen and scaled, and a shared expert beside
  them. DROPLESS: token-expert pairs are sorted by expert and meet the
  experts' matrices in grouped matmuls (``ops/grouped_matmul.py``,
  through the Pallas interpreter off a TPU), so a token's output never
  depends on its batch-mates. The layer is told which experts it holds
  (``held``), routes over all of them and adds its own experts' part.
  (``models/moe.py`` is the capacity-dropping TRAINING dispatch.)
* **The residual is ``hc_mult`` streams** (manifold-constrained
  hyper-connections, arXiv:2512.24880): around each sublayer, per token
  and in float32, a read-out of the streams (``H_pre``), a write-back
  (``H_post``) and a doubly stochastic mixing of the streams (``H_res``,
  ``hc_sinkhorn_iters`` Sinkhorn rounds), all three from the token's own
  normalised streams (``mhc_maps``). ``hc_mult == 1`` is the plain
  residual ``x + F(norm(x))``: no maps are held or computed.
* **Attention may be SELECTED** (``cfg.index_topk``; DSA, the
  ``glm_moe_dsa`` class and DeepSeek-V3.2): a lightning indexer
  (``index_n_heads`` queries of ``index_head_dim`` from the query
  bottleneck, ONE index key a position from the sublayer's input,
  per-head weights; ``ReLU`` dots, weighted and summed over heads, in
  float32) scores every cached position for every query, and the query
  attends to the ``index_topk`` of largest score alone (all of them
  while there are fewer; ties to the lower position). The index key is a
  SECOND cached row a position, written, kept in a dispatch's window
  buffer and merged beside the latent row. The choice is the exact
  top-k, found as a threshold by counting (``ops/sparse_select.py``; an
  admission piece's rounds on a TPU over keys held in VMEM,
  ``ops/select_threshold.py``), and reaches both forms of attention as
  a mask over the columns they walk.

State: ``{"dense": [k0, slots, R, max_len], "moe": [L - k0, slots, R,
max_len]}`` latent rows (``R = kv_lora_rank + qk_rope_head_dim``), one
array per stack of layers so that each layer scan takes its own as
scanned input or carry, or closes over it (decode on a TPU), and none
is ever cut; with a selection, ``{"dense_idx", "moe_idx"}`` ``[.., slots,
index_head_dim, max_len]`` index keys beside them, stack for stack. A
position is a COLUMN:
the positions run along the minor axis. That is the layout the chip's
compiler gives ``[.., max_len, R]`` of its own accord (R = 576 is four
and a half lane tiles; the positions fill them whole) and the one its
decode dots want; held the other way round, the admission program,
which wants rows, turned the whole cache over on entry and back on
exit. Admission writes a piece's rows in place, slot by slot; a decode
dispatch keeps its own rows in a small buffer and merges them once
(``merge_latents``, the slab scatter of ``decoder.merge_window``).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.models import layers as L
from copilot_for_consensus_tpu.models.configs import DecoderConfig
from copilot_for_consensus_tpu.models.quant import (
    quant_kind,
    quantize_tensor,
)
from copilot_for_consensus_tpu.obs.profile import scope
from copilot_for_consensus_tpu.ops import (
    latent_attention,
    latent_prefill_attention,
    select_threshold,
    sparse_select,
)
from copilot_for_consensus_tpu.ops.attention import (
    combine_partials,
    decode_attention_prefix_window,
    decode_window_partial,
)
from copilot_for_consensus_tpu.ops.grouped_matmul import (
    grouped_qmatmul,
    tile_counts,
)

Params = dict[str, Any]

#: cached positions that one round of an admission piece's attention
#: expands and scores (``piece_attention``); a cache extent is a
#: multiple of it or shorter
KV_BLOCK = 1024

#: leaves served as int8 (``quantize_params``); ``wkv_b`` and the
#: indexer's head weights ``w_idx`` stay in the activation type (the
#: absorbed form contracts the one with activations on either side; the
#: other's 32 columns weigh every score), the router, its bias and the
#: mHC maps in float32
MATRICES = ("wq_a", "wq_b", "wkv_a", "wo", "w_gate", "w_up", "w_down",
            "we_gate", "we_up", "we_down", "wq_idx", "wk_idx")

#: a stack's index keys in the cache, the window buffer and a step's
#: rows: the stack's name and this
IDX = "_idx"

#: the index key's LayerNorm epsilon (DeepSeek-V3.2's inference code;
#: ``config.json`` has no key for it)
INDEX_NORM_EPS = 1e-6

#: float32 index dots held at once (elements): the index heads are
#: scored in groups that keep to it, so that an admission piece's
#: ``[rows, heads, block]`` never exists for all heads
INDEX_DOTS = 1 << 25

#: the expert stacks ``[layers, E, ...]``: a layer scan closes over them
#: and hands the layer's index on, so that the grouped matmul reads a
#: layer's experts in place (``ops/grouped_matmul.py``)
EXPERTS = ("we_gate", "we_up", "we_down")

#: (experts touched, token-expert pairs, the busiest expert's pairs;
#: the pairs that have a group here, the rows the grouped matmul
#: multiplies for them: ``ops/grouped_matmul.py:tile_counts``): what a
#: layer's routing reports, summed over layers and steps
N_COUNTS = 5


# ---------------------------------------------------------------------------
# Shapes, parameters, cache
# ---------------------------------------------------------------------------


def latent_width(cfg: DecoderConfig) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def stacks(cfg: DecoderConfig) -> dict[str, int]:
    """Layers per stack, in order: the leading dense ones, then the
    expert layers. A stack with no layer is absent."""
    k0 = min(cfg.first_k_dense_replace, cfg.n_layers)
    out = {"dense": k0, "moe": cfg.n_layers - k0}
    return {k: v for k, v in out.items() if v}


def held_experts(cfg: DecoderConfig) -> tuple[int, int]:
    """(first, count) of the routed experts this device holds."""
    return tuple(cfg.held_experts) or (0, cfg.n_routed_experts)


def n_maps(cfg: DecoderConfig) -> int:
    """Columns of a sublayer's mHC map: pre, post, and the n x n mix."""
    return cfg.hc_mult * (cfg.hc_mult + 2)


def init_params(rng: jax.Array, cfg: DecoderConfig, dtype=jnp.bfloat16,
                quantize: bool = False) -> Params:
    """Random weights in this module's layout. The mHC maps are drawn
    alive: the maps' logits vary from token to token (``alpha`` of
    order one, the mix's a quarter of that), so that a wrong Sinkhorn
    or a dropped stream changes the logits."""
    d, h, n = cfg.d_model, cfg.n_heads, cfg.hc_mult
    rq, r, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    e, fe = cfg.n_routed_experts, cfg.moe_intermediate_size
    e_held = held_experts(cfg)[1]
    keys = iter(jax.random.split(rng, 64))

    def dense(shape, fan_in, dt=dtype):
        return (jax.random.truncated_normal(next(keys), -2, 2, shape,
                                            jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def gain(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    def stack(count: int, moe: bool) -> Params:
        out = {
            "attn_norm": gain((count, d)), "ffn_norm": gain((count, d)),
            "wq_a": dense((count, d, rq), d),
            "q_norm": gain((count, rq)),
            "wq_b": dense((count, rq, h * (dn + dr)), rq),
            "wkv_a": dense((count, d, r + dr), d),
            "kv_norm": gain((count, r)),
            "wkv_b": dense((count, r, h * (dn + dv)), r),
            "wo": dense((count, h * dv, d), h * dv),
        }
        for sub in ("attn", "ffn") if n > 1 else ():
            out[f"hc_{sub}_phi"] = dense((count, n, d, n_maps(cfg)), n * d,
                                         jnp.float32)
            # the mix's logits at a quarter of the others' size: twenty
            # Sinkhorn rounds then end within 1e-6 of doubly stochastic
            size = jnp.asarray([1.0, 1.0, 0.25])
            out[f"hc_{sub}_alpha"] = size * jax.random.uniform(
                next(keys), (count, 3), jnp.float32, 1.0, 2.0)
            out[f"hc_{sub}_bias"] = 0.5 * jax.random.normal(
                next(keys), (count, n_maps(cfg)), jnp.float32
            ) * jnp.where(jnp.arange(n_maps(cfg)) < 2 * n, 1.0, 0.5)
        f = fe * max(cfg.n_shared_experts, 1) if moe else cfg.d_ff
        out.update(w_gate=dense((count, d, f), d),
                   w_up=dense((count, d, f), d),
                   w_down=dense((count, f, d), f))
        if moe:
            out.update(
                router=dense((count, d, e), d, jnp.float32),
                e_bias=0.01 * jax.random.normal(next(keys), (count, e),
                                                jnp.float32),
                we_gate=dense((count, e_held, d, fe), d),
                we_up=dense((count, e_held, d, fe), d),
                we_down=dense((count, e_held, fe, d), fe))
        if cfg.selects:
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            out.update(
                wq_idx=dense((count, rq, hi * di), rq),
                wk_idx=dense((count, d, di), d),
                k_idx_gain=gain((count, di)),
                k_idx_bias=0.1 * jax.random.normal(
                    next(keys), (count, di), jnp.float32).astype(dtype),
                w_idx=dense((count, d, hi), d))
        return out

    params = {"tok_emb": dense((cfg.vocab_size, d), d),
              "final_norm": gain((d,)),
              "lm_head": dense((d, cfg.vocab_size), d)}
    for name, count in stacks(cfg).items():
        params[name] = stack(count, name == "moe")
    return quantize_params(params) if quantize else params


def quantize_params(params: Params) -> Params:
    """int8 with one float32 scale per output channel (per expert and
    channel in an expert stack) for every leaf of ``MATRICES`` and the
    output head; leaves that are already quantized pass."""
    def q(leaf):
        return leaf if quant_kind(leaf) else quantize_tensor(leaf)

    out = dict(params, lm_head=q(params["lm_head"]))
    for name in ("dense", "moe"):
        if name in params:
            out[name] = {k: q(v) if k in MATRICES else v
                         for k, v in params[name].items()}
    return out


def init_cache(cfg: DecoderConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    if max_len % min(KV_BLOCK, max_len):
        raise ValueError(
            f"latent cache: max_len {max_len} must be a multiple of the "
            f"expansion block ({KV_BLOCK})")
    out = {name: jnp.zeros((count, batch, latent_width(cfg), max_len),
                           dtype)
           for name, count in stacks(cfg).items()}
    if cfg.selects:
        out.update({name + IDX: jnp.zeros(
            (count, batch, cfg.index_head_dim, max_len), dtype)
            for name, count in stacks(cfg).items()})
    return out


# ---------------------------------------------------------------------------
# Rotary frequencies (YaRN), interleaved pairs
# ---------------------------------------------------------------------------


def rope_inv_freq(cfg: DecoderConfig) -> jax.Array:
    """``qk_rope_head_dim / 2`` inverse frequencies: ``theta ** (-2i /
    d)`` blended, frequency by frequency, with the same divided by
    ``factor``: a linear ramp between the two correction dimensions
    (where ``beta_fast`` and ``beta_slow`` turns fit the original
    context)."""
    dim = cfg.qk_rope_head_dim
    base = cfg.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    rs = dict(cfg.rope_scaling)
    if rs.get("type") != "yarn":
        return extra
    orig = rs["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / rs["factor"] * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: DecoderConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale ** 2``
    (``mscale = 0.1 mscale_all_dim ln(factor) + 1``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = dict(cfg.rope_scaling)
    if rs.get("type") == "yarn" and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


@scope("norm_rope")
def rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate the interleaved pairs ``(x[2i], x[2i + 1])`` of the last
    axis by ``angles`` (broadcast against ``x``'s leading axes)."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = xf[..., 0], xf[..., 1]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------------
# The residual streams
# ---------------------------------------------------------------------------


def sinkhorn(logits: list, iters: int, eps: float) -> list:
    """``iters`` rounds of row then column normalisation of
    ``exp(logits)``: ``logits[i][j]`` are arrays of one shape (a value
    per token), so every round is elementwise."""
    a = [[jnp.exp(m) for m in row] for row in logits]
    n = len(a)
    for _ in range(iters):
        for i in range(n):
            inv = 1.0 / (sum(a[i]) + eps)
            a[i] = [v * inv for v in a[i]]
        for j in range(n):
            inv = 1.0 / (sum(a[i][j] for i in range(n)) + eps)
            for i in range(n):
                a[i][j] = a[i][j] * inv
    return a


@scope("mhc")
def mhc_maps(x: jax.Array, layer: Params, sub: str, cfg: DecoderConfig):
    """The three maps of one sublayer from the streams ``x``
    ``[n, ..., d]`` float32: (``H_pre`` n arrays, ``H_post`` n arrays,
    ``H_res`` n x n arrays, each ``[...]``). The maps' input is the
    token's streams under ONE norm over all ``n d`` values, no gain."""
    n = cfg.hc_mult
    inv = jax.lax.rsqrt(
        jnp.mean(jnp.mean(x * x, axis=-1), axis=0) + cfg.norm_eps)
    raw = jnp.einsum("n...d,ndk->k...", x, layer[f"hc_{sub}_phi"],
                     precision=jax.lax.Precision.HIGHEST) * inv
    alpha, bias = layer[f"hc_{sub}_alpha"], layer[f"hc_{sub}_bias"]
    pre = [jax.nn.sigmoid(alpha[0] * raw[i] + bias[i]) for i in range(n)]
    post = [2.0 * jax.nn.sigmoid(alpha[1] * raw[n + i] + bias[n + i])
            for i in range(n)]
    res = sinkhorn(
        [[jnp.clip(alpha[2] * raw[2 * n + n * i + j]
                   + bias[2 * n + n * i + j],
                   cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max)
          for j in range(n)] for i in range(n)],
        cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return pre, post, res


@scope("mhc")
def mhc_read(x: jax.Array, pre: list) -> jax.Array:
    """``H_pre · X``: the sublayer's input ``[..., d]`` float32."""
    return sum(p[..., None] * x[i] for i, p in enumerate(pre))


@scope("mhc")
def mhc_write(x: jax.Array, y: jax.Array, post: list, res: list
              ) -> jax.Array:
    """``H_res · X + H_post ⊗ y``: the streams after the sublayer."""
    y = y.astype(jnp.float32)
    return jnp.stack([
        sum(res[i][j][..., None] * x[j] for j in range(len(post)))
        + post[i][..., None] * y for i in range(len(post))])


def sublayer_in(x: jax.Array, layer: Params, sub: str, cfg: DecoderConfig
                ) -> tuple[jax.Array, tuple | None]:
    """The sublayer's input ``[..., d]`` float32 from the streams
    ``[n, ..., d]``, before its norm, and what ``sublayer_out`` needs
    to write back: the maps' two halves, or nothing under the plain
    residual (``hc_mult == 1``: the one stream itself)."""
    if cfg.hc_mult == 1:
        return x[0], None
    pre, post, res = mhc_maps(x, layer, sub, cfg)
    return mhc_read(x, pre), (post, res)


def sublayer_out(x: jax.Array, y: jax.Array, maps: tuple | None
                 ) -> jax.Array:
    """The streams after the sublayer wrote ``y``."""
    if maps is None:
        return x + y.astype(jnp.float32)[None]
    return mhc_write(x, y, *maps)


# ---------------------------------------------------------------------------
# Attention: projections, the expanded form, the absorbed form
# ---------------------------------------------------------------------------


def _norm(x, gain, cfg, dtype):
    return L.rms_norm(x, gain, cfg.norm_eps, dtype=dtype)


@scope("qkv")
def project(hid: jax.Array, layer: Params, cfg: DecoderConfig,
            angles: jax.Array, dtype
            ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """hid ``[n, S, d]`` → (``q_n [n, S, H, dn]``, rotated ``q_r [n, S,
    H, dr]``, the positions' latent rows ``[n, S, R]``: normed ``c``
    and the rotated shared key, in ``dtype``; the normed query
    bottleneck ``c_q [n, S, rq]``, which the indexer reads too)."""
    n, s, _ = hid.shape
    r = cfg.kv_lora_rank
    c_q = _norm(L.qmatmul(hid, layer["wq_a"]), layer["q_norm"], cfg,
                hid.dtype)
    q = L.qmatmul(c_q, layer["wq_b"]).reshape(n, s, cfg.n_heads, -1)
    q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    kv = L.qmatmul(hid, layer["wkv_a"])
    latent = jnp.concatenate(
        [_norm(kv[..., :r], layer["kv_norm"], cfg, dtype),
         rope(kv[..., r:], angles).astype(dtype)], axis=-1)
    return q_n, rope(q_r, angles[:, :, None, :]).astype(hid.dtype), \
        latent, c_q


def _wkv_b(layer: Params, cfg: DecoderConfig) -> jax.Array:
    """``[r, H, dn + dv]``."""
    return layer["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)


@scope("latent_expand")
def expand(latent: jax.Array, layer: Params, cfg: DecoderConfig
           ) -> tuple[jax.Array, jax.Array]:
    """Latent rows as the cache holds them, ``[n, R, T]`` → every
    head's keys ``[n, T, H, dn + dr]`` (the rotary part is the one
    shared key, repeated) and values ``[n, T, H, dv]``."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    dt = layer["wkv_b"].dtype
    kv = jnp.einsum("nrt,rhd->nthd", latent[:, :r].astype(dt),
                    _wkv_b(layer, cfg))
    k_r = jnp.broadcast_to(
        latent[:, r:].astype(dt).transpose(0, 2, 1)[:, :, None, :],
        (*kv.shape[:3], cfg.qk_rope_head_dim))
    return jnp.concatenate([kv[..., :dn], k_r], axis=-1), kv[..., dn:]


def _block_rows(stack_a: jax.Array, li: jax.Array, slots: jax.Array,
                j: jax.Array, blk: int) -> jax.Array:
    """Block j (``blk`` columns) of layer ``li`` of a stack ``[La,
    slots, width, T]`` for each of a piece's rows: ``[n, width, blk]``,
    a read a row."""
    width = stack_a.shape[2]
    return jnp.stack([
        jax.lax.dynamic_slice(stack_a, (li, slots[r], 0, j * blk),
                              (1, 1, width, blk))[0, 0]
        for r in range(slots.shape[0])])


def _seen(col: jax.Array, q_pos: jax.Array, kv_len: jax.Array
          ) -> jax.Array:
    """Which of the columns ``col [blk]`` each query of a piece sees
    ``[n, S, blk]``: those up to its own position, below its row's
    ``kv_len``."""
    return (col[None, None, :] <= q_pos[:, :, None]) \
        & (col[None, None, :] < kv_len[:, None, None])


def piece_attention(q: jax.Array, cache_a: jax.Array, li: jax.Array,
                    slots: jax.Array, q_pos: jax.Array, kv_len: jax.Array,
                    n_blocks: jax.Array, layer: Params, cfg: DecoderConfig,
                    keep=None) -> jax.Array:
    """Expanded attention of a piece's queries ``[n, S, H, dn + dr]``
    (scaled) over their rows' cached latents, layer ``li`` of
    ``cache_a`` ``[La, slots, R, T]`` (the piece's own rows already
    written): query i of row r stands at ``q_pos[r, i]`` and sees the
    columns up to its own, below ``kv_len[r]``. ``n_blocks`` (traced)
    blocks of ``KV_BLOCK`` columns hold something some row sees; each
    is read, expanded and folded into a running softmax, the rest is
    never touched: one program whatever the lengths. ``keep(j)``
    (a selection: ``select_piece``) → which of block j's columns each
    query reads ``[n, S, blk]``, causal and below ``kv_len`` among its
    conditions; without it every column a query sees. → ``[n, S, H
    dv]`` in q's type.

    Two routes to the same fold. On a TPU a round's scores stay on the
    chip: ``ops/latent_prefill_attention.py`` folds the round into the
    carry a tile at a time and makes the causal mask itself, and the
    round is expanded into the layout its blocks read
    (``expand_heads``). Elsewhere the XLA rounds below over ``expand``,
    which the tests hold the kernel's route to."""
    n, s, h, _ = q.shape
    blk = min(KV_BLOCK, cache_a.shape[3])
    dv = cfg.v_head_dim
    if latent_prefill_attention.serves(blk):
        return _piece_attention_kernel(q, cache_a, li, slots, q_pos, kv_len,
                                       n_blocks, layer, cfg, keep)

    def fold(j, carry):
        acc, m, l = carry
        with scope("kv_prefix"):     # ... and the expansion there
            k, v = expand(_block_rows(cache_a, li, slots, j, blk), layer,
                          cfg)
        with scope("attn"):
            sc = jnp.einsum("nshd,nthd->nhst", q, k.astype(q.dtype),
                            preferred_element_type=jnp.float32)
            if keep is None:
                seen = _seen(j * blk + jnp.arange(blk), q_pos, kv_len)
            else:
                with scope("select"):
                    seen = keep(j)
            sc = jnp.where(seen[:, None], sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            p = jnp.exp(sc - m_safe)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "nhst,nthd->nhsd", p.astype(q.dtype), v.astype(q.dtype),
                preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, _, l = jax.lax.fori_loop(
        0, n_blocks, fold,
        (jnp.zeros((n, h, s, dv), jnp.float32),
         jnp.full((n, h, s, 1), -jnp.inf, jnp.float32),
         jnp.zeros((n, h, s, 1), jnp.float32)))
    with scope("attn"):
        o = acc / jnp.where(l > 0, l, 1.0)
        return o.transpose(0, 2, 1, 3).reshape(n, s, h * dv).astype(q.dtype)


#: lanes of a vreg and columns of an MXU pass on every TPU so far: what
#: an einsum's output columns are multiplied and stored in tiles of
LANE_TILE = 128


def _rotary_in_weight(cfg: DecoderConfig) -> bool:
    """Does the kernel's route expand a round heads first, the shared
    rotary key taken through the keys' weight (``expand_heads``)? Where
    a head's whole key fills no more column tiles than its no-position
    part alone (192 + 64 in two tiles of 128, as 192): the MXU then
    writes the rotary columns in a tile it multiplies anyway, for a
    ninth more rows of weight, and a round's copies go (2.81 → 2.40 ms
    a round at GLM's widths). Where the no-position part fills its
    tiles (128 + 64) the augmented weight adds a tile of columns a head
    and loses (0.93 → 0.99 ms at Xing's widths), and appending the key
    instead gains a fiftieth of a round, which that cell cannot tell
    from its two levels: those widths keep ``expand`` (all timed on
    the chip: ``PERF.md`` section 6, PR 48)."""
    dn, dk = cfg.qk_nope_head_dim, cfg.qk_nope_head_dim \
        + cfg.qk_rope_head_dim
    return -(-dk // LANE_TILE) == -(-dn // LANE_TILE)


def expansion_weights(layer: Params, cfg: DecoderConfig
                      ) -> tuple[jax.Array, jax.Array]:
    """``wkv_b`` as ``expand_heads`` reads it, its two halves apart,
    made once a layer call outside the rounds: the values' ``[r, H,
    dv]`` and the keys' ``[r + dr, H, dn + dr]``, which is the key half
    in one corner, the identity in the rotary corner and zeros
    elsewhere, so that ONE einsum over a whole latent row writes a
    head's key whole; its rotary columns are the row's shared key times
    1 summed with zeros in float32, which is exact."""
    r, h = cfg.kv_lora_rank, cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    w = _wkv_b(layer, cfg)
    eye = jnp.eye(dr, dtype=w.dtype)[:, None, :]
    w_k = jnp.zeros((r + dr, h, dn + dr), w.dtype) \
        .at[:r, :, :dn].set(w[..., :dn]).at[r:, :, dn:].set(eye)
    return w_k, w[..., dn:]


@scope("latent_expand")
def expand_heads(latent: jax.Array, w_k: jax.Array, w_v: jax.Array, dtype
                 ) -> tuple[jax.Array, jax.Array]:
    """``expand`` as the fold kernel reads it: latent rows ``[n, R,
    T]`` and ``expansion_weights`` → every head's keys ``[n, H, T, dn +
    dr]`` and values ``[n, H, T, dv]`` in ``dtype``, heads before
    positions and each at its whole width: two einsums, and nothing
    between them and the kernel."""
    latent = latent.astype(w_k.dtype)
    k = jnp.einsum("nrt,rhd->nhtd", latent, w_k)
    v = jnp.einsum("nrt,rhd->nhtd", latent[:, :w_v.shape[0]], w_v)
    return k.astype(dtype), v.astype(dtype)


def _piece_attention_kernel(q, cache_a, li, slots, q_pos, kv_len, n_blocks,
                            layer, cfg, keep):
    """``piece_attention`` with each round folded by the kernel: the
    same walk of the live rounds; a round's keys and values heads
    before positions as the kernel's tiles want them, written so by
    ``expand_heads`` where ``_rotary_in_weight``, elsewhere ``expand``'s
    turned over."""
    n, s, h, _ = q.shape
    blk = min(KV_BLOCK, cache_a.shape[3])
    heads_first = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    with scope("attn"):
        plan = latent_prefill_attention.plan_queries(q_pos, kv_len)
        q = heads_first(q)
    if _rotary_in_weight(cfg):
        with scope("kv_prefix"), scope("latent_expand"):
            w_k, w_v = expansion_weights(layer, cfg)

    def fold(j, carry):
        with scope("kv_prefix"):
            latent = _block_rows(cache_a, li, slots, j, blk)
            if _rotary_in_weight(cfg):
                k, v = expand_heads(latent, w_k, w_v, q.dtype)
            else:
                k, v = expand(latent, layer, cfg)
                with scope("latent_expand"):
                    k, v = heads_first(k.astype(q.dtype)), \
                        heads_first(v.astype(q.dtype))
        with scope("attn"):
            seen = None
            if keep is not None:
                with scope("select"):
                    seen = keep(j)
            return latent_prefill_attention.fold_round(
                q, k, v, carry, j * blk, plan, seen)

    carry = jax.lax.fori_loop(
        0, n_blocks, fold,
        latent_prefill_attention.empty_carry(n, h, s, cfg.v_head_dim))
    with scope("attn"):
        return latent_prefill_attention.finish(carry, q.dtype)


def absorbed_attention(q_n: jax.Array, q_r: jax.Array, cur: jax.Array,
                       cache_l: jax.Array | None, win_l: jax.Array,
                       pos0: jax.Array, w: jax.Array, layer: Params,
                       cfg: DecoderConfig, live=None, keep=None
                       ) -> jax.Array:
    """One token's attention in absorbed form. ``q_n [B, H, dn]``,
    rotated ``q_r [B, H, dr]``; the token's own latent row ``cur [B,
    R]``; ``cache_l [B, R, T]`` one layer of the cache, live below
    ``pos0``; ``win_l [B, W, R]`` the dispatch's own rows, live below
    step ``w``. The key half of ``wkv_b`` goes into the query, all
    heads score the shared rows under the dense decoder's joint softmax
    as the groups of ONE kv head, the value half comes after:
    → ``[B, H dv]``.

    Two routes to the same softmax. With ``cache_l``, the XLA one:
    every slot's whole extent scored and masked below its length.
    With ``live = (cache_a, li, plan)`` instead (a TPU's route): layer
    ``li`` of the stack ``cache_a [La, B, R, T]`` whole, of which only
    the blocks that ``plan`` lists are read, in place and once
    (``ops/latent_attention.py``); the dispatch's own rows and the
    token's own stay in XLA as one masked partial, and the fold puts
    every score under the one normaliser.

    ``keep = (cached [B, T], own [B, W + 1])`` (a selection:
    ``select_step``): the columns of the cache, and the dispatch's rows
    with the token's own last, that this token reads; live and causal
    are among its conditions. The same two routes, each piece a masked
    partial (the kernel takes the cached mask beside its blocks)."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    dt = q_n.dtype
    wkv = _wkv_b(layer, cfg)
    with scope("qkv"):
        q_c = jnp.einsum("bhd,rhd->bhr", q_n, wkv[..., :dn].astype(dt),
                         preferred_element_type=jnp.float32)
        # the shared softmax scales by its own head width
        fix = softmax_scale(cfg) * latent_width(cfg) ** 0.5
        q_abs = (jnp.concatenate([q_c, q_r.astype(jnp.float32)], axis=-1)
                 * fix).astype(dt)
    one = lambda a: a[:, None]  # noqa: E731
    if keep is not None:
        o = _kept_attention(q_abs, cur, cache_l, win_l, live, keep, r)
    elif live is None:
        rows = one(cache_l.transpose(0, 2, 1))  # a view: the dots take it
        o = decode_attention_prefix_window(
            q_abs, rows, rows, one(win_l), one(win_l), one(cur), one(cur),
            pos0, w)[..., :r]
    else:
        cache_a, li, plan = live
        with scope("attn"):
            past = latent_attention.live_partial(q_abs, cache_a, li, plan,
                                                 rank=r)
        own = decode_window_partial(
            one(q_abs), one(win_l), one(win_l[..., :r]), one(cur),
            one(cur[..., :r]), pos0, w)
        o = combine_partials([tuple(one(a) for a in past), own], dt)[:, 0]
    with scope("attn_out"):
        return jnp.einsum("bhr,rhd->bhd", o, wkv[..., dn:].astype(dt)
                          ).reshape(o.shape[0], -1)


def _partial(logits: jax.Array, values: jax.Array, dt
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial (acc, m, l) of masked float32 score rows ``[B, H,
    T]`` over values ``[B, T, r]``, in ``combine_partials``'s
    convention; the probabilities meet the values in ``dt``."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - jnp.where(jnp.isfinite(m), m, 0.0))
    acc = jnp.einsum("bht,btr->bhr", p.astype(dt), values.astype(dt),
                     preferred_element_type=jnp.float32)
    return acc, m, jnp.sum(p, axis=-1, keepdims=True)


def _kept_attention(q_abs: jax.Array, cur: jax.Array,
                    cache_l: jax.Array | None, win_l: jax.Array, live,
                    keep: tuple, r: int) -> jax.Array:
    """``absorbed_attention``'s softmax over the columns ``keep``
    marks: ``[B, H, r]``."""
    dt = q_abs.dtype
    scale = q_abs.shape[-1] ** -0.5
    keep_c, keep_o = keep
    own = jnp.concatenate([win_l, cur[:, None]], axis=1)      # [B, W+1, R]
    with scope("attn"):
        s_o = jnp.einsum("bhr,bwr->bhw", q_abs, own.astype(dt),
                         preferred_element_type=jnp.float32) * scale
        part_o = _partial(jnp.where(keep_o[:, None], s_o, -jnp.inf),
                          own[..., :r], dt)
        if live is None:
            s_c = jnp.einsum("bhr,brt->bht", q_abs, cache_l.astype(dt),
                             preferred_element_type=jnp.float32) * scale
            part_c = _partial(jnp.where(keep_c[:, None], s_c, -jnp.inf),
                              cache_l[:, :r].transpose(0, 2, 1), dt)
        else:
            cache_a, li, plan = live
            part_c = latent_attention.live_partial(
                q_abs, cache_a, li, plan, rank=r,
                keep=keep_c[:, None].astype(jnp.int32))
    return combine_partials([part_c, part_o], dt)


# ---------------------------------------------------------------------------
# The indexer and the selection (cfg.index_topk)
# ---------------------------------------------------------------------------


@scope("indexer")
def index_project(hid: jax.Array, c_q: jax.Array, layer: Params,
                  cfg: DecoderConfig, angles: jax.Array, dtype
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The indexer's three projections for positions hid ``[n, S, d]``
    (the attention sublayer's normed input) with query bottleneck
    ``c_q``: index queries ``[n, S, Hi, Di]`` in hid's type, the
    positions' index keys ``[n, S, Di]`` in ``dtype`` (the second
    cached row: a LayerNorm of ``wk_idx``'s product), the head weights
    ``[n, S, Hi]`` float32 with both of the score's scale factors in
    them. The first ``qk_rope_head_dim`` values of a query and of the
    key are rotary (interleaved pairs, the attention's frequencies)."""
    n, s, _ = hid.shape
    hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = L.qmatmul(c_q, layer["wq_idx"]).reshape(n, s, hi, di)
    q = jnp.concatenate([rope(q[..., :dr], angles[:, :, None, :]),
                         q[..., dr:].astype(jnp.float32)], axis=-1)
    k = L.layer_norm(L.qmatmul(hid, layer["wk_idx"]).astype(jnp.float32),
                     layer["k_idx_gain"], layer["k_idx_bias"],
                     INDEX_NORM_EPS)
    k = jnp.concatenate([rope(k[..., :dr], angles), k[..., dr:]], axis=-1)
    w = jnp.matmul(hid, layer["w_idx"].astype(hid.dtype),
                   preferred_element_type=jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    return q.astype(hid.dtype), k.astype(dtype), w


@scope("indexer")
def index_scores(q_i: jax.Array, w_i: jax.Array, keys: jax.Array
                 ) -> jax.Array:
    """``I[n, s, t] = sum_j w[n, s, j] ReLU(q[n, s, j] . k[n, :, t])``
    float32: queries ``[n, S, Hi, Di]`` and cached index keys ``[n, Di,
    T]`` meet in their own type with float32 accumulation; the ReLU,
    the weights and the sum over heads are float32. The heads go in
    groups of ``INDEX_DOTS`` elements' worth."""
    n, s, hi, _ = q_i.shape
    t = keys.shape[-1]
    g = max(1, min(hi, INDEX_DOTS // max(n * s * t, 1)))
    while hi % g:
        g -= 1
    keys = keys.astype(q_i.dtype)

    def group(acc, qw):
        q_g, w_g = qw                       # [n, g, S, Di], [n, g, S]
        dots = jnp.einsum("ngsd,ndt->ngst", q_g, keys,
                          preferred_element_type=jnp.float32)
        return acc + jnp.sum(jax.nn.relu(dots) * w_g[..., None], axis=1), \
            None

    def heads(a):                           # [n, S, Hi, ..] → [G, n, g, S, ..]
        a = a.reshape(n, s, hi // g, g, *a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 3), 1, 0)

    acc, _ = jax.lax.scan(group, jnp.zeros((n, s, t), jnp.float32),
                          (heads(q_i), heads(w_i)))
    return acc


def select_piece(q_i: jax.Array, w_i: jax.Array, idx_a: jax.Array,
                 li: jax.Array, slots: jax.Array, q_pos: jax.Array,
                 kv_len: jax.Array, n_blocks: jax.Array,
                 cfg: DecoderConfig):
    """Which cached columns each query of an admission piece reads:
    the ``index_topk`` of largest index score among those it sees (all
    of them while fewer). The scores of the ``n_blocks`` live blocks
    of layer ``li`` of the index keys ``idx_a [La, slots, Di, T]`` (the
    piece's own keys written) go, as sort keys, into one buffer ``[n,
    S, T]``; the threshold is counted over those blocks alone
    (``piece_threshold``). → ``keep(j)`` for ``piece_attention``."""
    n, s = q_pos.shape
    t = idx_a.shape[3]
    blk = min(KV_BLOCK, t)

    def fill(j, buf):
        return jax.lax.dynamic_update_slice(
            buf, sparse_select.sort_keys(
                index_scores(q_i, w_i,
                             _block_rows(idx_a, li, slots, j, blk)),
                _seen(_key_cols(j, blk), q_pos, kv_len)), (0, 0, j * blk))

    with scope("indexer"):
        buf = jax.lax.fori_loop(
            0, n_blocks, fill,
            jnp.full((n, s, t), sparse_select.NEVER, jnp.int32))
    with scope("select"):
        thr, cut = piece_threshold(buf, q_pos, kv_len, n_blocks,
                                   cfg.index_topk)
    return lambda j: sparse_select.chosen(
        _key_block(buf, j, blk), _key_cols(j, blk), thr[..., None],
        cut[..., None])


def _key_cols(j: jax.Array, blk: int) -> jax.Array:
    return j * blk + jnp.arange(blk, dtype=jnp.int32)


def _key_block(buf: jax.Array, j: jax.Array, blk: int) -> jax.Array:
    """Block j of a piece's sort keys ``buf [n, S, T]``."""
    return jax.lax.dynamic_slice(buf, (0, 0, j * blk), (*buf.shape[:2], blk))


def piece_threshold(buf: jax.Array, q_pos: jax.Array, kv_len: jax.Array,
                    n_blocks: jax.Array, topk: int
                    ) -> tuple[jax.Array, jax.Array]:
    """``sparse_select.threshold``'s (``thr``, ``cut``) ``[n, S]`` for
    an admission piece's sort keys ``buf [n, S, T]``, live in the first
    ``n_blocks`` blocks of ``KV_BLOCK`` columns: query i of row r keeps
    the ``topk`` largest of the keys it sees (all while fewer).

    Two routes to the same integers, chosen as ``piece_attention``'s
    are. On a TPU the rounds of counting run over a query tile's keys
    held in VMEM, of its row's own live blocks
    (``ops/select_threshold.py``); only the ties' cut walks the buffer
    in XLA, in a wave some query of which has its k-th score tied.
    Elsewhere every round walks the buffer's live blocks in XLA, which
    the tests hold the kernel to."""
    t = buf.shape[2]
    blk = min(KV_BLOCK, t)

    def count(test):
        def one(j):
            return jnp.sum(test(_key_block(buf, j, blk), _key_cols(j, blk)),
                           axis=-1, dtype=jnp.int32)

        return jax.lax.fori_loop(
            0, n_blocks, lambda j, acc: acc + one(j),
            jnp.zeros(jax.eval_shape(one, 0).shape, jnp.int32))

    k = jnp.clip(jnp.minimum(q_pos + 1, kv_len[:, None]), 1, topk)
    if not latent_prefill_attention.serves(blk):
        return sparse_select.threshold(count, k, t)
    # (one block at least: a row of no length counts a block of NEVER,
    # as the XLA rounds do)
    thr, above, at = select_threshold.piece_threshold(
        buf, k, jnp.clip((kv_len + blk - 1) // blk, 1, n_blocks), blk)
    return thr, sparse_select.tie_cut(count, thr, k - above, at, t)


def threshold_keys_read(kv_len: list[int], s: int, extent: int) -> int:
    """Sort keys that ``piece_threshold`` reads for a wave of pieces of
    ``s`` queries whose rows hold ``kv_len`` positions (host numbers),
    in a buffer of ``extent`` columns, on the route it takes here: the
    kernel copies each row's own live blocks in once; the XLA rounds
    walk the wave's live blocks of every row ``sparse_select.PASSES``
    times."""
    blk = min(KV_BLOCK, extent)
    live = [max(-(-n // blk), 1) for n in kv_len]
    if latent_prefill_attention.serves(blk):
        return s * blk * sum(live)
    return sparse_select.PASSES * len(live) * s * blk * max(live)


def expand_bytes_moved(kv_len: list[int], extent: int, cfg: DecoderConfig,
                       itemsize: int) -> int:
    """Bytes of expanded keys and values that a wave's rounds hand to
    their folds (host numbers): its live rounds (the longest row's) x
    the layers x every row's and head's ``KV_BLOCK`` columns of a key
    ``dn + dr`` and a value ``dv`` wide, ``itemsize`` bytes each. On
    the kernel's route these are ``fold_round``'s operands, written
    once by ``expand_heads`` and read once by the kernel."""
    blk = min(KV_BLOCK, extent)
    rounds = max(-(-max(kv_len) // blk), 0)
    width = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim
    return rounds * cfg.n_layers * len(kv_len) * cfg.n_heads * blk \
        * width * itemsize


def select_step(q_i: jax.Array, w_i: jax.Array, k_cur: jax.Array,
                idx_l: jax.Array, idx_win_l: jax.Array, pos0: jax.Array,
                w: jax.Array, cfg: DecoderConfig
                ) -> tuple[jax.Array, jax.Array]:
    """Which positions a decode step's token reads, ``absorbed_
    attention``'s ``keep``: index queries ``q_i [B, Hi, Di]`` with
    weights ``w_i [B, Hi]`` score one layer of the cached index keys
    ``idx_l [B, Di, T]`` (live below ``pos0``), the dispatch's own
    ``idx_win_l [B, W, Di]`` (live below step ``w``) and the token's
    own ``k_cur [B, Di]``; the ``index_topk`` largest of the live ones
    (all while fewer) are kept, ties to the lower position: cached
    columns come before the dispatch's rows, as their positions do."""
    t, n_win = idx_l.shape[-1], idx_win_l.shape[1]
    own = jnp.concatenate([idx_win_l, k_cur[:, None]], axis=1)
    scores = jnp.concatenate([
        index_scores(q_i[:, None], w_i[:, None], idx_l)[:, 0],
        index_scores(q_i[:, None], w_i[:, None],
                     own.transpose(0, 2, 1))[:, 0]], axis=-1)
    with scope("select"):
        col = jnp.arange(t + n_win + 1)
        live = jnp.where(col < t, col[None] < pos0[:, None],
                         (col - t)[None] < w) | (col == t + n_win)
        keys = sparse_select.sort_keys(scores, live)
        seen = jnp.minimum(pos0, t) + w + 1
        thr, cut = sparse_select.threshold(
            sparse_select.count_over(keys),
            jnp.minimum(seen, cfg.index_topk), t + n_win + 1)
        keep = sparse_select.chosen(keys, col, thr[:, None], cut[:, None])
    return keep[:, :t], keep[:, t:]


# ---------------------------------------------------------------------------
# Sparse experts, dropless
# ---------------------------------------------------------------------------


@scope("moe_route")
def route(hid: jax.Array, layer: Params, cfg: DecoderConfig
          ) -> tuple[jax.Array, jax.Array]:
    """hid ``[T, d]`` float32, the sublayer's input BEFORE it is
    rounded to the activation type → (chosen experts ``[T, k]``, their
    gates ``[T, k]`` float32). Scores, the choice and the gates in
    float32 at the highest matmul precision: a near-tie between the
    last expert in and the first out flips as rarely as the arithmetic
    allows."""
    scores = jax.nn.sigmoid(jnp.matmul(
        hid, layer["router"], precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + layer["e_bias"], cfg.experts_per_token)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = cfg.routed_scaling_factor * chosen \
        / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, gates


@scope("moe_route")
def group_by_expert(idx: jax.Array, live: jax.Array, n_experts: int,
                    first: int, count: int):
    """Sort the token-expert pairs ``idx [T, k]`` of live tokens by
    expert, experts ``[first, first + count)`` first and in order;
    pairs of other experts and of tokens that are not ``live`` go to
    the end and belong to no group. → (token of each sorted pair
    ``[T k]``, its group or ``count``, where each pair went, group
    sizes ``[count]``, the first three of ``N_COUNTS`` over ALL
    experts)."""
    t, k = idx.shape
    flat = idx.reshape(-1)
    alive = jnp.repeat(live, k)
    mine = alive & (flat >= first) & (flat < first + count)
    group = jnp.where(mine, flat - first, count)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    per = jnp.zeros((n_experts + 1,), jnp.int32).at[
        jnp.where(alive, flat, n_experts)].add(1)[:n_experts]
    counts = jnp.stack([jnp.sum(per > 0), jnp.sum(per), jnp.max(per)])
    return order // k, group[order], jnp.argsort(order), sizes, \
        counts.astype(jnp.int32)


def _grouped(x: jax.Array, w, li: jax.Array, sizes: jax.Array
             ) -> jax.Array:
    """Float32 ``x[rows of g] @ w[li, g]``: x ``[m, k]`` sorted by
    group, w ``[L, G, k, n]``. int8 with scales ``[L, G, 1, n]`` (what
    is served) goes through ``ops/grouped_matmul.py`` on every backend,
    interpreted off a TPU; plain matrices (an engine without
    ``quantize``) through ``jax.lax.ragged_dot``, which the tests hold
    the kernel to."""
    if quant_kind(w) == "int8":
        return grouped_qmatmul(x, w["q"], w["scale"], sizes, li)
    return jax.lax.ragged_dot(
        x, jax.lax.dynamic_index_in_dim(w, li, 0, keepdims=False
                                        ).astype(x.dtype),
        sizes, preferred_element_type=jnp.float32)


def routed_experts(hid: jax.Array, layer: Params, experts: Params,
                   li: jax.Array, cfg: DecoderConfig, live: jax.Array,
                   held: tuple[int, int] | None = None, dtype=None
                   ) -> tuple[jax.Array, jax.Array]:
    """The routed experts' part of the layer for tokens hid ``[T, d]``
    float32 (routed as they are, multiplied in ``dtype``, default
    bfloat16): float32 ``[T, d]`` and the routing's counts. ``layer`` has the
    router; ``experts`` the stacks of ``EXPERTS`` for all layers, of
    which layer ``li`` is read. ``held = (first, count)``: the experts
    this device holds (default all; ``experts`` then holds those
    alone); the choice is made over all experts and only the held
    ones' terms are added; the counts are over all experts too, unless
    the config itself names a share (``cfg.held_experts``: the served
    path of a device that holds one counts what it is asked for). No
    token is dropped: every live pair of a held expert is computed,
    each from its own row alone."""
    first, count = held or (0, cfg.n_routed_experts)
    idx, gates = route(hid, layer, cfg)
    tok, group, back, sizes, counts = group_by_expert(
        idx, live, cfg.n_routed_experts, first, count)
    if cfg.held_experts:
        # a device that holds a share counts what it is asked for
        counts = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes),
                            jnp.max(sizes)]).astype(jnp.int32)
    counts = jnp.concatenate([counts, tile_counts(
        sizes, idx.size, hid.shape[-1], cfg.moe_intermediate_size)])
    with scope("moe_experts"):
        hid = hid.astype(dtype or jnp.bfloat16)
        xs = hid[tok]
        act = jax.nn.silu(
            _grouped(xs, experts["we_gate"], li, sizes)) \
            * _grouped(xs, experts["we_up"], li, sizes)
        y = _grouped(act.astype(hid.dtype), experts["we_down"], li, sizes)
        # a pair of no group has no product: what stands there is not
        # a number the kernel wrote
        y = jnp.where((group < count)[:, None], y, 0.0)[back]
        y = y.reshape(*gates.shape, -1) * gates[..., None]
        return jnp.sum(y, axis=1), counts


def ffn(hid: jax.Array, layer: Params, experts: Params, li: jax.Array,
        cfg: DecoderConfig, live: jax.Array, dtype
        ) -> tuple[jax.Array, jax.Array]:
    """The feed-forward sublayer: its input ``[n, S, d]`` float32 →
    float32 ``[n, S, d]`` and the routing's counts: a SwiGLU (the dense
    layers), or the routed experts plus the shared expert's SwiGLU,
    multiplied in ``dtype``; the router reads the input unrounded.
    Under ``cfg.held_experts`` ``experts`` holds that share alone and
    only its terms are added (``routed_experts``); the shared expert is
    whole on every device."""
    y = L.swiglu(hid.astype(dtype), layer).astype(jnp.float32)
    if "router" not in layer:
        return y, jnp.zeros((N_COUNTS,), jnp.int32)
    n, s, d = hid.shape
    # (under ``ffn`` as well: a reduction that knows ``SCOPES`` alone
    # files the experts there, one that knows ``XING_SCOPES`` apart)
    with scope("ffn"):
        routed, counts = routed_experts(
            hid.reshape(n * s, d), layer, experts, li, cfg,
            live.reshape(n * s), tuple(cfg.held_experts) or None,
            dtype=dtype)
    return y + routed.reshape(n, s, d), counts


def _split(stack: Params) -> tuple[Params, Params]:
    """A stack's leaves that a layer scan slices, and the expert
    stacks it closes over."""
    return ({k: v for k, v in stack.items() if k not in EXPERTS},
            {k: v for k, v in stack.items() if k in EXPERTS})


# ---------------------------------------------------------------------------
# Embedding and head
# ---------------------------------------------------------------------------


def embed(params: Params, tokens: jax.Array, cfg: DecoderConfig
          ) -> jax.Array:
    """The streams at the input: the embedding, ``hc_mult`` times."""
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)
        return jnp.broadcast_to(x, (cfg.hc_mult, *x.shape))


@scope("unembed")
def unembed(x: jax.Array, params: Params, cfg: DecoderConfig) -> jax.Array:
    """Streams ``[n, ..., d]`` → float32 logits ``[..., V]``: the
    streams summed, normed, and the head with float32 accumulation."""
    w = params["lm_head"]
    xn = _norm(jnp.sum(x, axis=0), params["final_norm"], cfg,
               params["tok_emb"].dtype)
    if quant_kind(w) == "int8":
        return jnp.matmul(xn, w["q"].astype(xn.dtype),
                          preferred_element_type=jnp.float32) * w["scale"]
    return jnp.matmul(xn, w, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Admission: one piece of a prompt per row
# ---------------------------------------------------------------------------


def prefill_piece(params: Params, tokens: jax.Array, lens: jax.Array,
                  pos0: jax.Array, slots: jax.Array, cfg: DecoderConfig,
                  cache: Params) -> tuple[jax.Array, Params, jax.Array]:
    """One piece of a prompt for each of n rows, into the rows' slots.

    tokens ``[n, S]`` right-padded, ``lens[r]`` real; row r's piece
    starts at absolute position ``pos0[r]`` and ``pos0[r] + S`` lies
    within the cache (the engine cuts pieces at multiples of its
    largest bucket, which divides ``max_len``). The piece's latent rows
    go into the slot at their positions, then its queries attend in
    expanded form to everything the slot holds up to themselves
    (``piece_attention``; with a selection the positions' index keys go
    into the slot beside them and each query reads the columns
    ``select_piece`` keeps). Columns past ``lens[r]`` take rows nobody
    reads before they are written again; their tokens are not routed.
    The cache rides the layer scans' carry and is touched a row at a
    time. Rows may repeat (the engine pads a wave with copies of its
    first row: the same writes twice). Returns (logits after each row's
    last token ``[n, V]`` float32, cache, counts ``[N_COUNTS]``)."""
    n, s = tokens.shape
    dt = params["tok_emb"].dtype
    q_pos = pos0[:, None] + jnp.arange(s)[None, :]
    kv_len = pos0 + lens
    live = jnp.arange(s)[None, :] < lens[:, None]
    angles = q_pos[..., None].astype(jnp.float32) * rope_inv_freq(cfg)
    t = next(iter(cache.values())).shape[3]
    blk = min(KV_BLOCK, t)
    n_blocks = (jnp.max(kv_len) + blk - 1) // blk
    scale = softmax_scale(cfg)
    x = embed(params, tokens, cfg)
    counts = jnp.zeros((N_COUNTS,), jnp.int32)
    out = {}

    def write(cache_a, rows, li):
        with scope("kv_write"):
            for r in range(n):
                cache_a = jax.lax.dynamic_update_slice(
                    cache_a, rows[r].T[None, None],
                    (li, slots[r], 0, pos0[r]))
        return cache_a

    def body(experts, carry, scanned):
        x, cache_a, idx_a, counts = carry
        layer, li = scanned
        u, maps = sublayer_in(x, layer, "attn", cfg)
        hid = _norm(u, layer["attn_norm"], cfg, dt)
        q_n, q_r, latent, c_q = project(hid, layer, cfg, angles,
                                        cache_a.dtype)
        cache_a = write(cache_a, latent, li)
        keep = None
        if cfg.selects:
            q_i, k_i, w_i = index_project(hid, c_q, layer, cfg, angles,
                                          idx_a.dtype)
            idx_a = write(idx_a, k_i, li)
            keep = select_piece(q_i, w_i, idx_a, li, slots, q_pos, kv_len,
                                n_blocks, cfg)
        with scope("qkv"):
            q = (jnp.concatenate([q_n, q_r], axis=-1).astype(jnp.float32)
                 * scale).astype(dt)
        o = piece_attention(q, cache_a, li, slots, q_pos, kv_len,
                            n_blocks, layer, cfg, keep)
        x = sublayer_out(x, L.attn_out(o, layer), maps)
        u, maps = sublayer_in(x, layer, "ffn", cfg)
        y, c = ffn(_norm(u, layer["ffn_norm"], cfg, jnp.float32), layer,
                   experts, li, cfg, live, dt)
        return (sublayer_out(x, y, maps), cache_a, idx_a, counts + c), None

    for name, count in stacks(cfg).items():
        layers, experts = _split(params[name])
        (x, out[name], idx_a, counts), _ = jax.lax.scan(
            functools.partial(body, experts),
            (x, cache[name], cache.get(name + IDX), counts),
            (layers, jnp.arange(count)))
        if cfg.selects:
            out[name + IDX] = idx_a
    x_last = jnp.take_along_axis(
        x, jnp.broadcast_to((lens - 1)[None, :, None, None],
                            (cfg.hc_mult, n, 1, x.shape[-1])), axis=2)
    return unembed(x_last[:, :, 0], params, cfg), out, counts


# ---------------------------------------------------------------------------
# Decode: a dispatch of ``steps`` tokens for every slot
# ---------------------------------------------------------------------------


def decode_step(params: Params, tok: jax.Array, pos0: jax.Array,
                w: jax.Array, cfg: DecoderConfig, cache: Params,
                win: Params, max_len: int, plan: tuple | None = None
                ) -> tuple[jax.Array, Params, jax.Array]:
    """Step ``w`` (traced) of a dispatch that began at positions
    ``pos0``: one token per slot against a read-only cache and the
    dispatch's own rows ``win`` (a ``[La, B, W, R]`` per stack, live
    below ``w``). A slot that is not decoding stands at ``max_len``:
    its token is computed and not routed. Without a ``plan`` a layer
    scan takes its stack of the cache as scanned input and scores the
    layer whole; with one (``latent_attention.plan_blocks`` for this
    dispatch) it closes over the stack, of which the kernel reads each
    slot's live blocks in place: no layer of the cache is ever made.
    With a selection the index keys (cache and window, ``name + IDX``)
    ride the scan as scanned inputs on either route: the indexer scores
    a layer of them whole. Returns (logits ``[B, V]`` float32, this
    step's rows ``[La, B, R]`` per stack, and index keys ``[La, B, Di]``
    beside them, counts)."""
    dt = params["tok_emb"].dtype
    live = (pos0 < max_len)[:, None]
    angles = (pos0 + w)[:, None, None].astype(jnp.float32) \
        * rope_inv_freq(cfg)
    x = embed(params, tok[:, None], cfg)
    counts = jnp.zeros((N_COUNTS,), jnp.int32)
    cols = {}

    def body(experts, cache_a, carry, scanned):
        x, counts = carry
        layer, li, win_l, cache_l, idx_win_l, idx_l = scanned
        u, maps = sublayer_in(x, layer, "attn", cfg)
        hid = _norm(u, layer["attn_norm"], cfg, dt)
        q_n, q_r, latent, c_q = project(hid, layer, cfg, angles,
                                        win_l.dtype)
        keep = k_i = None
        if cfg.selects:
            q_i, k_i, w_i = index_project(hid, c_q, layer, cfg, angles,
                                          idx_win_l.dtype)
            keep = select_step(q_i[:, 0], w_i[:, 0], k_i[:, 0], idx_l,
                               idx_win_l, pos0, w, cfg)
            k_i = k_i[:, 0]
        o = absorbed_attention(
            q_n[:, 0], q_r[:, 0], latent[:, 0], cache_l, win_l, pos0, w,
            layer, cfg, live=(cache_a, li, plan) if plan else None,
            keep=keep)
        x = sublayer_out(x, L.attn_out(o[:, None], layer), maps)
        u, maps = sublayer_in(x, layer, "ffn", cfg)
        y, c = ffn(_norm(u, layer["ffn_norm"], cfg, jnp.float32), layer,
                   experts, li, cfg, live, dt)
        return (sublayer_out(x, y, maps), counts + c), (latent[:, 0], k_i)

    for name, count in stacks(cfg).items():
        layers, experts = _split(params[name])
        (x, counts), (cols[name], k_i) = jax.lax.scan(
            functools.partial(body, experts, cache[name]), (x, counts),
            (layers, jnp.arange(count), win[name],
             None if plan else cache[name], win.get(name + IDX),
             cache.get(name + IDX)))
        if cfg.selects:
            cols[name + IDX] = k_i
    return unembed(x[:, :, 0], params, cfg), cols, counts


@scope("kv_write")
def merge_latents(cache_a: jax.Array, win_a: jax.Array, pos0: jax.Array,
                  steps: int) -> jax.Array:
    """A dispatch's rows ``win_a [La, B, W, R]`` into the cache
    ``[La, B, R, T]``, once, in place: slot b's first ``steps`` rows
    land at the columns ``pos0[b] + [0, steps)``; one at or past the
    extent is dropped (a slot that is not decoding stands there). One
    slab a slot, by ``decoder.merge_window``'s scatter, for its
    reasons: the only index is the slab's first column, the batch axis
    is the cache's own, and a slab that would run past the extent is
    laid over the last columns with what the cache holds there."""
    s_max = cache_a.shape[3]
    w = min(win_a.shape[2], steps, s_max)
    start = jnp.clip(pos0, 0, s_max - w)
    shift = pos0 - start
    fresh = (jnp.arange(w)[None, :] >= shift[:, None])[None, :, None, :]
    roll = jax.vmap(lambda win_b, n: jnp.roll(win_b, n, axis=1),
                    in_axes=(1, 0), out_axes=1)
    new = roll(win_a[:, :, :w], shift).astype(cache_a.dtype)
    slab = jnp.where(fresh, new.transpose(0, 1, 3, 2),
                     cache_a[:, :, :, s_max - w:])
    return jax.lax.scatter(
        cache_a, start[:, None], slab.transpose(1, 0, 2, 3),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2, 3), inserted_window_dims=(),
            scatter_dims_to_operand_dims=(3,), operand_batching_dims=(1,),
            scatter_indices_batching_dims=(0,)),
        unique_indices=True,
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def decode_tokens(params: Params, tokens: jax.Array, pos0: jax.Array,
                  cfg: DecoderConfig, cache: Params, key: jax.Array,
                  sample_fn, *, steps: int, max_len: int,
                  with_logits: bool = False, live_blocks: bool = False):
    """``steps`` tokens for every slot in one program: decode → sample
    → feed back, the cache read-only until one merge at the end (the
    discipline of the engine's ``_decode``: a cache in the token loop's
    carry is copied every token). ``live_blocks``: attention reads each
    slot's live blocks of the cache in place (``decode_step`` with the
    dispatch's plan, made here, once). Returns (tokens ``[steps, B]``,
    cache, counts ``[N_COUNTS]`` summed over layers and steps) and,
    ``with_logits``, every step's logits ``[steps, B, V]``."""
    b = tokens.shape[0]
    win = {name: jnp.zeros((a.shape[0], b, steps, a.shape[2]), a.dtype)
           for name, a in cache.items()}
    plan = latent_attention.plan_blocks(
        pos0, extent=next(iter(cache.values())).shape[3]) \
        if live_blocks else None

    def body(carry, w):
        tok, win, counts, key = carry
        key, sub = jax.random.split(key)
        logits, cols, c = decode_step(params, tok, pos0, w, cfg, cache,
                                      win, max_len, plan)
        with scope("kv_write"):
            win = {name: jax.lax.dynamic_update_slice_in_dim(
                a, cols[name][:, :, None].astype(a.dtype), w, axis=2)
                for name, a in win.items()}
        nxt = sample_fn(logits, sub)
        return (nxt, win, counts + c, key), \
            (nxt, logits if with_logits else None)

    (_, win, counts, _), (toks, logits) = jax.lax.scan(
        body, (tokens, win, jnp.zeros((N_COUNTS,), jnp.int32), key),
        jnp.arange(steps))
    cache = {name: merge_latents(a, win[name], pos0, steps)
             for name, a in cache.items()}
    if with_logits:
        return toks, cache, counts, logits
    return toks, cache, counts
