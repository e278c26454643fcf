"""Decoder whose layers are of two KINDS, window and global, each kind
with a cache of its own (``cfg.attention == "mixed"``; the
``cohere2_moe`` class).

What sets the block apart, each under a key of ``models/configs.py``:

* **The layers repeat in periods** of ``layer_period``: member
  ``global_member`` of a period is a FULL layer (every query attends to
  every earlier position), the others are WINDOW layers (a query at
  position t attends to ``t - sliding_window < s <= t``). A kind
  rotates its queries and keys (interleaved pairs ``(2i, 2i + 1)``,
  ``theta ** (-2i / head_dim)``) or leaves them plain (``window_rope``,
  ``global_rope``: the family's global layers carry no positional
  encoding). Grouped queries: head j reads key/value head
  ``j // (n_heads / n_kv_heads)``.
* **One norm a layer feeds attention and experts side by side**
  (``parallel_block``): ``x' = x + A(h) + E(h)``, ``h = norm(x)``,
  ``norm_kind`` ``"layer"`` (mean-centred, a gain, no bias) or
  ``"rms"``.
* **The feed-forward part is sparse in every layer**: sigmoid scores,
  the ``experts_per_token`` largest chosen, gates normalised over the
  chosen; the routing, the grouping and the grouped matmuls are
  ``models/xing.py``'s (``routed_experts``: dropless, a device told
  which experts it holds adds its own experts' terms). Beside them
  ``n_shared_experts`` SwiGLUs of the experts' width whose outputs are
  summed or averaged (``shared_expert_combine``), held as ONE SwiGLU
  of ``n_shared_experts`` times the width (the same sum).
* **The head is the embedding** (``tie_embeddings``), its logits
  scaled by ``logit_scale``.

State, a dict of one array per layer kind and half: full layers
``full_k`` / ``full_v`` ``[Lf, slots, Hkv, max_len, Dh]``, position p
in column p; window layers ``window_k`` / ``window_v`` ``[Lw, slots,
Hkv, R, Dh]``, a RING on the absolute timeline: position p lives in
column ``p % R``, ``R = sliding_window + the largest admission piece``
(``ring_len``). Column c of a slot that holds ``n`` positions holds
position ``c + R * ((n - 1 - c) // R)``: the latest one of its residue
(negative: never written). A piece of S <= R - sliding_window
positions that starts at a multiple of the largest piece never wraps,
and what it overwrites lies more than a window behind every one of its
queries; a slot's reuse shows nothing of its last request, because a
column is read only where the position it would hold under the NEW
count lies within a query's window. Admission writes a piece's rows in
place and reads, per row, the slot's ring turned into timeline order
(window layers) or its extent (full layers) through the flash kernel
on a TPU (``ops/flash_attention.py``: query offset, begin bound,
window) and a masked softmax elsewhere. A decode dispatch keeps its
own columns in small buffers, reads the caches in place and merges
once at its end (``merge``); on a TPU the cached columns go through
``ops/dense_attention.py``, which fetches a slot's live blocks alone:
one contiguous range of a full layer, and of a ring the range that a
query's window leaves, as one call or, where it wraps, two.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from copilot_for_consensus_tpu.models import decoder, xing
from copilot_for_consensus_tpu.models import layers as L
from copilot_for_consensus_tpu.models.configs import DecoderConfig
from copilot_for_consensus_tpu.models.quant import (
    quant_kind,
    quantize_tensor,
)
from copilot_for_consensus_tpu.obs.profile import scope
from copilot_for_consensus_tpu.ops import dense_attention, flash_attention
from copilot_for_consensus_tpu.ops.attention import (
    combine_partials,
    decode_window_partial,
)

Params = dict[str, Any]

#: leaves served as int8 (``quantize_params``); the router stays in
#: float32, the embedding (which is the head) in the activation type
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
            *xing.EXPERTS)

N_COUNTS = xing.N_COUNTS

# ---------------------------------------------------------------------------
# Shapes, parameters, cache
# ---------------------------------------------------------------------------


def layer_kinds(cfg: DecoderConfig) -> tuple[str, ...]:
    """``"window"`` or ``"full"``, layer by layer."""
    return tuple("full" if li % cfg.layer_period == cfg.global_member
                 else "window" for li in range(cfg.n_layers))


def stacks(cfg: DecoderConfig) -> dict[str, int]:
    """Layers of each kind: the leading axis of its cache."""
    kinds = layer_kinds(cfg)
    return {"window": kinds.count("window"), "full": kinds.count("full")}


def ring_len(cfg: DecoderConfig, piece: int) -> int:
    """Columns of a window layer's ring in an engine whose largest
    admission piece is ``piece``."""
    return cfg.sliding_window + piece


def init_params(rng: jax.Array, cfg: DecoderConfig, dtype=jnp.bfloat16,
                quantize: bool = False) -> Params:
    """Random weights in this module's layout: one stack of all layers
    (both kinds hold the same matrices), the expert stacks beside it."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n, e, fe = cfg.n_layers, cfg.n_routed_experts, cfg.moe_intermediate_size
    f = fe * cfg.n_shared_experts
    e_held = xing.held_experts(cfg)[1]
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in, dt=dtype):
        return (jax.random.truncated_normal(next(keys), -2, 2, shape,
                                            jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def gain(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    params = {
        "tok_emb": dense((cfg.vocab_size, d), d),
        "final_norm": gain((d,)),
        "layers": {
            "norm": gain((n, d)),
            "wq": dense((n, d, h * dh), d),
            "wk": dense((n, d, hkv * dh), d),
            "wv": dense((n, d, hkv * dh), d),
            "wo": dense((n, h * dh, d), h * dh),
            "w_gate": dense((n, d, f), d),
            "w_up": dense((n, d, f), d),
            "w_down": dense((n, f, d), f),
            "router": dense((n, d, e), d, jnp.float32),
            "we_gate": dense((n, e_held, d, fe), d),
            "we_up": dense((n, e_held, d, fe), d),
            "we_down": dense((n, e_held, fe, d), fe),
        },
    }
    return quantize_params(params) if quantize else params


def quantize_params(params: Params) -> Params:
    """int8 with one float32 scale per output channel (per expert and
    channel in an expert stack) for every leaf of ``MATRICES``; leaves
    that are already quantized pass."""
    def q(leaf):
        return leaf if quant_kind(leaf) else quantize_tensor(leaf)

    return dict(params, layers={k: q(v) if k in MATRICES else v
                                for k, v in params["layers"].items()})


def init_cache(cfg: DecoderConfig, batch: int, max_len: int, piece: int,
               dtype=jnp.bfloat16) -> Params:
    """``piece``: the largest admission piece (``ring_len``)."""
    count = stacks(cfg)
    cols = {"window": ring_len(cfg, piece), "full": max_len}
    return {f"{kind}_{half}": jnp.zeros(
        (count[kind], batch, cfg.n_kv_heads, cols[kind], cfg.head_dim),
        dtype) for kind in ("window", "full") for half in ("k", "v")}


# ---------------------------------------------------------------------------
# The layer's parts
# ---------------------------------------------------------------------------


def inv_freq(cfg: DecoderConfig) -> jax.Array:
    return L.rope_frequencies(cfg.head_dim, cfg.rope_theta)


@scope("norm_rope")
def rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate the interleaved pairs ``(x[2i], x[2i + 1])`` of the last
    axis by ``angles`` ``[..., Dh / 2]`` (broadcast against ``x``'s
    leading axes): ``xing.rope``'s function with the last axis left
    whole (each value's partner fetched by a shift, not by splitting
    the axis in pairs: the split reaches back through the projection
    and the chip's compiler then turns the query matrix over, a copy of
    it every step)."""
    xf = x.astype(jnp.float32)
    cos = jnp.repeat(jnp.cos(angles), 2, axis=-1)
    sin = jnp.repeat(jnp.sin(angles), 2, axis=-1)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


@scope("norm_rope")
def norm(x: jax.Array, gain: jax.Array, cfg: DecoderConfig, dtype
         ) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "layer":
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + cfg.norm_eps)
            * gain.astype(jnp.float32)).astype(dtype)


def project(hid: jax.Array, layer: Params, kind: str, cfg: DecoderConfig,
            angles: jax.Array):
    """hid ``[n, S, d]`` → queries ``[n, S, H, Dh]``, keys and values
    ``[n, S, Hkv, Dh]``, queries and keys rotated by ``angles``
    ``[n, S, Dh / 2]`` where the layer's kind has a rotary encoding."""
    n, s, _ = hid.shape
    dh = cfg.head_dim
    with scope("qkv"):
        q = L.qmatmul(hid, layer["wq"]).reshape(n, s, cfg.n_heads, dh)
        k = L.qmatmul(hid, layer["wk"]).reshape(n, s, cfg.n_kv_heads, dh)
        v = L.qmatmul(hid, layer["wv"]).reshape(n, s, cfg.n_kv_heads, dh)
    if cfg.global_rope if kind == "full" else cfg.window_rope:
        q = rope(q, angles[:, :, None, :])
        k = rope(k, angles[:, :, None, :])
    return q, k, v


def experts_part(hid: jax.Array, layer: Params, experts: Params,
                 li: jax.Array, cfg: DecoderConfig, live: jax.Array, dtype
                 ) -> tuple[jax.Array, jax.Array]:
    """The layer's feed-forward part for its normed input ``[n, S, d]``
    float32: float32 ``[n, S, d]`` and the routing's counts. The shared
    experts multiplied in ``dtype``, whole on every device; the routed
    experts by ``xing.routed_experts`` (the router reads the input
    unrounded; under ``cfg.held_experts`` only the held ones' terms)."""
    n, s, d = hid.shape
    with scope("shared_experts"):
        hb = hid.astype(dtype)
        gate = jax.nn.silu(L.qmatmul(hb, layer["w_gate"])
                           .astype(jnp.float32))
        up = L.qmatmul(hb, layer["w_up"]).astype(jnp.float32)
        y = L.qmatmul((gate * up).astype(dtype),
                      layer["w_down"]).astype(jnp.float32)
        if cfg.shared_expert_combine == "average":
            y = y / cfg.n_shared_experts
    # no correction bias here: ``xing.route`` chooses by the scores
    # themselves (of equal scores the lower expert), left as it is
    unbiased = dict(layer, e_bias=jnp.zeros(
        (cfg.n_routed_experts,), jnp.float32))
    routed, counts = xing.routed_experts(
        hid.reshape(n * s, d), unbiased, experts, li, cfg,
        live.reshape(n * s), tuple(cfg.held_experts) or None, dtype=dtype)
    return y + routed.reshape(n, s, d), counts


def _split(stack: Params) -> tuple[Params, Params]:
    """The leaves a layer scan slices, and the expert stacks it closes
    over (``ops/grouped_matmul.py`` reads a layer's in place)."""
    return ({k: v for k, v in stack.items() if k not in xing.EXPERTS},
            {k: v for k, v in stack.items() if k in xing.EXPERTS})


def _by_period(tree: Params, period: int) -> Params:
    return jax.tree.map(
        lambda a: a.reshape(-1, period, *a.shape[1:]), tree)


def _layer(layers: Params, li: jax.Array) -> Params:
    """Layer ``li`` (traced) of the stack: one slice a leaf, as a layer
    scan takes its own (a period's slice cut again member by member is
    a copy of the period's matrices, every step)."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, li, 0, keepdims=False), layers)


def _members(cfg: DecoderConfig):
    """(member, kind, index among the period's layers of its kind)."""
    g = cfg.global_member
    return [(m, "full", 0) if m == g else (m, "window", m - (m > g))
            for m in range(cfg.layer_period)]


@scope("unembed")
def unembed(x: jax.Array, params: Params, cfg: DecoderConfig) -> jax.Array:
    """``[..., d]`` → float32 logits ``[..., V]``: the head is the
    embedding."""
    xn = norm(x, params["final_norm"], cfg, params["tok_emb"].dtype)
    return cfg.logit_scale * jnp.einsum(
        "...d,vd->...v", xn, params["tok_emb"],
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Admission: one piece of a prompt per row
# ---------------------------------------------------------------------------


def piece_timeline(kind: str, pos0, lens, s: int, t: int,
                   cfg: DecoderConfig):
    """Where a piece of ``s`` queries a row (row r's at positions
    ``pos0[r] + [0, s)``, ``lens[r]`` real) stands in the timeline of
    ``t`` columns that ``piece_attention`` lays out for a layer of
    ``kind``: (the first query's column, the first column that holds a
    position, the columns that hold one, each ``[n]``; the window as a
    distance, 0 for none). Traced arrays or, for the engine's step
    records, numpy's: the same arithmetic."""
    if kind == "window":
        return (pos0 * 0 + (t - s), (t - s - pos0).clip(0), t - s + lens,
                cfg.sliding_window)
    return pos0, pos0 * 0, pos0 + lens, 0


def piece_tiles(pos0, lens, s: int, max_len: int, ring: int,
                cfg: DecoderConfig) -> tuple[int, int, int]:
    """Key tiles the admission kernel walks for a wave (host ``pos0``,
    ``lens [n]``, pieces of ``s``), summed over the model's layers and
    query heads: ``(whole, edge, dead)``, dead what a walk of every
    tile of a timeline would have fetched beside (``ops/
    flash_attention.py:tile_counts``)."""
    total = np.zeros(3, np.int64)
    kinds = layer_kinds(cfg)
    for kind, t in (("full", max_len), ("window", ring)):
        q_off, begin, kv_len, window = piece_timeline(
            kind, np.asarray(pos0), np.asarray(lens), s, t, cfg)
        tiles = flash_attention.tile_counts(
            q_off, begin, kv_len, s, t, cfg.head_dim, causal=True,
            window=window)
        total += kinds.count(kind) * cfg.n_heads * np.asarray(tiles)
    return tuple(int(x) for x in total)


def piece_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                    lk: jax.Array, slots: jax.Array, pos0: jax.Array,
                    lens: jax.Array, kind: str, cfg: DecoderConfig,
                    impl: str = "auto") -> jax.Array:
    """A piece's queries ``[n, S, H, Dh]`` (row r's at positions
    ``pos0[r] + [0, S)``, ``lens[r]`` real) over what layer ``lk`` of
    its kind's cache holds for the rows' slots, the piece's own columns
    included (written before the call) → ``[n, S, H * Dh]``.

    A full layer's timeline is its columns. A window layer's is its
    ring turned so that column i holds position ``pos0 + S - R + i``:
    the piece's queries stand at ``R - S + [0, S)`` whatever the row,
    the positions below zero of a young sequence are cut off by the
    begin bound, and the window is a distance along the timeline. On a
    TPU that is the flash kernel's query offset, begin bound and window
    (``ops/flash_attention.py``: no scores in memory), elsewhere a
    masked softmax."""
    n, s, h, dh = q.shape
    hkv, t = cache_k.shape[2], cache_k.shape[3]

    def rows(a):
        return jnp.stack([jax.lax.dynamic_slice(
            a, (lk, slots[r], 0, 0, 0), (1, 1, hkv, t, dh))[0, 0]
            for r in range(n)])

    k_all, v_all = rows(cache_k), rows(cache_v)
    if kind == "window":
        turn = jax.vmap(lambda a, by: jnp.roll(a, -by, axis=1))
        k_all = turn(k_all, (pos0 + s) % t)
        v_all = turn(v_all, (pos0 + s) % t)
    q_off, begin, kv_len, window = piece_timeline(kind, pos0, lens, s, t,
                                                  cfg)
    qh = q.transpose(0, 2, 1, 3)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    with scope(f"attn_{kind}"):
        if impl == "pallas":
            o = flash_attention.flash_attention(
                qh, k_all, v_all, causal=True, window=window,
                kv_lengths=kv_len, q_offsets=q_off, kv_begins=begin)
        else:
            qg = qh.reshape(n, hkv, h // hkv, s, dh)
            logits = jnp.einsum("nhgsd,nhtd->nhgst", qg, k_all,
                                preferred_element_type=jnp.float32) \
                * dh ** -0.5
            q_at = (q_off[:, None] + jnp.arange(s)[None, :])[..., None]
            col = jnp.arange(t)[None, None, :]
            mask = ((col <= q_at) & (col >= begin[:, None, None])
                    & (col < kv_len[:, None, None]))
            if window:
                mask &= col > q_at - window
            logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            probs = jnp.where(jnp.isnan(probs), 0.0, probs)
            o = jnp.einsum("nhgst,nhtd->nhgsd", probs.astype(v_all.dtype),
                           v_all).reshape(n, h, s, dh)
    return o.transpose(0, 2, 1, 3).reshape(n, s, h * dh)


def prefill_piece(params: Params, tokens: jax.Array, lens: jax.Array,
                  pos0: jax.Array, slots: jax.Array, cfg: DecoderConfig,
                  cache: Params, attn_impl: str = "auto"
                  ) -> tuple[jax.Array, Params, jax.Array]:
    """One piece of a prompt for each of n rows, into the rows' slots.

    tokens ``[n, S]`` right-padded, ``lens[r]`` real; row r's piece
    starts at absolute position ``pos0[r]``, a multiple of the engine's
    largest piece, which divides ``max_len`` and the ring (so a piece's
    columns are one slab in either cache: ``pos0`` on in a full layer,
    ``pos0 % R`` on in a ring). The piece's keys and values go into the
    slot, then its queries attend (``piece_attention``). Columns past
    ``lens[r]`` take rows nobody reads before they are written again
    (in a ring they lie over positions more than a window behind every
    later query); their tokens are not routed. The caches ride the
    scan's carry and are touched a row at a time. Rows may repeat (the
    engine pads a wave with copies of its first row: the same writes
    twice). Returns (logits after each row's last token ``[n, V]``
    float32, cache, counts ``[N_COUNTS]``)."""
    n, s = tokens.shape
    dt = params["tok_emb"].dtype
    q_pos = pos0[:, None] + jnp.arange(s)[None, :]
    live = jnp.arange(s)[None, :] < lens[:, None]
    angles = q_pos[..., None].astype(jnp.float32) * inv_freq(cfg)
    ring = cache["window_k"].shape[3]
    col0 = {"window": pos0 % ring, "full": pos0}
    where = {"window": "ring_write", "full": "kv_write"}
    period = cfg.layer_period
    layers, experts = _split(params["layers"])
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)

    def write(cache_a, new, kind, lk):
        with scope(where[kind]):
            for r in range(n):
                cache_a = jax.lax.dynamic_update_slice(
                    cache_a, new[r].transpose(1, 0, 2)[None, None].astype(
                        cache_a.dtype), (lk, slots[r], 0, col0[kind][r], 0))
        return cache_a

    def body(carry, p):
        x, cache, counts = carry
        cache = dict(cache)
        for m, kind, nth in _members(cfg):
            li = p * period + m
            layer = _layer(layers, li)
            lk = p if kind == "full" else p * (period - 1) + nth
            hid = norm(x, layer["norm"], cfg, jnp.float32)
            q, k, v = project(hid.astype(dt), layer, kind, cfg, angles)
            ck = cache[f"{kind}_k"] = write(cache[f"{kind}_k"], k, kind, lk)
            cv = cache[f"{kind}_v"] = write(cache[f"{kind}_v"], v, kind, lk)
            o = piece_attention(q, ck, cv, lk, slots, pos0, lens, kind,
                                cfg, attn_impl)
            y, c = experts_part(hid, layer, experts, li, cfg, live, dt)
            x = x + L.attn_out(o, layer).astype(jnp.float32) + y
            counts = counts + c
        return (x, cache, counts), None

    (x, cache, counts), _ = jax.lax.scan(
        body, (x, cache, jnp.zeros((N_COUNTS,), jnp.int32)),
        jnp.arange(cfg.n_layers // period))
    x_last = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
    return unembed(x_last[:, 0], params, cfg), cache, counts


# ---------------------------------------------------------------------------
# Decode: a dispatch of ``steps`` tokens for every slot
# ---------------------------------------------------------------------------


def ring_ranges(lo: jax.Array, hi: jax.Array, ring: int):
    """The columns of a ring that hold positions ``[lo, hi)`` (``hi -
    lo <= ring``), as two ranges of columns ``[a, b)``: the run from
    ``lo``'s column to the ring's end or the run's, and what wrapped
    to the ring's head (empty when nothing did). Works on host
    integers, numpy and traced arrays alike."""
    a = lo % ring
    end = a + (hi - lo)
    over = (end - ring) * (end > ring)
    return (a, end - over), (a * 0, over)


def _cached_partial(qg: jax.Array, k_l: jax.Array, v_l: jax.Array,
                    lo: jax.Array, hi: jax.Array, ring: bool):
    """Flash partial of grouped queries ``[B, Hkv, G, Dh]`` over the
    positions ``[lo, hi)`` of one layer of a cache ``[B, Hkv, T, Dh]``,
    every column scored and masked by the position it holds (the XLA
    route: what the kernel's route is held to)."""
    dt = qg.dtype
    t, dh = k_l.shape[2], k_l.shape[3]
    col = jnp.arange(t)[None, :]
    pos = col + t * ((hi[:, None] - 1 - col) // t) if ring else col
    mask = (pos >= lo[:, None]) & (pos < hi[:, None])
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, k_l.astype(dt),
                   preferred_element_type=jnp.float32) * dh ** -0.5
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    acc = jnp.einsum("bhgt,bhtd->bhgd", p.astype(dt), v_l.astype(dt),
                     preferred_element_type=jnp.float32)
    return acc, m, jnp.sum(p, axis=-1, keepdims=True)


def step_attention(q: jax.Array, k_cur: jax.Array, v_cur: jax.Array,
                   kind: str, cache: Params, lk: jax.Array,
                   win_k: jax.Array, win_v: jax.Array, pos0: jax.Array,
                   w: jax.Array, lo: jax.Array, hi: jax.Array,
                   cfg: DecoderConfig, plans: tuple | None) -> jax.Array:
    """One token's queries ``[B, H, Dh]`` at positions ``pos0 + w``
    over layer ``lk`` of its kind's cache (positions ``[lo, hi)`` of
    it: below the dispatch's start and, in a window layer, within the
    window), the dispatch's own columns ``win_*`` ``[B, Hkv, W, Dh]``
    (live below ``w``) and itself, under one softmax → ``[B, H *
    Dh]``. ``plans``: the kernel's plans for the cached columns
    (``dense_attention.plan_blocks``; a ring's two ranges), or None
    for the XLA route."""
    b, h, dh = q.shape
    hkv = k_cur.shape[1]
    qg = q.reshape(b, hkv, h // hkv, dh)
    ck, cv = cache[f"{kind}_k"], cache[f"{kind}_v"]
    with scope(f"attn_{kind}"):
        if plans is not None:
            parts = [dense_attention.live_partial(qg, ck, cv, lk, plan)
                     for plan in plans]
        else:
            parts = [_cached_partial(
                qg, *(jax.lax.dynamic_index_in_dim(a, lk, 0, keepdims=False)
                      for a in (ck, cv)), lo, hi, kind == "window")]
    parts.append(decode_window_partial(
        qg, win_k, win_v, k_cur, v_cur, pos0, w,
        window=cfg.sliding_window if kind == "window" else 0))
    return combine_partials(parts, q.dtype).reshape(b, h * dh)


def decode_step(params: Params, tok: jax.Array, pos0: jax.Array,
                w: jax.Array, cfg: DecoderConfig, cache: Params,
                win: Params, max_len: int, full_plan: tuple | None = None
                ) -> tuple[jax.Array, Params, jax.Array]:
    """Step ``w`` (traced) of a dispatch that began at positions
    ``pos0``: one token per slot against the read-only caches and the
    dispatch's own columns ``win`` (the caches' shapes with ``steps``
    columns, live below ``w``). A slot that is not decoding stands at
    ``max_len``: it reads nothing of the caches and its token is not
    routed. ``full_plan``: the kernel's plan for the full layers
    (the same in every step of the dispatch); with it the rings' plans
    are made here, once a token, and every layer reads its live blocks
    in place. Returns (logits ``[B, V]`` float32, this step's keys and
    values ``[L_kind, B, Hkv, Dh]`` under the caches' names, counts)."""
    dt = params["tok_emb"].dtype
    live = (pos0 < max_len)[:, None]
    t = pos0 + w
    angles = t[:, None, None].astype(jnp.float32) * inv_freq(cfg)
    ring = cache["window_k"].shape[3]
    hi = pos0 * (pos0 < max_len)
    lo = {"full": hi * 0,
          "window": (t + 1 - cfg.sliding_window).clip(0, None).clip(None, hi)}
    plans = {"full": None, "window": None}
    if full_plan is not None:
        plans = {"full": (full_plan,), "window": tuple(
            dense_attention.plan_blocks(a, b, extent=ring)
            for a, b in ring_ranges(lo["window"], hi, ring))}
    period = cfg.layer_period
    layers, experts = _split(params["layers"])
    with scope("embed"):
        x = params["tok_emb"][tok][:, None].astype(jnp.float32)

    def body(carry, scanned):
        x, counts = carry
        p, win_p = scanned
        cols = {name: [] for name in win_p}
        for m, kind, nth in _members(cfg):
            li = p * period + m
            layer = _layer(layers, li)
            lk = p if kind == "full" else p * (period - 1) + nth
            hid = norm(x, layer["norm"], cfg, jnp.float32)
            q, k, v = project(hid.astype(dt), layer, kind, cfg, angles)
            k, v = k[:, 0], v[:, 0]
            o = step_attention(
                q[:, 0], k, v, kind, cache, lk, win_p[f"{kind}_k"][nth],
                win_p[f"{kind}_v"][nth], pos0, w, lo[kind], hi, cfg,
                plans[kind])
            y, c = experts_part(hid, layer, experts, li, cfg, live, dt)
            x = x + L.attn_out(o[:, None], layer).astype(jnp.float32) + y
            counts = counts + c
            cols[f"{kind}_k"].append(k)
            cols[f"{kind}_v"].append(v)
        return (x, counts), {name: jnp.stack(c) for name, c in cols.items()}

    per = {"window": period - 1, "full": 1}
    (x, counts), cols = jax.lax.scan(
        body, (x, jnp.zeros((N_COUNTS,), jnp.int32)),
        (jnp.arange(cfg.n_layers // period),
         {name: _by_period(a, per[name.split("_")[0]])
          for name, a in win.items()}))
    cols = {name: a.reshape(-1, *a.shape[2:]) for name, a in cols.items()}
    return unembed(x[:, 0], params, cfg), cols, counts


def _merge_ring(half: jax.Array, win: jax.Array, pos0: jax.Array,
                live: jax.Array, steps: int) -> jax.Array:
    """A dispatch's columns ``win`` ``[Lw, B, Hkv, W, Dh]`` into a ring
    ``[Lw, B, Hkv, R, Dh]``, in place: slot b's first ``steps`` go to
    the columns ``(pos0[b] + i) % R``; a slot that is not ``live``
    writes nothing. Two slabs a slot, each laid as
    ``decoder.merge_window`` lays its one (a scatter whose only index
    is the slab's first column; columns it must not touch filled with
    what the ring held there when the dispatch ended): the run up to
    the ring's end, laid over the last W columns where it would pass
    it, and what wrapped, laid over the first W."""
    r = half.shape[3]
    w = min(win.shape[3], steps, r // 2)
    c0 = jnp.where(live, pos0 % r, r)
    start = jnp.clip(c0, 0, r - w)
    shift = c0 - start                  # > 0 only where start is r - w
    over = jnp.where(live, shift, 0)    # columns that wrapped
    at = jnp.arange(w)[None, :]

    def cols(mask):                     # [B, w] → over [L, B, Hkv, w, Dh]
        return mask[None, :, None, :, None]

    roll = jax.vmap(lambda win_b, by: jnp.roll(win_b, by, axis=2),
                    in_axes=(1, 0), out_axes=1)
    new = win[:, :, :, :w].astype(half.dtype)

    def lay(half, first, slab):
        return jax.lax.scatter(
            half, first[:, None], slab.transpose(1, 0, 2, 3, 4),
            jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1, 2, 3, 4), inserted_window_dims=(),
                scatter_dims_to_operand_dims=(3,),
                operand_batching_dims=(1,),
                scatter_indices_batching_dims=(0,)),
            unique_indices=True,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    tail = jnp.where(cols(at >= shift[:, None]), roll(new, shift),
                     half[:, :, :, r - w:])
    # column j < over takes the window's column w - over + j; a slot
    # with nothing wrapped lays its one slab a second time (both slabs'
    # old columns are read before either is laid)
    wrapped = over > 0
    head = jnp.where(cols(at < over[:, None]), roll(new, over),
                     half[:, :, :, :w])
    head = jnp.where(cols(wrapped[:, None]), head, tail)
    return lay(lay(half, start, tail), jnp.where(wrapped, 0, start), head)


def merge(cache: Params, win: Params, pos0: jax.Array, steps: int,
          max_len: int) -> Params:
    """A dispatch's own columns into the caches, once, in place."""
    full = decoder.merge_window(
        {"k": cache["full_k"], "v": cache["full_v"]}, win["full_k"],
        win["full_v"], pos0, steps)
    live = pos0 < max_len
    with scope("ring_write"):
        return {"full_k": full["k"], "full_v": full["v"],
                **{name: _merge_ring(cache[name], win[name], pos0, live,
                                     steps)
                   for name in ("window_k", "window_v")}}


def decode_tokens(params: Params, tokens: jax.Array, pos0: jax.Array,
                  cfg: DecoderConfig, cache: Params, key: jax.Array,
                  sample_fn, *, steps: int, max_len: int,
                  with_logits: bool = False, live_blocks: bool = False):
    """``steps`` tokens for every slot in one program: decode → sample
    → feed back, the caches read-only until one merge at the end.
    ``live_blocks``: attention reads each slot's live blocks of the
    caches in place (``decode_step`` with the kernel's plans). Returns
    (tokens ``[steps, B]``, cache, counts ``[N_COUNTS]`` summed over
    layers and steps) and, ``with_logits``, every step's logits
    ``[steps, B, V]``."""
    win = {name: jnp.zeros((*a.shape[:3], steps, a.shape[4]), a.dtype)
           for name, a in cache.items()}
    hi = pos0 * (pos0 < max_len)
    full_plan = dense_attention.plan_blocks(hi * 0, hi, extent=max_len) \
        if live_blocks else None

    def body(carry, w):
        tok, win, counts, key = carry
        key, sub = jax.random.split(key)
        logits, cols, c = decode_step(params, tok, pos0, w, cfg, cache,
                                      win, max_len, full_plan)
        with scope("kv_write"):
            win = {name: jax.lax.dynamic_update_slice_in_dim(
                a, cols[name][:, :, :, None].astype(a.dtype), w, axis=3)
                for name, a in win.items()}
        nxt = sample_fn(logits, sub)
        return (nxt, win, counts + c, key), \
            (nxt, logits if with_logits else None)

    (_, win, counts, _), (toks, logits) = jax.lax.scan(
        body, (tokens, win, jnp.zeros((N_COUNTS,), jnp.int32), key),
        jnp.arange(steps))
    cache = merge(cache, win, pos0, steps, max_len)
    if with_logits:
        return toks, cache, counts, logits
    return toks, cache, counts
