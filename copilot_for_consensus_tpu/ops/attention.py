"""Attention front-end: dispatches to Pallas flash or XLA reference.

Shapes (GQA throughout — Mistral/Llama/Mixtral all use it):
    q: [B, Hq, S, D]    k, v: [B, Hkv, S, D]    Hq % Hkv == 0

The reference never runs attention itself (it delegates to Ollama /
llama.cpp — ``local_llm_summarizer.py:106``); this op is the core of the
first-party engine that replaces them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from copilot_for_consensus_tpu.obs.profile import scope


def _gqa_expand(k: jax.Array, hq: int) -> jax.Array:
    """[B, Hkv, S, D] → [B, Hq, S, D] by repeating each kv head."""
    b, hkv, s, d = k.shape
    if hkv == hq:
        return k
    return jnp.repeat(k, hq // hkv, axis=1)


def make_attention_mask(
    s_q: int,
    s_kv: int,
    *,
    causal: bool,
    window: int = 0,
    q_offset: int = 0,
    kv_lengths: jax.Array | None = None,
) -> jax.Array:
    """Boolean mask [.., s_q, s_kv]; True = attend.

    ``q_offset`` positions the query block inside the kv timeline (used by
    chunked prefill). ``window`` > 0 applies Mistral-style sliding-window
    attention. ``kv_lengths`` [B] masks padded kv positions.
    """
    q_pos = jnp.arange(s_q)[:, None] + q_offset
    k_pos = jnp.arange(s_kv)[None, :]
    mask = jnp.ones((s_q, s_kv), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    if kv_lengths is not None:
        pad = k_pos[None] < kv_lengths[:, None, None]     # [B, 1, s_kv]
        return mask[None] & pad
    return mask


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_lengths: jax.Array | None = None,
) -> jax.Array:
    """Reference scaled-dot-product attention in pure XLA (fp32 softmax)."""
    b, hq, s_q, d = q.shape
    s_kv = k.shape[2]
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    scale = d ** -0.5
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = make_attention_mask(
        s_q, s_kv, causal=causal, window=window, q_offset=q_offset,
        kv_lengths=kv_lengths,
    )
    if mask.ndim == 3:           # [B, s_q, s_kv] → broadcast over heads
        mask = mask[:, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


@scope("attn")
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    kv_lengths: jax.Array | None = None,
    q_offset: int = 0,
    impl="auto",
) -> jax.Array:
    """Full-sequence attention (prefill / encoder). Dispatches to the Pallas
    flash kernel on TPU, XLA reference elsewhere. ``impl`` may also be a
    callable with this same (q, k, v, causal, window, kv_lengths)
    contract — e.g. ``parallel.ring.make_ring_attention(mesh)`` for
    sequence-parallel long-context forwards. ``q_offset`` (chunked
    prefill: query block placed at an offset in the kv timeline) currently
    forces the XLA path."""
    if callable(impl):
        if q_offset:
            raise NotImplementedError(
                "q_offset with a custom attention impl")
        return impl(q, k, v, causal=causal, window=window,
                    kv_lengths=kv_lengths)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if q_offset:
        impl = "xla"
    if impl == "pallas":
        from copilot_for_consensus_tpu.ops.flash_attention import (
            flash_attention,
        )
        return flash_attention(
            q, k, v, causal=causal, window=window, kv_lengths=kv_lengths
        )
    return attention_xla(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        kv_lengths=kv_lengths,
    )


@scope("attn")
def prefill_attention_seeded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_pref: jax.Array,
    v_pref: jax.Array,
    prefix_lens: jax.Array,
    kv_lengths: jax.Array | None = None,
) -> jax.Array:
    """Suffix-prefill attention over (seeded prefix KV ++ fresh suffix KV).

    Two engine paths run on this op:

    * The prefix-cache admission path (``GenerationEngine``) prefills
      only the un-cached tail of a prompt; its queries sit at absolute
      positions ``prefix_lens[b] + i`` and must attend both the reused
      prefix KV (gathered from the device block pool, already
      RoPE-rotated at its original absolute positions — prefixes always
      start at position 0, so reuse needs no re-rotation) and the fresh
      suffix KV causally.
    * The speculative-decoding verify dispatch (``decoder
      .verify_seeded``) scores k+1 draft positions per decode slot with
      the slot's own cache as the seeded prefix. The strict
      ``j < prefix_lens[b]`` prefix mask below is what that path's
      invalidation discipline rests on: cache columns at or past a
      slot's committed length — e.g. KV from a previous dispatch's
      REJECTED draft tokens — are structurally unreadable and simply
      get overwritten by the next write at those positions.

    One joint softmax over the concatenated pieces keeps the math
    elementwise-identical to a monolithic prefill over the full prompt:
    identical logits in identical order, with padding masked to -inf
    exactly as the full pass masks its bucket padding.

    q/k/v: [B, Hq|Hkv, S, D] fresh suffix projections; k_pref/v_pref:
    [B, Hkv, P, D] (any dtype — cast to q's); prefix_lens: [B] valid
    prefix per row (rows with 0 are plain misses); kv_lengths: [B]
    valid SUFFIX length per row (masks bucket padding).

    XLA only (einsum + mask): the admission wave is MXU-bound and the
    engine's q_offset prefill path already routes off the flash kernel;
    a seeded flash variant is future work.
    """
    b, hq, s, d = q.shape
    p = k_pref.shape[2]
    k_all = jnp.concatenate(
        [_gqa_expand(k_pref.astype(q.dtype), hq), _gqa_expand(k, hq)],
        axis=2)
    v_all = jnp.concatenate(
        [_gqa_expand(v_pref.astype(q.dtype), hq), _gqa_expand(v, hq)],
        axis=2)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_all,
        preferred_element_type=jnp.float32) * (d ** -0.5)
    # prefix piece: kv position j valid iff j < prefix_lens[b] (causality
    # is implied: every suffix query sits at position >= prefix_lens[b])
    jpos = jnp.arange(p)[None, None, :]                       # [1,1,P]
    mask_pref = jnp.broadcast_to(
        jpos < prefix_lens[:, None, None], (b, s, p))
    # suffix piece: plain causal within the suffix block (+ pad mask)
    iq = jnp.arange(s)[:, None]
    jk = jnp.arange(s)[None, :]
    mask_suf = jnp.broadcast_to((jk <= iq)[None], (b, s, s))
    if kv_lengths is not None:
        mask_suf = mask_suf & (jk[None] < kv_lengths[:, None, None])
    mask = jnp.concatenate([mask_pref, mask_suf], axis=-1)[:, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v_all.dtype), v_all)


def _grouped_scores(qg: jax.Array, k: jax.Array) -> jax.Array:
    """Unscaled GQA scores [B, Hkv, G, S] of grouped queries against
    one KV piece [B, Hkv, S, D] (fp32 accumulation, the shared
    numerics of every decode-attention entry point)."""
    d = qg.shape[-1]
    return jnp.einsum("bhgd,bhsd->bhgs", qg, k,
                      preferred_element_type=jnp.float32) * (d ** -0.5)


def _piece_mask(pos_abs: jax.Array, valid_below: jax.Array,
                q_pos: jax.Array, window: int) -> jax.Array:
    """The one masking rule every decode KV piece obeys: a column at
    absolute position ``pos_abs`` is attendable iff it is strictly
    below the piece's valid bound and — under a sliding window — within
    ``window`` positions of the query's own absolute position
    ``q_pos``. ``decode_attention`` is the single-piece instance
    (bound = lengths, q_pos = lengths - 1);
    ``decode_attention_prefix_window`` applies it per piece against
    the dispatch timeline."""
    mask = pos_abs < valid_below
    if window > 0:
        mask &= pos_abs > q_pos - window
    return mask


def _joint_probs(pieces_logits: list[jax.Array]) -> list[jax.Array]:
    """One softmax over the concatenated (already masked) score pieces,
    split back per piece — numerically identical to attention over one
    contiguous cache holding all pieces back to back. Fully-masked rows
    (parked slots) produce NaN probabilities and are zeroed."""
    logits = jnp.concatenate(pieces_logits, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    if len(pieces_logits) == 1:
        return [probs]
    splits = np.cumsum([p.shape[-1] for p in pieces_logits])[:-1]
    return jnp.split(probs, splits, axis=-1)


@scope("attn")
def combine_partials(parts: list[tuple[jax.Array, jax.Array, jax.Array]],
                     dtype) -> jax.Array:
    """Fold flash-style (acc, m, l) partials from independent KV pieces
    into the jointly-softmaxed attention output — the reassociation
    that lets the paged Pallas kernel score the pool piece in place
    while the dispatch-local pieces stay in XLA, with no concatenated
    score tensor and no gathered KV copy.

    Each part: acc [..., R, D] = Σ exp(s - m)·v over its piece, m
    [..., R, 1] running max (-inf when fully masked), l [..., R, 1]
    = Σ exp(s - m). Rows masked in EVERY piece emit exact zeros — the
    same value ``_joint_probs``'s NaN guard produces."""
    m_tot = functools.reduce(jnp.maximum, [m for _, m, _ in parts])
    m_safe = jnp.where(jnp.isfinite(m_tot), m_tot, 0.0)
    l_tot = acc_tot = 0.0
    for acc, m, l in parts:
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_tot = l_tot + l * scale
        acc_tot = acc_tot + acc * scale
    out = acc_tot / jnp.where(l_tot > 0, l_tot, 1.0)
    return jnp.where(l_tot > 0, out, 0.0).astype(dtype)


def _masked_partial(logits: jax.Array, v_pieces: list[jax.Array]
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(acc, m, l) of already-masked score rows [..., R, T] against
    their stacked values [..., T, D] — the XLA side of a
    ``combine_partials`` fold (f32 throughout)."""
    v_all = jnp.concatenate([v.astype(jnp.float32) for v in v_pieces],
                            axis=-2) if len(v_pieces) > 1 \
        else v_pieces[0].astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe)                     # exp(-inf)=0 pads
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("...rt,...td->...rd", p, v_all,
                     preferred_element_type=jnp.float32)
    return acc, m, l


@scope("attn")
def decode_window_partial(
    qg: jax.Array,
    k_win: jax.Array,
    v_win: jax.Array,
    k_cur: jax.Array,
    v_cur: jax.Array,
    prefix_lengths: jax.Array,
    w: jax.Array,
    window: int = 0,
    k_done: jax.Array | None = None,
    v_done: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial over the DISPATCH-LOCAL pieces of
    ``decode_attention_prefix_window`` — completed windows, current
    window, self — everything except the big pool/prefix piece, which
    the paged kernel scores in place. Masks are the reference path's
    ``_piece_mask`` against the identical dispatch timeline, so
    combining this partial with the kernel's pool partial reproduces
    the reference's joint softmax.

    qg: [B, Hkv, G, D] grouped queries; k_win/v_win: [B, Hkv, W, D];
    k_cur/v_cur: [B, Hkv, D]. Returns f32 (acc [B, Hkv, G, D],
    m/l [B, Hkv, G, 1])."""
    dt = qg.dtype
    n_win = k_win.shape[2]
    n_done = 0 if k_done is None else k_done.shape[2]
    d = qg.shape[-1]

    lw = _grouped_scores(qg, k_win.astype(dt))
    lc = jnp.einsum("bhgd,bhd->bhg", qg, k_cur.astype(dt),
                    preferred_element_type=jnp.float32)[..., None] \
        * (d ** -0.5)
    cur_pos = (prefix_lengths + n_done + w)[:, None, None, None]
    iw = jnp.arange(n_win)[None, None, None, :]
    pos_w = prefix_lengths[:, None, None, None] + n_done + iw
    mask_w = _piece_mask(pos_w, cur_pos, cur_pos, window)
    lw = jnp.where(mask_w, lw, -jnp.inf)
    pieces_l, pieces_v = [], []
    if n_done:
        ld = _grouped_scores(qg, k_done.astype(dt))
        idn = jnp.arange(n_done)[None, None, None, :]
        pos_dn = prefix_lengths[:, None, None, None] + idn
        mask_dn = _piece_mask(pos_dn, cur_pos, cur_pos, window)
        pieces_l.append(jnp.where(mask_dn, ld, -jnp.inf))
        pieces_v.append(v_done.astype(dt))
    pieces_l += [lw, lc]
    pieces_v += [v_win.astype(dt), v_cur.astype(dt)[:, :, None, :]]
    return _masked_partial(jnp.concatenate(pieces_l, axis=-1), pieces_v)


@scope("attn")
def causal_suffix_partial(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lengths: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial over the fresh causal-suffix piece of
    ``prefill_attention_seeded`` (``jk <= iq`` and below the row's
    valid suffix length), with the (g, s) query rows flattened
    row-major into R = G·S — the row layout the paged kernel's seeded
    pass scores the pool/prefix piece in, so the two partials zip
    straight into ``combine_partials``.

    q: [B, Hq, S, D]; k/v: [B, Hkv, S, D]. Returns f32
    (acc [B, Hkv, G·S, D], m/l [B, Hkv, G·S, 1])."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, s, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    iq = jnp.arange(s)[:, None]
    jk = jnp.arange(s)[None, :]
    mask = jnp.broadcast_to((jk <= iq)[None, None, None],
                            (b, hkv, g, s, s))
    if kv_lengths is not None:
        mask = mask & (jk[None, None, None, None]
                       < kv_lengths[:, None, None, None, None])
    logits = jnp.where(mask, logits, -jnp.inf)
    return _masked_partial(logits.reshape(b, hkv, g * s, s), [v])


@functools.partial(jax.jit, static_argnames=("window", "kv_len"))
@scope("attn")
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    window: int = 0,
    kv_len: int | None = None,
) -> jax.Array:
    """Single-token decode attention over a slot KV cache.

    q: [B, Hq, D]; caches: [B, Hkv, S_max, D]; lengths: [B] — number of
    valid cache positions per slot (the new token's kv already written).
    ``kv_len`` (static) restricts the read to cache prefix [0, kv_len) —
    decode is HBM-bound, so attending over only the occupied prefix
    instead of all of S_max is a direct bandwidth saving; the engine
    buckets it so only a handful of shapes compile.

    This is the SINGLE-piece instance of the shared decode-attention
    core (``_grouped_scores`` / ``_piece_mask`` / ``_joint_probs``)
    that ``decode_attention_prefix_window`` composes over four pieces —
    and the reference semantics the paged kernel
    (``ops/paged_attention.py``) must match bit-for-bit on its XLA
    path.
    """
    if kv_len is not None and kv_len < k_cache.shape[2]:
        k_cache = k_cache[:, :, :kv_len]
        v_cache = v_cache[:, :, :kv_len]
    if k_cache.dtype != q.dtype:
        # float8 caches: 8-bit floats have no implicit promotion; the
        # astype fuses into the einsum loads, so HBM traffic stays f8.
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
    b, hq, d = q.shape
    hkv, s_max = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d)
    logits = _grouped_scores(qg, k_cache)
    pos = jnp.arange(s_max)[None, None, None, :]
    mask = _piece_mask(pos, lengths[:, None, None, None],
                       lengths[:, None, None, None] - 1, window)
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = _joint_probs([logits])[0]
    out = jnp.einsum("bhgs,bhsd->bhgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, hq, d)


@scope("attn")
def decode_attention_prefix_window(
    q: jax.Array,
    k_pref: jax.Array,
    v_pref: jax.Array,
    k_win: jax.Array,
    v_win: jax.Array,
    k_cur: jax.Array,
    v_cur: jax.Array,
    prefix_lengths: jax.Array,
    w: jax.Array,
    window: int = 0,
    kv_len: int | None = None,
    k_done: jax.Array | None = None,
    v_done: jax.Array | None = None,
) -> jax.Array:
    """Decode attention over up to four KV pieces with one joint softmax.

    The pieces: the big prefix cache (read-only — keeping it OUT of the
    decode scan carry is the whole point: a carried cache is
    re-materialized every step, ~2× the cache bytes per token), the
    completed windows of the CURRENT dispatch (``k_done`` [B, Hkv, Wd,
    D], all columns valid — kept out of the cache so a multi-window
    dispatch touches the big cache only once, which is what keeps HBM
    at ONE cache allocation; merging per-window ping-ponged a second
    full cache copy and OOM'd at kv extents > 256), the current
    window's fresh KV (``k_win`` [B, Hkv, W, D], valid columns [0, w)),
    and the current token's own KV. Scores are concatenated (tiny),
    softmaxed jointly — numerically identical to attention over one
    contiguous cache.

    q: [B, Hq, D]; k_pref/v_pref: [B, Hkv, S_max, D]; k_cur/v_cur:
    [B, Hkv, D]. prefix_lengths: [B] — valid prefix per slot (the
    position where THIS DISPATCH started). ``w``: traced scan counter —
    window columns at index ≥ w are garbage and masked; done columns
    precede the current window. ``window``: sliding-window size
    (0 = full).
    """
    if kv_len is not None and kv_len < k_pref.shape[2]:
        k_pref = k_pref[:, :, :kv_len]
        v_pref = v_pref[:, :, :kv_len]
    dt = q.dtype
    k_pref, v_pref = k_pref.astype(dt), v_pref.astype(dt)
    k_win, v_win = k_win.astype(dt), v_win.astype(dt)
    k_cur, v_cur = k_cur.astype(dt), v_cur.astype(dt)
    b, hq, d = q.shape
    hkv = k_pref.shape[1]
    s_max = k_pref.shape[2]
    n_win = k_win.shape[2]
    n_done = 0 if k_done is None else k_done.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d)

    lp = _grouped_scores(qg, k_pref)
    lw = _grouped_scores(qg, k_win)
    lc = jnp.einsum("bhgd,bhd->bhg", qg, k_cur,
                    preferred_element_type=jnp.float32)[..., None] \
        * (d ** -0.5)

    # The dispatch's own columns start at prefix_lengths: done columns
    # at +[0, n_done), current-window column i at +n_done+i; the token
    # itself sits at +n_done+w. Every piece runs the same masking rule
    # (_piece_mask) against that timeline.
    cur_pos = (prefix_lengths + n_done + w)[:, None, None, None]  # [B]
    pos_p = jnp.arange(s_max)[None, None, None, :]
    mask_p = _piece_mask(pos_p, prefix_lengths[:, None, None, None],
                         cur_pos, window)
    iw = jnp.arange(n_win)[None, None, None, :]
    pos_w = prefix_lengths[:, None, None, None] + n_done + iw
    # valid bound for the window piece: strictly earlier steps, i.e.
    # columns below the current absolute position
    mask_w = _piece_mask(pos_w, cur_pos, cur_pos, window)
    lp = jnp.where(mask_p, lp, -jnp.inf)
    lw = jnp.where(mask_w, lw, -jnp.inf)
    pieces_l = [lp]
    pieces_v = [v_pref]
    if n_done:
        k_done = k_done.astype(dt)
        ld = _grouped_scores(qg, k_done)
        idn = jnp.arange(n_done)[None, None, None, :]
        pos_dn = prefix_lengths[:, None, None, None] + idn
        # done columns are all committed (always below cur_pos); only
        # the window bound can mask them
        mask_dn = _piece_mask(pos_dn, cur_pos, cur_pos, window)
        ld = jnp.where(mask_dn, ld, -jnp.inf)
        pieces_l.append(ld)
        pieces_v.append(v_done.astype(dt))
    pieces_l += [lw, lc]

    parts = _joint_probs(pieces_l)
    out = jnp.einsum("bhgs,bhsd->bhgd", parts[0].astype(dt), v_pref)
    if n_done:
        out += jnp.einsum("bhgw,bhwd->bhgd", parts[1].astype(dt),
                          pieces_v[1])
    out += jnp.einsum("bhgw,bhwd->bhgd", parts[-2].astype(dt), v_win)
    out += parts[-1].astype(dt) * v_cur[:, :, None, :]
    return out.reshape(b, hq, d)
