"""Decoder-only LLM (Mistral / Llama-3 / Mixtral class).

Pre-norm transformer with RoPE, GQA, SwiGLU (or MoE) FFN, RMSNorm.
Layers are stacked on a leading axis and driven by ``lax.scan``:
compile time is O(1) in depth and every weight is one pjit-shardable
tensor. Three entry points:

* ``forward``      — [B, S] → logits [B, S, V] (scoring / training)
* ``prefill``      — builds the KV cache, returns last-position logits
* ``decode_step``  — one token per active slot against the cache

This model fills the generative-engine role the reference delegates to
Ollama / llama.cpp (``adapters/copilot_summarization/.../factory.py:89-94``,
``local_llm_summarizer.py:106-115``).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.models.configs import DecoderConfig
from copilot_for_consensus_tpu.models import layers as L
from copilot_for_consensus_tpu.models.moe import moe_ffn
from copilot_for_consensus_tpu.obs.profile import scope
from copilot_for_consensus_tpu.ops import dense_attention

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Init + sharding metadata
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: DecoderConfig,
                dtype=jnp.bfloat16) -> Params:
    """Truncated-normal init, scaled 1/sqrt(fan_in) for projections."""
    n, d, dh = cfg.n_layers, cfg.d_model, cfg.head_dim
    hq, hkv, f, v = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size
    keys = iter(jax.random.split(rng, 16))

    def dense(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    layer: Params = {
        "attn_norm": jnp.ones((n, d), dtype),
        "wq": dense(next(keys), (n, d, hq * dh), d),
        "wk": dense(next(keys), (n, d, hkv * dh), d),
        "wv": dense(next(keys), (n, d, hkv * dh), d),
        "wo": dense(next(keys), (n, hq * dh, d), hq * dh),
        "ffn_norm": jnp.ones((n, d), dtype),
    }
    if cfg.is_moe:
        e = cfg.n_experts
        layer.update({
            "router": dense(next(keys), (n, d, e), d),
            "w_gate": dense(next(keys), (n, e, d, f), d),
            "w_up": dense(next(keys), (n, e, d, f), d),
            "w_down": dense(next(keys), (n, e, f, d), f),
        })
    else:
        layer.update({
            "w_gate": dense(next(keys), (n, d, f), d),
            "w_up": dense(next(keys), (n, d, f), d),
            "w_down": dense(next(keys), (n, f, d), f),
        })
    params: Params = {
        "tok_emb": dense(next(keys), (v, d), d),
        "layers": layer,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(keys), (d, v), d)
    return params


def logical_axes(cfg: DecoderConfig) -> Params:
    """Same structure as params; leaves are logical-axis tuples."""
    layer = {
        "attn_norm": (None, "norm"),
        "wq": (None, "embed", "heads"),
        "wk": (None, "embed", "kv_heads"),
        "wv": (None, "embed", "kv_heads"),
        "wo": (None, "heads", "embed"),
        "ffn_norm": (None, "norm"),
    }
    if cfg.is_moe:
        layer.update({
            "router": (None, "embed", None),
            "w_gate": (None, "experts", "embed", "expert_ffn"),
            "w_up": (None, "experts", "embed", "expert_ffn"),
            "w_down": (None, "experts", "expert_ffn", "embed"),
        })
    else:
        layer.update({
            "w_gate": (None, "embed", "ffn"),
            "w_up": (None, "embed", "ffn"),
            "w_down": (None, "ffn", "embed"),
        })
    axes: Params = {
        "tok_emb": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("norm",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


@scope("embed")
def _embed(params: Params, tokens: jax.Array) -> jax.Array:
    return params["tok_emb"][tokens]


def _ffn(x: jax.Array, layer: Params, cfg: DecoderConfig) -> jax.Array:
    if cfg.is_moe:
        with scope("ffn"):
            return moe_ffn(x, layer, cfg)
    return L.swiglu(x, layer)


@scope("unembed")
def _unembed(x: jax.Array, params: Params, cfg: DecoderConfig) -> jax.Array:
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return (x @ params["tok_emb"].T).astype(jnp.float32)
    return L.qmatmul(x, params["lm_head"]).astype(jnp.float32)


def block(x: jax.Array, layer: Params, cfg: DecoderConfig,
          lengths: jax.Array | None = None,
          attn_impl: str = "auto", reduce=None) -> jax.Array:
    """One transformer block: [B, S, D] → [B, S, D]. The single source of
    the block body — forward and the pp pipeline both run this, so model
    changes cannot drift between them. ``reduce`` (default identity)
    completes partial products when the layer's head/ffn width is
    tensor-parallel sharded — the pp×tp path passes a psum."""
    if reduce is None:
        reduce = lambda t: t  # noqa: E731
    h, _, _ = L.attn_prefill(
        L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
        layer, cfg, lengths=lengths, impl=attn_impl)
    x = x + reduce(h)
    return x + reduce(_ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                           layer, cfg))


def forward(params: Params, tokens: jax.Array, cfg: DecoderConfig,
            lengths: jax.Array | None = None,
            attn_impl: str = "auto") -> jax.Array:
    """Scoring/training pass: [B, S] int tokens → [B, S, V] fp32 logits."""
    x = _embed(params, tokens)

    def body(x, layer):
        return block(x, layer, cfg, lengths, attn_impl), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _unembed(x, params, cfg)


def init_cache(cfg: DecoderConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes() -> Params:
    return {"k": (None, "batch", "kv_heads", None, None),
            "v": (None, "batch", "kv_heads", None, None)}


def cache_prefix(cache: Params, kv_len: int | None) -> Params:
    """The cache cut to its first ``kv_len`` (static) columns: what a
    decode-side program attends to. Cut short of the full extent, each
    half is a strided COPY of ``kv_len`` columns of every slot —
    cache-sized work, so a program takes it once per dispatch, outside
    any loop over tokens (the compiler does not hoist it)."""
    if kv_len is None or kv_len >= cache["k"].shape[3]:
        return cache
    with scope("kv_prefix"):
        return {"k": cache["k"][:, :, :, :kv_len],
                "v": cache["v"][:, :, :, :kv_len]}


def prefill(params: Params, tokens: jax.Array, lengths: jax.Array,
            cfg: DecoderConfig, cache: Params,
            attn_impl: str = "auto") -> tuple[jax.Array, Params]:
    """Prompt pass. tokens: [B, S] right-padded; lengths: [B]. Writes kv for
    positions [0, S) into the cache and returns (last-valid-position logits
    [B, V] fp32, cache)."""
    b, s = tokens.shape
    x = _embed(params, tokens)

    def body(x, scanned):
        layer, k_cache, v_cache = scanned
        h, k, v = L.attn_prefill(
            L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
            layer, cfg, lengths=lengths, impl=attn_impl)
        x = x + h
        x = x + _ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                     layer, cfg)
        with scope("kv_write"):
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k.astype(k_cache.dtype), 0, axis=2)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v.astype(v_cache.dtype), 0, axis=2)
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    # Select each row's last valid hidden state BEFORE the lm_head:
    # unembedding all S positions materializes [B, S, V] fp32 logits
    # (1 GB at 128×128×32k — the admission-path OOM driver) and burns
    # S× the lm_head FLOPs for rows where only the last token samples.
    x_last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return _unembed(x_last, params, cfg)[:, 0], {"k": k_new, "v": v_new}


def prefill_seeded(params: Params, tokens: jax.Array, lengths: jax.Array,
                   k_pref: jax.Array, v_pref: jax.Array,
                   prefix_lens: jax.Array, cfg: DecoderConfig,
                   cache: Params) -> tuple[jax.Array, Params]:
    """Suffix prompt pass against seeded prefix KV (prefix cache hits).

    tokens: [B, S] right-padded SUFFIX tokens — row b's token i sits at
    absolute position ``prefix_lens[b] + i``; lengths: [B] suffix
    lengths (>= 1: the first generated token samples from the last
    suffix position). k_pref/v_pref: [L, B, Hkv, P, Dh] reused prefix
    KV gathered from the block pool (zero-padded past prefix_lens —
    masked in attention). Writes SUFFIX kv into scratch positions
    [0, S) (the engine scatters them into the slot cache at the
    per-row offset) and returns (last-valid-position logits [B, V]
    fp32, scratch). Rows with prefix_lens 0 compute exactly what
    ``prefill`` computes — mixed hit/miss admission waves run as one
    program."""
    x = _embed(params, tokens)

    def body(x, scanned):
        layer, k_pref_l, v_pref_l, k_cache, v_cache = scanned
        h, k, v = L.attn_prefill_seeded(
            L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
            layer, cfg, k_pref_l, v_pref_l, prefix_lens,
            lengths=lengths)
        x = x + h
        x = x + _ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                     layer, cfg)
        with scope("kv_write"):
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k.astype(k_cache.dtype), 0, axis=2)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v.astype(v_cache.dtype), 0, axis=2)
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], k_pref, v_pref,
                  cache["k"], cache["v"]))
    x_last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return _unembed(x_last, params, cfg)[:, 0], {"k": k_new, "v": v_new}


def verify_seeded(params: Params, tokens: jax.Array, lengths: jax.Array,
                  prefix_lens: jax.Array, cfg: DecoderConfig,
                  cache: Params, kv_len: int | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-token verification pass for speculative decoding.

    A short seeded prefill over DECODE SLOTS: row b's S = k+1 tokens
    (the committed next token plus its k drafted continuations) sit at
    absolute positions ``prefix_lens[b] + i`` and attend (slot cache
    prefix ++ fresh causal suffix) through the same
    ``attn_prefill_seeded`` machinery the prefix-cache admission wave
    uses — one weight pass scores all k+1 positions of every slot,
    which is the entire point (decode is pinned at the HBM weight-read
    wall; see docs/SPEC_DECODE.md).

    Differences from :func:`prefill_seeded`: the seeded prefix is the
    engine's own slot cache ``[L, B, Hkv, S_max, Dh]`` read in place
    (sliced to the static ``kv_len`` bucket, streamed per layer as
    read-only scan xs — never in the carry, the same discipline as
    ``decode_step_windowed``), and logits come back for EVERY position
    (acceptance needs all k+1 distributions, not just the last).
    Positions at or past ``prefix_lens[b]`` are masked, so KV left over
    from a previous dispatch's rejected drafts is dead by construction.

    tokens: [B, S] right-padded; lengths: [B] valid tokens per row
    (>= 1); prefix_lens: [B] committed cache prefix per slot (free
    slots park out of range and produce garbage that the engine's
    scatter drops). Returns (logits [B, S, V] fp32, k_new, v_new
    [L, B, Hkv, S, Dh] — ``merge_window`` layout, for the engine's
    single end-of-dispatch scatter at the per-row offset)."""
    x = _embed(params, tokens)
    pref = cache_prefix(cache, kv_len)

    def body(x, scanned):
        layer, k_pref_l, v_pref_l = scanned
        h, k, v = L.attn_prefill_seeded(
            L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
            layer, cfg, k_pref_l, v_pref_l, prefix_lens,
            lengths=lengths)
        x = x + h
        x = x + _ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                     layer, cfg)
        return x, (k, v)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], pref["k"], pref["v"]))
    return _unembed(x, params, cfg), k_new, v_new


def decode_step(params: Params, tokens: jax.Array, positions: jax.Array,
                cfg: DecoderConfig, cache: Params,
                kv_len: int | None = None
                ) -> tuple[jax.Array, Params]:
    """One decode step. tokens: [B] int — the tokens to feed; positions:
    [B] — the cache index each token occupies; ``kv_len`` (static) bounds
    the cache prefix attention reads. Returns ([B, V] fp32 logits,
    updated cache)."""
    x = _embed(params, tokens)[:, None, :]               # [B, 1, D]

    # The stacked cache rides the scan CARRY with per-column scatter
    # writes (attn_decode_stacked): as scan xs/ys it would be fully
    # re-materialized (read + write) every token step — more HBM traffic
    # than the weights at serving shapes.
    def body(carry, scanned):
        x, k_cache, v_cache = carry
        layer, li = scanned
        h, k_cache, v_cache = L.attn_decode_stacked(
            L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
            layer, cfg, positions, k_cache, v_cache, li, kv_len=kv_len)
        x = x + h
        x = x + _ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                     layer, cfg)
        return (x, k_cache, v_cache), None

    (x, k_new, v_new), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    return _unembed(x, params, cfg)[:, 0], {"k": k_new, "v": v_new}


def decode_step_windowed(params: Params, tokens: jax.Array,
                         positions0: jax.Array, w: jax.Array,
                         cfg: DecoderConfig, cache: Params,
                         k_win: jax.Array, v_win: jax.Array,
                         kv_len: int | None = None,
                         k_done: jax.Array | None = None,
                         v_done: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step that never writes the big cache.

    The stacked cache in a decode-window scan carry is re-materialized
    (read + copied) once per token step — measured at ~2× the cache
    bytes, which dominated the step once weights went int8. Here the
    cache is a read-only loop invariant; fresh KV goes into the small
    per-window buffers ``k_win``/``v_win`` [L, B, Hkv, W, Dh] carried by
    the engine's window scan, and is merged into the cache ONCE per
    DISPATCH. A multi-window dispatch passes the completed windows as
    ``k_done``/``v_done`` [L, B, Hkv, Wd, Dh] (a fourth attention
    piece) instead of merging them — merging per window made the big
    cache a loop variable again and ping-ponged a second full cache
    allocation (the r2 OOM at kv extents > 256).

    tokens: [B]; positions0: [B] dispatch-start positions; ``w``: traced
    in-window step index. ``cache``: the halves attention reads, every
    column of them. The engine calls this from the body of its token
    scan with the prefix it cut before the scan (``cache_prefix``) and
    no ``kv_len``; given a ``kv_len`` the cut is made here, on every
    call — for a caller that holds a full cache and runs no loop over
    tokens around this. Returns ([B, V] fp32 logits, k_cols, v_cols)
    where k_cols/v_cols [L, B, Hkv, Dh] are this step's new KV columns
    for the caller to slot into the window buffers at index ``w``.
    """
    x = _embed(params, tokens)[:, None, :]               # [B, 1, D]
    # The prefix is streamed per layer as scan xs (read-only, never in
    # ys): attention reads exactly the occupied [0, kv_len) columns per
    # layer and nothing writes back. A dynamic per-layer index into the
    # full-extent cache instead materializes max_len-proportional layer
    # copies (measured: going max_len 256→512 with identical kv_len
    # cost ~12 ms/step).
    pref = cache_prefix(cache, kv_len)
    have_done = k_done is not None
    xs = (params["layers"], jnp.arange(cfg.n_layers), pref["k"], pref["v"])
    if have_done:
        xs = xs + (k_done, v_done)

    def body(x, scanned):
        layer, li, k_pref_l, v_pref_l = scanned[:4]
        k_done_l = scanned[4] if have_done else None
        v_done_l = scanned[5] if have_done else None
        # Window buffers are [L, B, H, W, D] (attention-native layout;
        # merge_window transposes once per window, not per layer/step).
        k_win_l = jax.lax.dynamic_index_in_dim(k_win, li, 0,
                                               keepdims=False)
        v_win_l = jax.lax.dynamic_index_in_dim(v_win, li, 0,
                                               keepdims=False)
        h, k_cur, v_cur = L.attn_decode_windowed(
            L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
            layer, cfg, positions0, w, k_pref_l, v_pref_l,
            k_win_l, v_win_l, kv_len=None,
            k_done_l=k_done_l, v_done_l=v_done_l)
        x = x + h
        x = x + _ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                     layer, cfg)
        return x, (k_cur, v_cur)

    x, (k_cols, v_cols) = jax.lax.scan(body, x, xs)
    return _unembed(x, params, cfg)[:, 0], k_cols, v_cols


def decode_step_windowed_paged(params: Params, tokens: jax.Array,
                               positions0: jax.Array, w: jax.Array,
                               cfg: DecoderConfig, partial_fn,
                               k_win: jax.Array, v_win: jax.Array,
                               k_done: jax.Array | None = None,
                               v_done: jax.Array | None = None
                               ) -> tuple[jax.Array, jax.Array,
                                          jax.Array]:
    """Kernel-route twin of :func:`decode_step_windowed`: the big
    cache piece never appears as an array at all. ``partial_fn(li,
    qg, lengths, q_pos)`` scores the slot's committed pool blocks in
    place (the Pallas paged kernel, layer selected by the traced
    ``li`` on the scalar-prefetch lane — no per-layer pool slice
    materializes either), and the fresh KV discipline is identical:
    window buffers in the engine's scan carry, completed windows as a
    ``k_done`` piece, one pool scatter per dispatch by the caller.

    tokens: [B]; positions0: [B] dispatch-start positions; ``w``:
    traced in-window step index. Returns ([B, V] fp32 logits, k_cols,
    v_cols [L, B, Hkv, Dh]) exactly like the reference twin."""
    x = _embed(params, tokens)[:, None, :]               # [B, 1, D]
    have_done = k_done is not None
    xs = (params["layers"], jnp.arange(cfg.n_layers))
    if have_done:
        xs = xs + (k_done, v_done)

    def body(x, scanned):
        layer, li = scanned[:2]
        k_done_l = scanned[2] if have_done else None
        v_done_l = scanned[3] if have_done else None
        k_win_l = jax.lax.dynamic_index_in_dim(k_win, li, 0,
                                               keepdims=False)
        v_win_l = jax.lax.dynamic_index_in_dim(v_win, li, 0,
                                               keepdims=False)
        h, k_cur, v_cur = L.attn_decode_windowed_paged(
            L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
            layer, cfg, positions0, w,
            functools.partial(partial_fn, li), k_win_l, v_win_l,
            k_done_l=k_done_l, v_done_l=v_done_l)
        x = x + h
        x = x + _ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                     layer, cfg)
        return x, (k_cur, v_cur)

    x, (k_cols, v_cols) = jax.lax.scan(body, x, xs)
    return _unembed(x, params, cfg)[:, 0], k_cols, v_cols


def decode_step_windowed_live(params: Params, tokens: jax.Array,
                              positions0: jax.Array, w: jax.Array,
                              cfg: DecoderConfig, cache: Params,
                              k_win: jax.Array, v_win: jax.Array,
                              k_done: jax.Array | None = None,
                              v_done: jax.Array | None = None
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`decode_step_windowed` without a cut of the cache: the
    route of a TPU that holds the cache on one device. ``cache`` is the
    stacked cache WHOLE; of it each slot's live blocks are read, by the
    slot's own length, in place (``ops/dense_attention.py``: the layer
    scan closes over the two halves, a block with no live column is
    neither fetched nor scored, a free or parked slot reads nothing) —
    where the XLA route reads every slot to the longest slot's bucket
    out of a strided copy. The plan of blocks is made here, once a
    token; the kernel's partial is the prefix piece of
    :func:`decode_step_windowed_paged`, whose layer loop, dispatch-local
    partial and fold serve unchanged. Same arguments and results as
    :func:`decode_step_windowed`."""
    extent = cache["k"].shape[3]
    n_done = 0 if k_done is None else k_done.shape[3]
    plan = dense_attention.plan_blocks(
        *dense_attention.live_range(positions0, positions0 + n_done + w,
                                    cfg.sliding_window, extent),
        extent=extent)

    def partial_fn(li, qg, lengths, q_pos):
        return dense_attention.live_partial(qg, cache["k"], cache["v"],
                                            li, plan)

    return decode_step_windowed_paged(
        params, tokens, positions0, w, cfg, partial_fn, k_win, v_win,
        k_done=k_done, v_done=v_done)


def prefill_seeded_paged(params: Params, tokens: jax.Array,
                         lengths: jax.Array, prefix_lens: jax.Array,
                         cfg: DecoderConfig, partial_fn, *,
                         all_logits: bool
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel-route seeded suffix pass: one program standing in for
    :func:`prefill_seeded` (``all_logits=False`` — admission) and
    :func:`verify_seeded` (``all_logits=True`` — spec-decode verify
    and chunked prefill), with the seeded prefix scored straight off
    the paged block pool by ``partial_fn(li, q_rows, lengths,
    q_pos)`` instead of a gathered ``k_pref`` view. Masking semantics
    are the reference twins' exactly: prefix columns at or past
    ``prefix_lens[b]`` are structurally unreadable, suffix attention
    is causal below ``lengths[b]``.

    tokens: [B, S] right-padded suffix tokens at absolute positions
    ``prefix_lens[b] + i``. Returns (logits, k_new, v_new
    [L, B, Hkv, S, Dh] in compute dtype — ``merge_window`` layout for
    the engine's single pool scatter): logits are [B, S, V] fp32 when
    ``all_logits`` else the last-valid-position [B, V] (selected
    BEFORE the lm_head — the same admission OOM guard as
    ``prefill``)."""
    x = _embed(params, tokens)

    def body(x, scanned):
        layer, li = scanned
        h, k, v = L.attn_prefill_seeded_paged(
            L.rms_norm(x, layer["attn_norm"], cfg.norm_eps),
            layer, cfg, functools.partial(partial_fn, li),
            prefix_lens, lengths=lengths)
        x = x + h
        x = x + _ffn(L.rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                     layer, cfg)
        return x, (k, v)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.n_layers)))
    if all_logits:
        return _unembed(x, params, cfg), k_new, v_new
    x_last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return _unembed(x_last, params, cfg)[:, 0], k_new, v_new


@scope("kv_write")
def put_window_column(win: jax.Array, cols: jax.Array,
                      w: jax.Array) -> jax.Array:
    """One decode step's new KV columns ``cols`` [L, B, Hkv, Dh] into
    column ``w`` of the window buffer ``win`` [L, B, Hkv, W, Dh]."""
    return jax.lax.dynamic_update_slice_in_dim(
        win, cols[:, :, :, None].astype(win.dtype), w, axis=3)


@scope("kv_write")
def merge_window(cache: Params, k_win: jax.Array, v_win: jax.Array,
                 positions0: jax.Array, steps: int) -> Params:
    """Write a dispatch's fresh KV into the big cache, once, in place.

    k_win/v_win: [L, B, Hkv, W, Dh]; slot b's first ``steps`` window
    columns land at cache positions ``positions0[b] + [0, steps)``, and
    a column whose position is at or past the cache extent is DROPPED
    (free and chunk-prefilling slots park there; a live slot near the end
    loses only what does not fit).

    Each slot's columns go in as ONE slab [L, Hkv, W, Dh] at
    ``(b, start[b])``: a scatter whose only index is the slab's first
    column and whose batch axis is the cache's own, which XLA:TPU
    expands to a loop of in-place ``dynamic-update-slice`` on the
    donated buffer and GSPMD partitions over a sharded slot axis with
    no collective. (A scatter with one index per COLUMN,
    ``.at[:, bidx, :, pidx, :]``, wants its operand with the sequence
    axis ahead of the heads: XLA copied each half into that layout and
    back, four cache-sized copies a dispatch to write W columns a
    slot.) A slab cannot drop single columns, so one that would run
    past the extent is laid over the last W columns instead, its
    window columns shifted to their positions and the columns before
    them filled with what the cache holds there. Layout assignment is
    touchy here — the same slabs written by a vmapped
    ``dynamic_update_slice``, or their old columns read by a gather,
    bring cache-sized copies back — so tests/test_decode_structure.py
    compiles the decode program for the chip and holds it to none.
    """
    s_max = cache["k"].shape[3]
    # a window column at index >= s_max can land nowhere (positions >= 0)
    w = min(k_win.shape[3], steps, s_max)
    start = jnp.clip(positions0, 0, s_max - w)     # [B] first slab column
    shift = positions0 - start       # > 0 only where start is s_max - w
    fresh = (jnp.arange(w)[None, :] >= shift[:, None])[None, :, None, :, None]
    roll = jax.vmap(lambda win_b, n: jnp.roll(win_b, n, axis=2),
                    in_axes=(1, 0), out_axes=1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3, 4), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(3,), operand_batching_dims=(1,),
        scatter_indices_batching_dims=(0,))

    def merge(half, win):
        new = roll(win[:, :, :, :w], shift).astype(half.dtype)
        slab = jnp.where(fresh, new, half[:, :, :, s_max - w:])
        return jax.lax.scatter(
            half, start[:, None], slab.transpose(1, 0, 2, 3, 4), dnums,
            unique_indices=True,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    return {"k": merge(cache["k"], k_win), "v": merge(cache["v"], v_win)}
