"""EVA-attention decoder (EvaByte class): exact attention inside an
aligned window, chunk summaries of everything before it, one softmax.

The block is the dense decoder's (pre-norm, RoPE, SwiGLU; projections,
``qmatmul``, ``apply_rope`` and ``swiglu`` are ``models/layers.py``'s)
with three differences the published config states: RMS gains stored
as offsets from one, residual adds and the output head in float32, and
an output matrix of ``num_pred_heads`` x ``vocab_size`` columns. The
attention (Zheng et al., arXiv:2302.04542) with W = ``window_size`` and
C = ``chunk_size``:

* a query at position t attends exactly to the positions of its own
  window, ``{j : j // W == t // W, j <= t}``;
* every earlier window is seen through one summary key and one summary
  value per chunk of C positions, pooled from the ROTATED keys with two
  learned vectors per head:
  ``k̄_c = Σ_m softmax_m(mu·k_m) k_m``, ``v̄_c = Σ_m softmax_m(phi·k_m) v_m``;
* exact scores and summary scores share ONE softmax
  (``ops.attention._joint_probs``, the join the decode path already
  uses for its cache pieces).

So a sequence's state is two things side by side, and the cache this
module owns holds both for every slot:

``k``/``v``   ``[L, slots, H, W + margin, Dh]`` the open window, exact:
              column j is position ``(t // W) * W + j``; the ``margin``
              columns past W take the slab of a slot that is not
              decoding, which has to land somewhere
``ks``/``vs`` ``[L, slots, H, max_len / C, Dh]`` the summaries, RIGHT
              aligned: with n live summaries the columns
              ``[R - n, R)`` hold them, a closing window's W / C go in
              just below. Order does not matter under a softmax, and a
              valid run that ends where the window begins is what lets
              one flash call see both (``prefill_piece``). A slot never
              holds more than ``R - W / C``: the lowest W / C columns
              are where a dispatch's slab lands for a slot that closed
              no window.

A window that fills is COMPACTED: its W / C summaries are appended and
the window restarts empty (``pool_chunks``, scope ``kv_compact``) — in
an admission piece that ends on a window edge, and inside a decode
dispatch, at the step where a slot's window fills, for exactly those
slots, on the device (``decode_tokens``).

Decode attention reads of this state what is live. On a TPU, block by
block and in place: a kernel over the four stacked halves
(``ops/eva_attention.py``) walks each slot's live window blocks and
then its live summary blocks, and its flash partial is folded with the
dispatch's own columns, a closing window's fresh summaries and the
token's own key under the same one softmax
(``ops.attention.combine_partials``; ``live_attention``). Elsewhere,
whole pieces and a mask (``joint_attention``): the CPU's route, and
what the tests hold the kernel to.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.models import layers as L
from copilot_for_consensus_tpu.models.configs import DecoderConfig
from copilot_for_consensus_tpu.models.decoder import put_window_column
from copilot_for_consensus_tpu.models.quant import quant_kind
from copilot_for_consensus_tpu.obs.profile import scope
from copilot_for_consensus_tpu.ops.attention import (
    _grouped_scores,
    _joint_probs,
    _masked_partial,
    combine_partials,
)
from copilot_for_consensus_tpu.ops.eva_attention import (
    plan_blocks,
    state_partial,
)

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: DecoderConfig,
                dtype=jnp.bfloat16) -> Params:
    """Random weights in the decoder's checkpoint layout plus ``mu`` and
    ``phi`` ``[L, H, Dh]``; norm gains are offsets around zero and the
    output matrix has ``num_pred_heads * vocab_size`` columns."""
    n, d, dh, h = cfg.n_layers, cfg.d_model, cfg.head_dim, cfg.n_heads
    f, v = cfg.d_ff, cfg.vocab_size * cfg.num_pred_heads
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(keys), -2, 2, shape,
                                            jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(shape):
        return (0.1 * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    layer = {
        "attn_norm": gain((n, d)), "ffn_norm": gain((n, d)),
        "wq": dense((n, d, h * dh), d), "wk": dense((n, d, h * dh), d),
        "wv": dense((n, d, h * dh), d), "wo": dense((n, h * dh, d), h * dh),
        "w_gate": dense((n, d, f), d), "w_up": dense((n, d, f), d),
        "w_down": dense((n, f, d), f),
        "mu": dense((n, h, dh), dh), "phi": dense((n, h, dh), dh),
    }
    return {"tok_emb": dense((cfg.vocab_size, d), d), "layers": layer,
            "final_norm": gain((d,)), "lm_head": dense((d, v), d)}


def init_cache(cfg: DecoderConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, margin: int = 8) -> Params:
    w, c = cfg.window_size, cfg.chunk_size
    if w % c or max_len % w:
        raise ValueError(
            f"eva cache: window_size {w} must be a multiple of chunk_size "
            f"{c} and max_len {max_len} of window_size")
    win = (cfg.n_layers, batch, cfg.n_heads, w + margin, cfg.head_dim)
    summ = (cfg.n_layers, batch, cfg.n_heads, max_len // c, cfg.head_dim)
    return {"k": jnp.zeros(win, dtype), "v": jnp.zeros(win, dtype),
            "ks": jnp.zeros(summ, dtype), "vs": jnp.zeros(summ, dtype)}


def live_state(cfg: DecoderConfig, position: int) -> tuple[int, int]:
    """(exact columns, summaries) a sequence holds before it takes the
    token at ``position``."""
    w = cfg.window_size
    return position % w, position // w * (w // cfg.chunk_size)


# ---------------------------------------------------------------------------
# Pieces of the block
# ---------------------------------------------------------------------------


def _norm(x, gain, cfg, dtype):
    return L.rms_norm(x, gain, cfg.norm_eps,
                      unit_offset=cfg.norm_unit_offset, dtype=dtype)


def _embed(params: Params, tokens: jax.Array) -> jax.Array:
    with scope("embed"):
        return params["tok_emb"][tokens]


def _block_tail(x, o, layer, cfg, dt):
    """Residual stream in float32: ``x + Wo·o``, then the SwiGLU."""
    x = x + L.attn_out(o, layer).astype(jnp.float32)
    return x + L.swiglu(_norm(x, layer["ffn_norm"], cfg, dt),
                        layer).astype(jnp.float32)


@scope("unembed")
def unembed(x: jax.Array, params: Params, cfg: DecoderConfig) -> jax.Array:
    """Float32 logits of every prediction head, ``[..., heads * V]``:
    columns ``[V i, V (i + 1))`` predict the token i + 1 ahead."""
    xn = _norm(x, params["final_norm"], cfg, jnp.float32)
    w = params["lm_head"]
    hi = jax.lax.Precision.HIGHEST
    if quant_kind(w) == "int8":
        return jnp.matmul(xn, w["q"].astype(jnp.float32), precision=hi) \
            * w["scale"].astype(jnp.float32)
    return jnp.matmul(xn, w.astype(jnp.float32), precision=hi)


@scope("kv_compact")
def pool_chunks(k: jax.Array, v: jax.Array, mu: jax.Array, phi: jax.Array,
                chunk: int) -> tuple[jax.Array, jax.Array]:
    """Chunk summaries of rotated keys ``k`` and values ``v``
    ``[..., H, T, Dh]`` (T a multiple of ``chunk``) under one layer's
    ``mu``/``phi`` ``[H, Dh]`` → float32 ``[..., H, T / chunk, Dh]``.
    Both sums are weighted by the KEYS: ``mu·k_m`` the keys',
    ``phi·k_m`` the values'."""
    *lead, t, dh = k.shape
    kf = k.astype(jnp.float32).reshape(*lead, t // chunk, chunk, dh)
    vf = v.astype(jnp.float32).reshape(*lead, t // chunk, chunk, dh)
    pk = jax.nn.softmax(jnp.einsum("...hncd,hd->...hnc", kf,
                                   mu.astype(jnp.float32)), axis=-1)
    pv = jax.nn.softmax(jnp.einsum("...hncd,hd->...hnc", kf,
                                   phi.astype(jnp.float32)), axis=-1)
    return (jnp.einsum("...hnc,...hncd->...hnd", pk, kf),
            jnp.einsum("...hnc,...hncd->...hnd", pv, vf))


# ---------------------------------------------------------------------------
# Admission: one piece of a prompt per row
# ---------------------------------------------------------------------------


@scope("attn")
def piece_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                    q_off: jax.Array, kv_begin: jax.Array,
                    kv_len: jax.Array, impl: str = "auto") -> jax.Array:
    """Attention of a piece's queries ``[n, H, S, Dh]`` over a row's
    timeline ``[n, H, T, Dh]`` (summaries, then the window): query i of
    row r stands at column ``q_off[r] + i`` and sees the columns from
    ``kv_begin[r]`` to its own, below ``kv_len[r]``. On the chip this
    is the flash kernel's query offset and begin bound; elsewhere a
    masked softmax."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        from copilot_for_consensus_tpu.ops.flash_attention import (
            flash_attention,
        )
        return flash_attention(q, k_all, v_all, causal=True,
                               kv_lengths=kv_len, q_offsets=q_off,
                               kv_begins=kv_begin)
    s, t, d = q.shape[2], k_all.shape[2], q.shape[3]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_all,
                        preferred_element_type=jnp.float32) * d ** -0.5
    q_pos = q_off[:, None, None] + jnp.arange(s)[None, :, None]
    col = jnp.arange(t)[None, None, :]
    mask = ((col <= q_pos) & (col >= kv_begin[:, None, None])
            & (col < kv_len[:, None, None]))
    logits = jnp.where(mask[:, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v_all.dtype), v_all)


def prefill_piece(params: Params, tokens: jax.Array, lens: jax.Array,
                  pos0: jax.Array, slots: jax.Array, cfg: DecoderConfig,
                  cache: Params, attn_impl: str = "auto"
                  ) -> tuple[jax.Array, Params]:
    """One piece of a prompt for each of n rows, into the rows' slots.

    tokens ``[n, S]`` right-padded, ``lens[r]`` real; row r's piece
    starts at absolute position ``pos0[r]`` and does not cross a window
    edge, and ``pos0[r] % W + S <= W`` (the engine cuts pieces so).
    The piece's keys and values go into the slot's window at their
    columns, its queries attend (the slot's summaries, the window up to
    themselves) in one call, and a piece that ends on the window's edge
    compacts the window: W / C summaries appended, nothing exact kept.
    The cache rides the layer scan's carry and is touched a row at a
    time (a window is 1/slots of a layer of it). Rows may repeat (the
    engine pads a wave with copies of its first row: the same writes
    twice). Returns (all heads' logits after each row's last token
    ``[n, heads * V]`` float32, cache)."""
    w_sz, c = cfg.window_size, cfg.chunk_size
    wc = w_sz // c
    n, s = tokens.shape
    h, dh = cfg.n_heads, cfg.head_dim
    r_sz = cache["ks"].shape[3]
    dt = params["tok_emb"].dtype
    off = pos0 % w_sz
    nsum = pos0 // w_sz * wc
    closes = (off + lens) == w_sz
    positions = pos0[:, None] + jnp.arange(s)[None, :]
    s_start = jnp.clip(r_sz - nsum - wc, 0, r_sz - wc)
    x = _embed(params, tokens).astype(jnp.float32)

    def row_of(buf, li, slot, start, size):
        return jax.lax.dynamic_slice(
            buf, (li, slot, 0, start, 0), (1, 1, h, size, dh))[0, 0]

    def body(carry, scanned):
        x, kw, vw, ks, vs = carry
        layer, li = scanned
        q, k, v = L._project_qkv(_norm(x, layer["attn_norm"], cfg, dt),
                                 layer, cfg, positions)
        with scope("kv_write"):
            for r in range(n):
                at = (li, slots[r], 0, off[r], 0)
                kw = jax.lax.dynamic_update_slice(
                    kw, k[r][None, None].astype(kw.dtype), at)
                vw = jax.lax.dynamic_update_slice(
                    vw, v[r][None, None].astype(vw.dtype), at)
        with scope("kv_prefix"):
            k_win = [row_of(kw, li, slots[r], 0, w_sz) for r in range(n)]
            v_win = [row_of(vw, li, slots[r], 0, w_sz) for r in range(n)]
            k_all = jnp.stack([jnp.concatenate(
                [row_of(ks, li, slots[r], 0, r_sz), k_win[r]], axis=1)
                for r in range(n)]).astype(dt)
            v_all = jnp.stack([jnp.concatenate(
                [row_of(vs, li, slots[r], 0, r_sz), v_win[r]], axis=1)
                for r in range(n)]).astype(dt)
        o = piece_attention(q, k_all, v_all, r_sz + off, r_sz - nsum,
                            r_sz + off + lens, attn_impl)
        o = o.transpose(0, 2, 1, 3).reshape(n, s, h * dh)
        x = _block_tail(x, o, layer, cfg, dt)
        with scope("kv_compact"):
            for r in range(n):
                k_new, v_new = pool_chunks(k_win[r], v_win[r], layer["mu"],
                                           layer["phi"], c)
                at = (li, slots[r], 0, s_start[r], 0)
                k_new = jnp.where(closes[r], k_new.astype(ks.dtype),
                                  row_of(ks, li, slots[r], s_start[r], wc))
                v_new = jnp.where(closes[r], v_new.astype(vs.dtype),
                                  row_of(vs, li, slots[r], s_start[r], wc))
                ks = jax.lax.dynamic_update_slice(ks, k_new[None, None], at)
                vs = jax.lax.dynamic_update_slice(vs, v_new[None, None], at)
        return (x, kw, vw, ks, vs), None

    (x, kw, vw, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"], cache["ks"], cache["vs"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    x_last = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
    return (unembed(x_last, params, cfg)[:, 0],
            {"k": kw, "v": vw, "ks": ks, "vs": vs})


# ---------------------------------------------------------------------------
# Decode: a dispatch of ``steps`` tokens for every slot
# ---------------------------------------------------------------------------


def _masked_scores(q: jax.Array, k_cur: jax.Array, pieces: list) -> list:
    """Scaled scores ``[B, H, 1, T]`` of one token's queries against
    each piece ``(k [B, H, T, Dh], v, mask [B, T])``, ``-inf`` where
    masked, and last against the token's own key."""
    dt = q.dtype
    qg = q[:, :, None, :]
    logits = [jnp.where(m[:, None, None, :],
                        _grouped_scores(qg, k.astype(dt)), -jnp.inf)
              for k, _v, m in pieces]
    logits.append(_grouped_scores(qg, k_cur.astype(dt)[:, :, None, :]))
    return logits


@scope("attn")
def joint_attention(q: jax.Array, k_cur: jax.Array, v_cur: jax.Array,
                    pieces: list) -> jax.Array:
    """One token's attention ``[B, H, Dh]`` over its own key and value
    and any number of pieces ``(k [B, H, T, Dh], v, mask [B, T])``:
    scores of every piece under one softmax."""
    dt = q.dtype
    probs = _joint_probs(_masked_scores(q, k_cur, pieces))
    out = probs[-1].astype(dt) * v_cur.astype(dt)[:, :, None, :]
    for p, (_k, v, _m) in zip(probs, pieces):
        out += jnp.einsum("bhgt,bhtd->bhgd", p.astype(dt), v.astype(dt))
    return out[:, :, 0, :]


def _reads_live_blocks() -> bool:
    """Does decode attention go through the kernel that walks a slot's
    live blocks (``ops/eva_attention.py``)? On a TPU; elsewhere the
    XLA ``joint_attention`` over whole pieces serves (and is what the
    tests hold the kernel to)."""
    return jax.default_backend() == "tpu"


@scope("attn")
def live_attention(q: jax.Array, k_cur: jax.Array, v_cur: jax.Array,
                   pieces: list, cache: Params, li: jax.Array,
                   plan: tuple, window: int) -> jax.Array:
    """``joint_attention`` of one token ``[B, H, Dh]`` over its own key
    and value, the dispatch-local ``pieces`` and the live window
    columns and summaries of layer ``li`` of the stacked ``cache``, of
    which only the blocks that ``plan`` lists are read, in place
    (``ops.eva_attention``: ``plan_blocks`` for windows of ``window``
    columns, ``state_partial``). The local pieces stay in XLA as one
    masked partial; the fold puts every score under the one
    normaliser."""
    state = state_partial(q, cache, li, plan, window=window)
    local = _masked_partial(
        jnp.concatenate(_masked_scores(q, k_cur, pieces), axis=-1),
        [v for _k, v, _m in pieces] + [v_cur[:, :, None, :]])
    return combine_partials([state, local], q.dtype)[:, :, 0, :]


def decode_step(params: Params, tok: jax.Array, pos0: jax.Array,
                w: jax.Array, cfg: DecoderConfig, cache: Params,
                k_buf: jax.Array, v_buf: jax.Array,
                k_new: jax.Array | None, v_new: jax.Array | None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Step ``w`` (traced) of a dispatch that began at positions
    ``pos0``: one token per slot against a read-only cache.

    Of the cache a slot's live state is read: its window's columns
    below its fill and its summaries, none for a slot that is not
    decoding (position ``max_len``). On a TPU block by block, in place
    (``live_attention``: the layer scan closes over the cache, and a
    block with no live column is neither fetched nor scored);
    elsewhere whole and masked (``joint_attention``; a cut in XLA is a
    strided copy of what it keeps). ``k_buf``/``v_buf``
    ``[L, B, H, steps, Dh]``: the dispatch's own columns, valid below
    ``w``. ``k_new``/``v_new`` ``[L, B, H, W / C, Dh]`` or None: the
    summaries of a window that filled earlier in THIS dispatch. A slot
    whose window has filled ("crossed") no longer sees the old window
    nor the dispatch columns that belonged to it, and sees its
    summaries instead. Returns (logits ``[B, heads * V]`` float32, this
    step's key and value columns ``[L, B, H, Dh]``)."""
    w_sz, c = cfg.window_size, cfg.chunk_size
    b = tok.shape[0]
    h, dh = cfg.n_heads, cfg.head_dim
    dt = params["tok_emb"].dtype
    t_win, t_sum = cache["k"].shape[3], cache["ks"].shape[3]
    fill0 = pos0 % w_sz
    crossed = fill0 + w >= w_sz
    live = pos0 < t_sum * c
    win_len = jnp.where(crossed, 0, fill0)
    sum_n = jnp.where(live, pos0 // w_sz * (w_sz // c), 0)
    steps = k_buf.shape[3]
    i_buf = jnp.arange(steps)[None, :]
    m_buf = (i_buf >= jnp.where(crossed, w_sz - fill0, 0)[:, None]) \
        & (i_buf < w)
    pos = (pos0 + w)[:, None]
    x = _embed(params, tok)[:, None, :].astype(jnp.float32)
    in_place = _reads_live_blocks()
    if in_place:
        with scope("attn"):
            plan = plan_blocks(win_len, sum_n, window=w_sz, store=t_sum)
        halves = ()
    else:
        m_win = jnp.arange(t_win)[None, :] < win_len[:, None]
        m_sum = jnp.arange(t_sum)[None, :] >= t_sum - sum_n[:, None]
        halves = (cache["k"], cache["v"], cache["ks"], cache["vs"])

    def body(x, scanned):
        layer, li, *halves_l = scanned
        q, k, v = L._project_qkv(_norm(x, layer["attn_norm"], cfg, dt),
                                 layer, cfg, pos)
        take = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
            a, li, 0, keepdims=False)
        local = [(take(k_buf), take(v_buf), m_buf)]
        if k_new is not None:
            kn_l = take(k_new)
            local.append((kn_l, take(v_new), jnp.broadcast_to(
                crossed[:, None], (b, kn_l.shape[2]))))
        k_cur, v_cur = k[:, :, 0, :], v[:, :, 0, :]
        if in_place:
            o = live_attention(q[:, :, 0, :], k_cur, v_cur, local, cache,
                               li, plan, w_sz)
        else:
            kw_l, vw_l, ks_l, vs_l = halves_l
            o = joint_attention(
                q[:, :, 0, :], k_cur, v_cur,
                [(kw_l, vw_l, m_win), local[0], (ks_l, vs_l, m_sum),
                 *local[1:]])
        x = _block_tail(x, o.reshape(b, 1, h * dh), layer, cfg, dt)
        return x, (k_cur, v_cur)

    x, (k_cols, v_cols) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.n_layers), *halves))
    return unembed(x, params, cfg)[:, 0], k_cols, v_cols


@scope("kv_compact")
def compact_closing(cache: Params, k_buf: jax.Array, v_buf: jax.Array,
                    fill0: jax.Array, closing: jax.Array, k_new: jax.Array,
                    v_new: jax.Array, layers: Params, cfg: DecoderConfig
                    ) -> tuple[jax.Array, jax.Array]:
    """The summaries of the window that just filled, for the slots in
    ``closing`` ``[B]``; other slots keep ``k_new``/``v_new``.

    A closing slot's window is its cache columns below ``fill0`` and,
    after them, the dispatch's own columns. Those reach back at most
    ``steps`` columns from the window's end, so all chunks but the
    last ``ceil(steps / C)`` pool straight from the cache; the tail is
    overlaid first. One layer at a time (the float32 working copy of a
    layer's windows is the only large temporary), and only in a step
    in which a window fills: about one step in ``W / slots``."""
    w_sz, c = cfg.window_size, cfg.chunk_size
    steps = k_buf.shape[3]
    tail = min(-(-steps // c) * c, w_sz)
    col = w_sz - tail + jnp.arange(tail)[None, :]            # [1, tail]
    from_buf = (col >= fill0[:, None])[:, None, :, None]     # [B,1,tail,1]
    idx = jnp.clip(col - fill0[:, None], 0, steps - 1)[:, None, :, None]
    sel = closing[:, None, None, None]

    def overlay(win_l, buf_l):
        fresh = jnp.take_along_axis(buf_l, idx, axis=2)
        return jnp.concatenate(
            [win_l[:, :, :w_sz - tail],
             jnp.where(from_buf, fresh.astype(win_l.dtype),
                       win_l[:, :, w_sz - tail:w_sz])], axis=2)

    def one_layer(_, scanned):
        li, mu, phi, kb_l, vb_l, kn_l, vn_l = scanned
        take = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
            a, li, 0, keepdims=False)
        ks_l, vs_l = pool_chunks(overlay(take(cache["k"]), kb_l),
                                 overlay(take(cache["v"]), vb_l),
                                 mu, phi, c)
        return None, (jnp.where(sel, ks_l.astype(kn_l.dtype), kn_l),
                      jnp.where(sel, vs_l.astype(vn_l.dtype), vn_l))

    _, (k_new, v_new) = jax.lax.scan(
        one_layer, None,
        (jnp.arange(cfg.n_layers), layers["mu"], layers["phi"], k_buf,
         v_buf, k_new, v_new))
    return k_new, v_new


@scope("kv_write")
def merge_dispatch(cache: Params, k_buf: jax.Array, v_buf: jax.Array,
                   k_new: jax.Array | None, v_new: jax.Array | None,
                   pos0: jax.Array, max_len: int,
                   cfg: DecoderConfig) -> Params:
    """A dispatch's state into the cache, once, in place.

    Each slot's ``steps`` fresh columns go in as one slab, by the
    scatter ``decoder.merge_window`` uses (batched over the cache's own
    slot axis; the compiler expands it to in-place updates). A slot
    whose window did not fill: at its fill. One whose window filled:
    the old window is dead, the columns after the edge open the new
    one at column 0 (what follows them in the slab lies past the new
    fill, unread until overwritten). A slot that is not decoding
    (position ``max_len``): into the margin. With ``k_new`` (a program
    that may close windows) every slot also writes a W / C slab of
    summaries just below its live ones: the closed window's, or
    nothing anyone reads."""
    w_sz, wc = cfg.window_size, cfg.window_size // cfg.chunk_size
    steps = k_buf.shape[3]
    r_sz = cache["ks"].shape[3]
    fill0 = pos0 % w_sz
    live = pos0 < max_len
    closed = live & (fill0 + steps >= w_sz)
    start = jnp.where(live, jnp.where(closed, 0, fill0), w_sz)
    shift = jnp.where(closed, w_sz - fill0, 0)
    roll = jax.vmap(lambda buf_b, n: jnp.roll(buf_b, -n, axis=2),
                    in_axes=(1, 0), out_axes=1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3, 4), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(3,), operand_batching_dims=(1,),
        scatter_indices_batching_dims=(0,))

    def put(half, slab, at):
        return jax.lax.scatter(
            half, at[:, None],
            slab.astype(half.dtype).transpose(1, 0, 2, 3, 4), dnums,
            unique_indices=True,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    out = dict(cache)
    out["k"] = put(cache["k"], roll(k_buf, shift), start)
    out["v"] = put(cache["v"], roll(v_buf, shift), start)
    if k_new is not None:
        at = jnp.clip(r_sz - pos0 // w_sz * wc - wc, 0, r_sz - wc)
        out["ks"] = put(cache["ks"], k_new, at)
        out["vs"] = put(cache["vs"], v_new, at)
    return out


def decode_tokens(params: Params, tokens: jax.Array, pos0: jax.Array,
                  cfg: DecoderConfig, cache: Params, key: jax.Array,
                  sample_fn, *, steps: int, may_close: bool, max_len: int,
                  with_logits: bool = False):
    """``steps`` tokens for every slot in one program: decode → sample
    → feed back, the cache read-only until one merge at the end (the
    discipline of the engine's ``_decode``: a cache in the token loop's
    carry is copied every token).

    ``may_close`` (static): the caller passes True whenever some
    slot's window can fill within the dispatch, and only such a
    program holds the compaction: at the step where a slot's window
    fills, its W / C summaries are pooled into a buffer in the loop's
    carry (``compact_closing``, under a ``cond`` that is false in all
    but about one step in W / slots), the slot's later steps read them
    as a piece, and the merge appends them.
    ``sample_fn(logits [B, V], key)`` picks from prediction head 0.
    Returns (tokens ``[steps, B]``, cache) and, ``with_logits``, every
    step's logits of all heads ``[steps, B, heads * V]``."""
    w_sz, wc = cfg.window_size, cfg.window_size // cfg.chunk_size
    n_l, b = cfg.n_layers, tokens.shape[0]
    dt = cache["k"].dtype
    fill0 = pos0 % w_sz
    live = pos0 < max_len
    buf = jnp.zeros((n_l, b, cfg.n_heads, steps, cfg.head_dim), dt)
    new = jnp.zeros((n_l, b, cfg.n_heads, wc, cfg.head_dim), dt) \
        if may_close else None

    def body(carry, w):
        tok, k_buf, v_buf, k_new, v_new, key = carry
        key, sub = jax.random.split(key)
        logits, k_cols, v_cols = decode_step(
            params, tok, pos0, w, cfg, cache, k_buf, v_buf, k_new, v_new)
        k_buf = put_window_column(k_buf, k_cols, w)
        v_buf = put_window_column(v_buf, v_cols, w)
        if may_close:
            closing = live & (fill0 + w + 1 == w_sz)
            k_new, v_new = jax.lax.cond(
                jnp.any(closing),
                lambda: compact_closing(cache, k_buf, v_buf, fill0,
                                        closing, k_new, v_new,
                                        params["layers"], cfg),
                lambda: (k_new, v_new))
        nxt = sample_fn(logits[:, :cfg.vocab_size], sub)
        return (nxt, k_buf, v_buf, k_new, v_new, key), \
            (nxt, logits if with_logits else None)

    (_, k_buf, v_buf, k_new, v_new, _), (toks, logits) = jax.lax.scan(
        body, (tokens, buf, buf, new, new, key), jnp.arange(steps))
    cache = merge_dispatch(cache, k_buf, v_buf, k_new, v_new, pos0,
                           max_len, cfg)
    return (toks, cache, logits) if with_logits else (toks, cache)
