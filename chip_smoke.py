#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, on one TPU chip, through the entry points an
operator calls, at full Mistral-7B width and depth with random int8
weights made from a seed:

* **kernels** — every Pallas kernel an ``auto`` route selects on a TPU is
  compiled (``interpret=False``) at Mistral-7B widths and compared with
  its XLA reference on the chip: ``flash_attention`` (plain, windowed,
  ``kv_lengths``; then command-a-plus's, Mistral's and EvaByte's
  admission calls at their served shapes, timed), the paged kernel
  against ``paged_gather_layer`` +
  ``decode_attention`` over an fp8 pool (decode rows ``r = group`` and
  seeded rows ``r = group * S``), the EVA decode kernel over a slot's
  live blocks (at EvaByte's widths) against ``joint_attention`` over
  whole pieces, the dense and the latent decode kernels over live
  blocks (at the served cells' shapes, timed at each block width
  tried), the latent admission kernel against ``piece_attention``'s
  XLA rounds (a wave of 2 x 2,048 queries at both MLA cells' shapes,
  GLM's under a selection's mask: ms a round on either route), the
  admission threshold's kernel against ``sparse_select.threshold``'s
  XLA rounds and ``jax.lax.top_k`` (the sort keys of such a wave: ms a
  call on either route at three live extents), the experts' grouped int8 matmul against ``ragged_dot`` (a decode step's
  pairs and both MLA cells' admission waves: ms a matmul under the
  served tiling beside the deep-k one that served them until PR 42),
  and ``int4_matmul``. Then one short
  paged ``GenerationEngine(kv_kernel="auto")`` run, depth cut to
  ``KERNEL_PHASE_LAYERS``, whose route must resolve to ``"kernel"``.
* **serve** — ``python -m copilot_for_consensus_tpu serve`` with the
  drivers ``deploy/config/pipeline.json`` ships (``embedding: tpu/
  minilm-l6``, ``vector_store: tpu``, ``llm: tpu/mistral-7b``), in-proc
  bus and in-memory stores: ``/readyz``, upload of the committed fixture
  mbox, one report per thread, semantic search (embed + on-device vector
  query), ``/metrics``, then SIGTERM and a clean drain with exit 0. The
  prefill program it served with must contain a Mosaic
  ``tpu_custom_call``.

One process per chip: this parent never imports jax; each phase is a
child that takes the chip, runs, and exits before the next one starts.
Any failed check raises — nothing records a failure and carries on. The
last stdout line is the result, and it is printed only for a full-size
pass on a TPU:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse`` runs the same phases at ``tiny`` size wherever JAX was
told to run (``JAX_PLATFORMS=cpu python chip_smoke.py --rehearse``; the
kernels then run interpreted). A rehearsal exercises the script, never
the chip: it prints no result line and exits 3.

Seconds printed here are set-up facts (compile, build, warm-up), not
metrics.
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import functools
import json
import pathlib
import queue
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse
import urllib.request

REPO = pathlib.Path(__file__).resolve().parent
FIXTURE = REPO / "tests" / "fixtures" / "ietf-sample.mbox"
FIXTURE_THREADS = 3          # threads in the committed fixture mbox
#: whole-run wall budget: the contract is exit 0 within 1200 s, compile
#: included, so every wait below is bounded by what is left of this
BUDGET_S = 1140.0
#: depth of the kernel phase's paged engine (width is never cut)
KERNEL_PHASE_LAYERS = 2
#: max |kernel - reference| / max(1, max |reference|), bf16 operands
#: (the repo's flash oracle tolerance, tests/test_ops_attention.py)
KERNEL_TOL = 2e-2
REHEARSAL_EXIT = 3


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def fact(**fields) -> None:
    """One JSON line of facts on stdout (the result line comes last)."""
    print(json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


# ---------------------------------------------------------------------------
# children: shared bring-up
# ---------------------------------------------------------------------------


def _cache_files(cache_dir: str) -> int:
    root = pathlib.Path(cache_dir)
    return sum(1 for p in root.rglob("*") if p.is_file()) \
        if root.is_dir() else 0


def _bring_up(rehearse: bool) -> dict:
    """Place the compile cache, take the device, and refuse anything
    but a TPU unless this is a rehearsal. Returns the device facts."""
    from importlib import metadata

    import jax

    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
        require_accelerator,
    )

    cache_dir = enable_compile_cache()
    dev = require_accelerator("chip_smoke.py")
    check(rehearse or dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r}, "
          f"{dev.device_kind}); the smoke runs on the chip only")
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "versions": {p: metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache": cache_dir,
        "cache_files_before": _cache_files(cache_dir),
    }


# ---------------------------------------------------------------------------
# child: kernels phase
# ---------------------------------------------------------------------------


def _latent_waves(rehearse: bool):
    """An admission wave of 2 x 2,048 queries at each served latent
    cell's widths (32 heads of 192 / 128; 64 heads of 256 / 256 under a
    mask that keeps 2,048 columns a query), tiny when rehearsing: →
    (cell, config, the numbers of live rounds to time, a function
    ``piece(q, cache_a, chance, pos0, fold)`` that hands a piece
    starting at ``pos0`` to ``fold(q, cache_a, slots, q_pos, kv_len,
    n_blocks, layer, cfg, keep)``, ``piece_attention``'s arguments
    after the layer index, and its arrays ``(q, cache_a, chance)``)."""
    import jax
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.models import xing
    from copilot_for_consensus_tpu.models.configs import decoder_config

    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    blk = xing.KV_BLOCK
    cells = {
        "xing": (decoder_config("tiny-xing"), dict(
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, n_heads=32), 16384, (4, 8, 15), 0),
        "glm": (decoder_config("tiny-glm"), dict(
            kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
            v_head_dim=256, n_heads=64), 32768, (4, 8, 23), 2048)}
    n, s = (2, 64) if rehearse else (2, 2048)
    for cell, (cfg, widths, extent, rounds, topk) in cells.items():
        if rehearse:
            extent, rounds, topk = 4 * blk, (2, 4), 24 * bool(topk)
        else:
            cfg = dataclasses.replace(cfg, **widths)
        h, dk, dv = cfg.n_heads, cfg.qk_nope_head_dim \
            + cfg.qk_rope_head_dim, cfg.v_head_dim
        keys = jax.random.split(jax.random.PRNGKey(38), 4)
        cache_a = jax.random.normal(
            keys[0], (1, 4, xing.latent_width(cfg), extent), dtype)
        layer = {"wkv_b": (jax.random.normal(
            keys[1], (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + dv)),
            jnp.float32) * cfg.kv_lora_rank ** -0.5).astype(dtype)}
        q = (jax.random.normal(keys[2], (n, s, h, dk), jnp.float32)
             * dk ** -0.5).astype(dtype)
        slots = jnp.asarray([2, 0], jnp.int32)
        chance = jax.random.uniform(keys[3], (n, s, extent))

        def piece(q, cache_a, chance, pos0, fold, topk=topk, cfg=cfg,
                  layer=layer, slots=slots, extent=extent):
            q_pos = pos0 + jnp.arange(s)[None, :] + jnp.zeros((n, 1),
                                                              jnp.int32)
            kv_len = jnp.full((n,), pos0 + s, jnp.int32)
            keep = None
            if topk:
                # each query keeps about topk of the columns it sees
                kept = xing._seen(jnp.arange(extent), q_pos, kv_len) \
                    & (chance * (q_pos[..., None] + 1) < topk)
                keep = lambda j: jax.lax.dynamic_slice(  # noqa: E731
                    kept, (0, 0, j * blk), (n, s, blk))
            return fold(q, cache_a, slots, q_pos, kv_len,
                        (pos0 + s + blk - 1) // blk, layer, cfg, keep)

        yield cell, cfg, rounds, piece, (q, cache_a, chance)


def _best_ms(fn, *args, rehearse: bool):
    """(``fn``'s result, the best of five timed calls after the first,
    in milliseconds)."""
    import jax

    out = jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(1 if rehearse else 5):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return out, best * 1e3


def admission_attention_rates(rehearse: bool, compare) -> dict:
    """The admission kernel (``ops/latent_prefill_attention.py``)
    against ``models/xing.py:piece_attention``'s XLA rounds at both
    served cells' shapes (``_latent_waves``): a wave whose piece ends
    4, 8 and 15 / 23 live rounds into its slots' latent caches, the
    expansion of each round included on both routes. → a cell and a
    number of rounds: agreement (``compare``), milliseconds a round on
    either route, and the share of the MXU's peak that the kernel
    route's time stands for, counted over the (query, column) pairs
    attention needs: the seen ones, under a mask or not, since the
    program scores them all."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.models import xing
    from copilot_for_consensus_tpu.ops import latent_prefill_attention

    blk = xing.KV_BLOCK
    rates: dict = {"tiles": [latent_prefill_attention.TQ,
                             latent_prefill_attention.TK]}
    for cell, cfg, rounds, piece, arrays in _latent_waves(rehearse):
        n, s, h, dk = arrays[0].shape
        dv = cfg.v_head_dim

        def route(kernel):
            def fold(q, cache_a, *rest):
                # the route is read when the program is traced
                with mock.patch.object(latent_prefill_attention, "serves",
                                       lambda block: kernel):
                    return xing.piece_attention(q, cache_a, jnp.int32(0),
                                                *rest)
            return jax.jit(functools.partial(piece, fold=fold))

        routes = {"xla": route(False), "kernel": route(True)}
        rates[cell] = {}
        for live in rounds:
            pos0 = jnp.int32(live * blk - s)
            ms = {}
            for name, fn in routes.items():
                out, ms[name] = _best_ms(fn, *arrays, pos0,
                                         rehearse=rehearse)
                if name == "xla":
                    want = out
            compare(f"mla_prefill_attention/{cell}/rounds={live}", out, want)
            pairs = n * (s * (live * blk - s) + s * (s + 1) // 2)
            rates[cell][f"rounds_{live}"] = {
                "xla_ms_per_round": round(ms["xla"] / live, 4),
                "kernel_ms_per_round": round(ms["kernel"] / live, 4),
                "kernel_mxu_share": round(
                    2 * pairs * h * (dk + dv) / (ms["kernel"] * 1e-3)
                    / 197e12, 4)}
    return rates


def latent_expand_rates(rehearse: bool) -> dict:
    """A round of the kernel route's admission attention
    (``xing._piece_attention_kernel``) at both served cells' shapes
    (``_latent_waves``, the deepest piece: 15 / 23 live rounds), split
    in two: the whole round (a round's latents read, expanded on the
    route the widths take, folded by the kernel) and the fold alone
    (the same walk over ONE round's operands, expanded once before
    it). → a cell: which way its widths take
    (``xing._rotary_in_weight``: ``expand_heads``, or ``expand``'s
    turned over), milliseconds a round whole, the fold's, their
    difference (what the expansion costs beside the kernel), and the
    rate at which that time writes and reads the kernel's operands
    (``xing.expand_bytes_moved``'s bytes a round and layer, twice:
    written, then read); and whether ``expand_heads``' keys and values
    of a round are ``xing.expand``'s transposed bit for bit on this
    backend (a fact, not a check: both are float32 sums of the same
    products, in the order the backend's dot takes them)."""
    import jax
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.models import xing
    from copilot_for_consensus_tpu.ops import latent_prefill_attention as lpa

    blk = xing.KV_BLOCK
    rates: dict = {}
    for cell, cfg, rounds, piece, arrays in _latent_waves(rehearse):
        live = rounds[-1]
        n, s, h, _ = arrays[0].shape

        def whole(q, cache_a, *rest):
            return xing._piece_attention_kernel(q, cache_a, jnp.int32(0),
                                                *rest)

        def fold_alone(q, cache_a, slots, q_pos, kv_len, n_blocks, layer,
                       cfg, keep):
            k, v = xing.expand_heads(
                xing._block_rows(cache_a, jnp.int32(0), slots, 0, blk),
                *xing.expansion_weights(layer, cfg), q.dtype)
            plan = lpa.plan_queries(q_pos, kv_len)
            q = q.transpose(0, 2, 1, 3)
            carry = jax.lax.fori_loop(
                0, n_blocks, lambda j, carry: lpa.fold_round(
                    q, k, v, carry, j * blk, plan,
                    None if keep is None else keep(j)),
                lpa.empty_carry(n, h, s, cfg.v_head_dim))
            return lpa.finish(carry, q.dtype)

        def operands(q, cache_a, slots, q_pos, kv_len, n_blocks, layer, cfg,
                     keep):
            latent = xing._block_rows(cache_a, jnp.int32(0), slots, 0, blk)
            got = xing.expand_heads(
                latent, *xing.expansion_weights(layer, cfg), q.dtype)
            want = xing.expand(latent, layer, cfg)
            return jnp.stack([jnp.all(g == w.astype(q.dtype).transpose(
                0, 2, 1, 3)) for g, w in zip(got, want)])

        pos0 = jnp.int32(live * blk - s)
        same = jax.jit(functools.partial(piece, fold=operands))(
            *arrays, pos0)
        ms = {name: _best_ms(jax.jit(functools.partial(piece, fold=fold)),
                             *arrays, pos0, rehearse=rehearse)[1] / live
              for name, fold in (("round", whole), ("fold", fold_alone))}
        moved = xing.expand_bytes_moved(
            [blk] * n, blk, dataclasses.replace(cfg, n_layers=1),
            arrays[0].dtype.itemsize)
        rates[cell] = {
            "rotary_in_weight": xing._rotary_in_weight(cfg),
            "operands_are_expands_bit_for_bit": bool(same.all()),
            "rounds": live,
            "round_ms": round(ms["round"], 4),
            "fold_ms": round(ms["fold"], 4),
            "expand_ms": round(ms["round"] - ms["fold"], 4),
            "operand_mb_a_round": round(moved / 1e6, 1),
            "expand_gb_s": round(2 * moved / max(
                ms["round"] - ms["fold"], 1e-6) / 1e6, 1)}
    return rates


def select_threshold_rates(rehearse: bool) -> dict:
    """The admission threshold (``models/xing.py:piece_threshold``) on
    its two routes at the served shape: the sort keys of a wave of 2 x
    2,048 queries in a buffer of 32,768 columns, the 2,048 best kept.
    (1) Rows of unequal extents, one with scores on a coarse grid (ties
    at the k-th score in most queries): the kernel route's (``thr``,
    ``cut``) are the XLA rounds' integers, and the kept set of a few
    queries a row is ``jax.lax.top_k``'s. (2) TIMED, both rows 4,096 /
    12,288 / 23,552 columns into their slots, every score distinct (the
    ties' branch, which both routes run in XLA, stays out): milliseconds
    a call on either route, and what the kernel's time is a key and
    round; then at 12,288 with the tied scores, where that branch runs
    on both routes."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.models import xing
    from copilot_for_consensus_tpu.ops import (
        latent_prefill_attention,
        select_threshold,
        sparse_select,
    )

    blk = xing.KV_BLOCK
    n, s, extent, topk, lives = (2, 64, 4 * blk, 24, (2, 4)) if rehearse \
        else (2, 2048, 32768, 2048, (4, 12, 23))
    col = jnp.arange(extent, dtype=jnp.int32)

    @jax.jit
    def keys_of(scores, kv_len):
        q_pos = kv_len[:, None] - s + jnp.arange(s)[None, :]
        return sparse_select.sort_keys(
            scores, xing._seen(col, q_pos, kv_len)), q_pos

    def route(kernel):
        def fn(buf, q_pos, kv_len):
            # the route is read when the program is traced
            with mock.patch.object(latent_prefill_attention, "serves",
                                   lambda block: kernel):
                return xing.piece_threshold(
                    buf, q_pos, kv_len, (jnp.max(kv_len) + blk - 1) // blk,
                    topk)
        return jax.jit(fn)

    routes = {"xla": route(False), "kernel": route(True)}
    scores = jax.random.normal(jax.random.PRNGKey(47), (n, s, extent),
                               jnp.float32)
    rates: dict = {"tile": select_threshold.TQ}

    # (1) the same integers, and top_k's set
    tied = scores.at[1].set(jnp.round(scores[1] * 8) / 8)
    kv_len = jnp.asarray([lives[-1] * blk - 7, lives[0] * blk + 5], jnp.int32)
    buf, q_pos = keys_of(tied, kv_len)
    (thr, cut), (thr_x, cut_x) = (
        routes[r](buf, q_pos, kv_len) for r in ("kernel", "xla"))
    check(bool((thr == thr_x).all() & (cut == cut_x).all()),
          "select_threshold: the kernel's (thr, cut) are not the XLA "
          "rounds'")
    check(bool((cut[1] < extent).any()),
          "select_threshold: no query's k-th score is tied")
    for r in range(n):
        for i in (0, s // 2 + 1, s - 1):
            kk = min(topk, int(q_pos[r, i]) + 1)
            # (-0.0 and 0.0 are one score: top_k tells them apart)
            _, top = jax.lax.top_k(
                jnp.where(buf[r, i] > sparse_select.NEVER,
                          jnp.where(tied[r, i] == 0, 0.0, tied[r, i]),
                          -jnp.inf), kk)
            got = np.flatnonzero(np.asarray(sparse_select.chosen(
                buf[r, i], col, thr[r, i], cut[r, i])))
            check(set(got.tolist()) == set(np.asarray(top).tolist()),
                  f"select_threshold/row={r}/query={i}: the threshold "
                  f"keeps {len(got)} columns, not top_k's {kk}")

    # (2) timed: every query's scores distinct (a multiplier's residues
    # modulo a prime over the extent), so the ties' branch stays out;
    # then the tied scores of (1), where it runs on both routes
    def timed(fn, *args) -> float:
        return _best_ms(fn, *args, rehearse=rehearse)[1]

    prime = 32771
    distinct = ((col[None, None, :] * 7919
                 + jnp.arange(s)[None, :, None] * 104729
                 + jnp.arange(n)[:, None, None] * 13) % prime
                ).astype(jnp.float32)
    for live in lives:
        kv_len = jnp.full((n,), live * blk, jnp.int32)
        buf, q_pos = keys_of(distinct, kv_len)
        check(bool((routes["kernel"](buf, q_pos, kv_len)[1] == extent).all()),
              "select_threshold: a k-th score is tied among distinct scores")
        ms = {name: timed(fn, buf, q_pos, kv_len)
              for name, fn in routes.items()}
        rates[f"live_{live * blk}"] = {
            "xla_ms": round(ms["xla"], 4),
            "kernel_ms": round(ms["kernel"], 4),
            "kernel_ps_per_key_round": round(
                ms["kernel"] * 1e9 / (n * s * live * blk * 32), 3)}
    kv_len = jnp.full((n,), lives[1] * blk, jnp.int32)
    buf, q_pos = keys_of(tied, kv_len)
    rates[f"tied_{lives[1] * blk}"] = {
        f"{name}_ms": round(timed(fn, buf, q_pos, kv_len), 4)
        for name, fn in routes.items()}
    return rates


def flash_attention_rates(rehearse: bool, compare) -> dict:
    """The admission kernel of the GQA configurations
    (``ops/flash_attention.py``) at the shapes it is served with, each
    against a masked softmax in XLA over the first and the last query
    head (a full layer's float32 scores for all 128 would not fit) and
    timed: command-a-plus's full layer (a wave of 2 x 2,048 queries,
    128 heads on 8, over a 32,768-column extent, the pieces at ``pos0``
    0 and 22,528) and its ring in timeline order (6,144 columns, window
    4,096, a young row's begin bound and an old one's), Mistral's
    admission wave (4 x 2,048, 32 heads on 8) and EvaByte's piece
    (2,048 queries of 32 heads over 1,024 summary columns, 640 of them
    filled, then the window), all at the tiles the wrapper picks from
    the shapes. → a case: agreement (``compare``), milliseconds a call,
    the key tiles a head's rows walk (whole, edge) and leave (dead),
    and the share of the MXU's peak the time stands for, counted over
    the (query, column) pairs the mask leaves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.ops import flash_attention as fa

    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    d = 32 if rehearse else 128
    # name: (rows, heads, kv heads, queries, columns, window,
    #        query offsets, begin bounds, lengths)
    if rehearse:
        cases = {
            "full/pos0=0": (2, 8, 2, 32, 256, 0, [0] * 2, [0] * 2, [32] * 2),
            "ring/young+old": (2, 8, 2, 32, 96, 64, [64] * 2, [64, 0],
                               [96] * 2)}
    else:
        cases = {
            "full/pos0=0": (2, 128, 8, 2048, 32768, 0, [0] * 2, [0] * 2,
                            [2048] * 2),
            "full/pos0=22528": (2, 128, 8, 2048, 32768, 0, [22528] * 2,
                                [0] * 2, [24576] * 2),
            "ring/young+old": (2, 128, 8, 2048, 6144, 4096, [4096] * 2,
                               [4096, 0], [6144, 5000]),
            "mistral/4x2048": (4, 32, 8, 2048, 2048, 4096, [0] * 4, [0] * 4,
                               [2048, 1877, 1707, 1536]),
            "evabyte/2x2048": (2, 32, 32, 2048, 3072, 0, [1024] * 2,
                               [384] * 2, [3072] * 2)}
    rates: dict = {}
    for name, (b, hq, hkv, s, t, window, off, begin, kv_len) in \
            cases.items():
        keys = jax.random.split(jax.random.PRNGKey(44), 3)
        q = jax.random.normal(keys[0], (b, hq, s, d), dtype)
        k = jax.random.normal(keys[1], (b, hkv, t, d), dtype)
        v = jax.random.normal(keys[2], (b, hkv, t, d), dtype)
        off, begin, kv_len = (jnp.asarray(a, jnp.int32)
                              for a in (off, begin, kv_len))
        fn = functools.partial(
            fa.flash_attention, causal=True, window=window,
            kv_lengths=kv_len, q_offsets=off, kv_begins=begin,
            interpret=not on_tpu)
        got = jax.block_until_ready(fn(q, k, v))
        best = float("inf")
        for _ in range(1 if rehearse else 5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k, v))
            best = min(best, time.perf_counter() - t0)
        at = np.arange(s)[None, :, None] + np.asarray(off)[:, None, None]
        col = np.arange(t)[None, None, :]
        mask = ((col <= at) & (col > at - (window or t + s))
                & (col >= np.asarray(begin)[:, None, None])
                & (col < np.asarray(kv_len)[:, None, None]))
        pairs = int(mask.sum())
        heads = jnp.asarray([0, hq - 1])
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q[:, heads], k[:, heads // (hq // hkv)],
            preferred_element_type=jnp.float32) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(jnp.asarray(mask)[:, None], logits, -jnp.inf), axis=-1)
        want = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(dtype),
                          v[:, heads // (hq // hkv)])
        compare(f"flash_attention/{name}", got[:, heads], want)
        del logits, probs
        whole, edge, dead = fa.tile_counts(
            np.asarray(off), np.asarray(begin), np.asarray(kv_len), s, t, d,
            causal=True, window=window)
        rates[name] = {
            "tile": list(fa.tiles(s, t, d)), "ms": round(best * 1e3, 4),
            "tiles_whole": whole, "tiles_edge": edge, "tiles_dead": dead,
            "mxu_share": round(4 * pairs * hq * d / best / 197e12, 4)}
    return rates


def grouped_matmul_rates(rehearse: bool, compare) -> dict:
    """The experts' grouped int8 matmul (``ops/grouped_matmul.py``) at
    the served shapes, against XLA's ``ragged_dot`` over the
    dequantized layer (``compare``) and TIMED: an admission wave of one
    row of 256 tokens and of 1 and 2 x 2,048 (Xing4.0: 1,024, 8,192 and
    16,384 pairs, all over 64 experts of 1024 x 3584 and, the down
    matrix, 3584 x 1024; GLM-5: 16,384 and 32,768 pairs, an eighth of
    them over the 32 held experts of 6144 x 2048 and 2048 x 6144, the
    others of no group) and a decode step's 32 pairs; two layers' stack
    read at layer 1, an expert nobody chose among them. → a shape and a
    tiling (``served``; ``deep``, which served every shape until PR 42
    and is left out where the served tiles ARE the deep ones, a decode
    step's): milliseconds a matmul (the metadata's few XLA ops
    included), the share of the MXU's peak that stands for over the
    pairs that exist, the share of 819 GB/s over the touched experts'
    bytes, and the fill (pairs over visits x row tile)."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.ops import grouped_matmul

    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.default_rng(42)
    # experts, one pair in how many is of a held one, [k, n] of the
    # gate / up matrices and of the down matrix, the waves' pairs
    waves = {"xing": (8, 1, (256, 128), (128, 256), (128, 256)),
             "glm": (8, 8, (256, 128), (128, 256), (256, 512))} \
        if rehearse else {
            "xing": (64, 1, (1024, 3584), (3584, 1024),
                     (1024, 8192, 16384)),
            "glm": (32, 8, (6144, 2048), (2048, 6144), (16384, 32768))}

    def timed(fn, *args) -> float:
        best = float("inf")
        for _ in range(1 if rehearse else 5):
            t = time.perf_counter()
            for _ in range(8):
                out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t) / 8)
        return 1e3 * best

    rates: dict = {}
    for cell, (ge, held_of, up, down, ms_) in waves.items():
        # a decode step's pairs meet the first matrix, once
        for (gk, gn), gms in ((up, (32, *ms_) if cell == "xing" else ms_),
                              (down, ms_)):
            gq = jnp.asarray(rng.integers(-127, 128, (2, ge, gk, gn)),
                             jnp.int8)
            gscale = jnp.asarray(rng.uniform(0.5, 1.5, (2, ge, 1, gn))
                                 * gk ** -0.5 / 73.3, jnp.float32)
            layer = (gq[1].astype(jnp.float32) * gscale[1]).astype(dtype)
            for gm in gms:
                # a wave's pairs of held experts; a decode step's, an
                # eighth of its slots idle
                pairs = gm // held_of if gm > 64 else gm - gm // 8
                share = rng.multinomial(pairs, np.ones(ge) / ge)
                share[1] = 0                  # an expert nobody chose
                sizes = jnp.asarray(share, jnp.int32)
                used = int(share.sum())
                lhs = jnp.asarray(rng.standard_normal((gm, gk)), dtype)
                ref = jax.lax.ragged_dot(
                    lhs, layer, sizes, preferred_element_type=jnp.float32)
                tried = {"served": grouped_matmul.tiling(gm, ge, gk, gn)}
                deep = grouped_matmul.deep_tiling(gm, gk, gn)
                if deep != tried["served"]:
                    tried["deep"] = deep
                at = rates.setdefault(f"{cell}/{gk}x{gn}/m={gm}", {})
                for name, tiles in tried.items():
                    # the tiles are read when the program is traced
                    with mock.patch.object(grouped_matmul, "tiling",
                                           lambda *_a, t=tiles: t):
                        fn = jax.jit(functools.partial(
                            grouped_matmul.grouped_qmatmul.__wrapped__,
                            interpret=not on_tpu))
                        got = fn(lhs, gq, gscale, sizes, jnp.int32(1))
                        kept, mult = (int(c) for c in
                                      grouped_matmul.tile_counts(
                                          sizes, gm, gk, gn))
                    compare(f"grouped_qmatmul/{cell}/{gk}x{gn}/m={gm}"
                            f"/{name}", got[:used], ref[:used])
                    ms = timed(fn, lhs, gq, gscale, sizes, jnp.int32(1))
                    at[name] = {
                        "tiles": list(tiles[:3]), "ms": round(ms, 4),
                        "mxu_share": round(
                            2 * used * gk * gn / (ms * 1e-3) / 197e12, 4),
                        "hbm_share": round(
                            int((share > 0).sum()) * gk * gn
                            / (ms * 1e-3) / 819e9, 4),
                        "fill": round(kept / mult, 4)}
            del gq, gscale, layer
    return rates


def phase_kernels(rehearse: bool) -> int:
    t0 = time.monotonic()
    facts = _bring_up(rehearse)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.engine.generation import GenerationEngine
    from copilot_for_consensus_tpu.engine.kv_pool import BLOCK_TABLE_DTYPE
    from copilot_for_consensus_tpu.models import decoder_config, quant
    from copilot_for_consensus_tpu.ops.attention import (
        attention_xla,
        combine_partials,
        decode_attention,
    )
    from copilot_for_consensus_tpu.ops.flash_attention import flash_attention
    from copilot_for_consensus_tpu.ops.paged_attention import (
        paged_attention_partial_pallas,
        paged_decode_attention_pallas,
        paged_gather_layer,
    )
    from copilot_for_consensus_tpu.ops.quant_matmul import (
        int4_matmul,
        int4_matmul_xla,
    )

    on_tpu = facts["platform"] == "tpu"
    interpret = not on_tpu           # rehearsal only; the chip compiles
    # bf16 operands on the chip; XLA:CPU has no bf16 x bf16 -> f32 dot
    # for the interpreted int4 kernel, so a rehearsal computes in f32
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    cfg = decoder_config("tiny" if rehearse else "mistral-7b")
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = hq // hkv
    rng = np.random.default_rng(0)
    errors: dict[str, float] = {}

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def compare(name: str, got, ref) -> None:
        got = np.asarray(jax.device_get(got), np.float32)
        ref = np.asarray(jax.device_get(ref), np.float32)
        check(got.shape == ref.shape,
              f"{name}: shape {got.shape} != reference {ref.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        err = float(np.abs(got - ref).max()
                    / max(1.0, float(np.abs(ref).max())))
        errors[name] = float(f"{err:.3g}")
        check(err <= KERNEL_TOL,
              f"{name}: error {err:.4g} vs XLA reference exceeds "
              f"{KERNEL_TOL}")
        say(f"kernel {name}: error {err:.3g} (tol {KERNEL_TOL})")

    # -- flash attention (prefill, attn_impl="auto" on a TPU) ----------
    b, s = 2, 64 if rehearse else 512
    q, k, v = normal(b, hq, s, d), normal(b, hkv, s, d), normal(b, hkv, s, d)
    lens = jnp.asarray([s, s - s // 3], jnp.int32)
    for name, kw in (("plain", {}), ("windowed", {"window": s // 4}),
                     ("kv_lengths", {"kv_lengths": lens})):
        compare(f"flash_attention/{name}",
                flash_attention(q, k, v, causal=True, interpret=interpret,
                                **kw),
                attention_xla(q, k, v, causal=True, **kw))
    flash_rates = flash_attention_rates(rehearse, compare)
    say(f"flash attention at the served shapes: {flash_rates}")

    # -- paged kernel over an fp8 pool (kv_kernel="auto" on a TPU) -----
    n_l, nbtot, nb, blk, li = 2, 48, 8, 64, 1
    pool_k = normal(n_l, nbtot, hkv, blk, d).astype(jnp.float8_e4m3fn)
    pool_v = normal(n_l, nbtot, hkv, blk, d).astype(jnp.float8_e4m3fn)
    pb = 4
    tables = jnp.asarray(rng.integers(0, nbtot, (pb, nb)),
                         BLOCK_TABLE_DTYPE)
    # parked row, single token, full table, mid-block fill
    lengths = jnp.asarray([0, 1, nb * blk, 3 * blk + 17], jnp.int32)
    view_k, view_v = paged_gather_layer(pool_k[li], pool_v[li], tables)
    qd = normal(pb, hq, d)
    for window in (0, 2 * blk):
        compare(f"paged_decode_attention_pallas/r={group}/window={window}",
                paged_decode_attention_pallas(
                    qd, pool_k[li], pool_v[li], tables, lengths,
                    window=window, interpret=interpret),
                decode_attention(qd, view_k, view_v, lengths,
                                 window=window))
    # seeded rows: r = group * S query rows per kv head against the
    # committed prefix — as one wide "decode" it is the same reference
    s_rows = 16
    qs = normal(pb, hkv, group * s_rows, d)
    part = paged_attention_partial_pallas(
        qs, pool_k, pool_v, jnp.asarray([li], jnp.int32), tables,
        lengths, lengths - 1, window=0, interpret=interpret)
    compare(f"paged_attention_partial_pallas/r={group * s_rows}",
            combine_partials([part], dtype),
            decode_attention(qs.reshape(pb, hkv * group * s_rows, d),
                             view_k, view_v, lengths).reshape(qs.shape))

    # -- EVA decode attention over live blocks (attention="eva" on a
    # TPU), at EvaByte's widths: 32 heads of 128, windows of 2,048 ----
    from copilot_for_consensus_tpu.models import eva
    from copilot_for_consensus_tpu.ops.eva_attention import plan_blocks

    eh, ew, er, esteps = (4, 32, 16, 8) if rehearse else (32, 2048, 1024, 8)
    state = {n: normal(n_l, pb, eh, t, d)
             for n, t in (("k", ew + esteps), ("v", ew + esteps),
                          ("ks", er), ("vs", er))}
    # nothing live, a column, a window short of full behind a full
    # store, mid-block extents
    win_len = jnp.asarray([0, 1, ew - 1, ew // 2 + 5], jnp.int32)
    sum_n = jnp.asarray([0, 0, er - er // 8, er // 4], jnp.int32)
    qe, k_cur, v_cur = (normal(pb, eh, d) for _ in range(3))
    local = [(normal(pb, eh, esteps, d), normal(pb, eh, esteps, d),
              jnp.broadcast_to(jnp.arange(esteps)[None, :] < 3,
                               (pb, esteps)))]
    compare(
        "eva_decode_attention",
        eva.live_attention(
            qe, k_cur, v_cur, local, state, jnp.asarray(li, jnp.int32),
            plan_blocks(win_len, sum_n, window=ew, store=er), ew),
        eva.joint_attention(
            qe, k_cur, v_cur,
            [(state["k"][li], state["v"][li],
              jnp.arange(ew + esteps)[None, :] < win_len[:, None]),
             *local,
             (state["ks"][li], state["vs"][li],
              jnp.arange(er)[None, :] >= er - sum_n[:, None])]))

    # -- grouped int8 matmul over sparse experts (attention="mla" on a
    # TPU, ops/grouped_matmul.py), at the served shapes of a decode
    # step and of both MLA cells' admission waves: compared and timed --
    grouped_rates = grouped_matmul_rates(rehearse, compare)
    say(f"grouped int8 matmul over the experts, ms a matmul: "
        f"{grouped_rates}")

    # -- dense decode attention over live blocks (the contiguous cache
    # on a TPU), at the served cells' shapes: 8 slots x 4096 columns of
    # 8 kv heads of 128, all of Mistral's 32 layers. Two mixes of
    # lengths: five sequences and three empty slots (`qa-steady`'s
    # occupancy), eight sequences (`summarize-backlog`'s mean live
    # columns). The kernel's partial folded with the dispatch's own
    # columns against the XLA route over the prefix cut at the longest
    # slot's bucket; then a dispatch's 8 tokens of 32 layer calls under
    # one jit, timed: the XLA route whole (its one cut included), and
    # the kernel ALONE at each block width tried (the served one is
    # ``dense_attention.BLOCK``) ----------------------------------------
    from copilot_for_consensus_tpu.models.decoder import cache_prefix
    from copilot_for_consensus_tpu.ops import dense_attention
    from copilot_for_consensus_tpu.ops.attention import (
        decode_attention_prefix_window,
        decode_window_partial,
    )

    n_dl, slots, ext, w_sz = (2, 8, 512, 8) if rehearse else (32, 8, 4096, 8)
    mixes = {"qa": [600, 1200, 2100, 2300, 1700],
             "backlog": [2150, 2250, 2100, 2200, 700, 1300, 1650, 900]}
    if rehearse:
        mixes = {m: [n // 8 for n in ns] for m, ns in mixes.items()}
    dense = {h: jax.random.normal(jax.random.PRNGKey(i),
                                  (n_dl, slots, hkv, ext, d), dtype)
             for i, h in enumerate("kv")}        # made on the device
    qd8, kcur, vcur = normal(slots, hq, d), normal(slots, hkv, d), \
        normal(slots, hkv, d)
    kwin, vwin = normal(slots, hkv, w_sz, d), normal(slots, hkv, w_sz, d)
    w_at = jnp.asarray(3, jnp.int32)
    sw = cfg.sliding_window

    def positions(lens):
        return jnp.asarray(lens + [ext] * (slots - len(lens)), jnp.int32)

    def bucket(lens):
        return -(-(max(lens) + 1) // 128) * 128

    def xla_tokens(lens, tokens, dense, q):
        pos0, pref = positions(lens), cache_prefix(dense, bucket(lens))

        def layer(acc, kv):
            return acc + decode_attention_prefix_window(
                q, kv[0], kv[1], kwin, vwin, kcur, vcur, pos0, w_at,
                window=sw), None

        def token(acc, _):
            return jax.lax.scan(layer, acc, (pref["k"], pref["v"]))[0], None

        return jax.lax.scan(token, jnp.zeros_like(q), None,
                            length=tokens)[0]

    def kernel_tokens(lens, folded, tokens, dense, q):
        pos0 = positions(lens)
        plan = dense_attention.plan_blocks(
            *dense_attention.live_range(pos0, pos0 + w_at, sw, ext),
            extent=ext)
        qg = q.reshape(slots, hkv, group, d)
        local = decode_window_partial(qg, kwin, vwin, kcur, vcur, pos0,
                                      w_at, window=sw)

        def layer(acc, li):
            part = dense_attention.live_partial(
                qg, dense["k"], dense["v"], li, plan, interpret=interpret)
            if not folded:
                return acc + part[0], None
            return acc + combine_partials([part, local], q.dtype).reshape(
                q.shape), None

        def token(acc, _):
            return jax.lax.scan(layer, acc, jnp.arange(n_dl))[0], None

        acc0 = jnp.zeros_like(q) if folded \
            else jnp.zeros(qg.shape, jnp.float32)
        return jax.lax.scan(token, acc0, None, length=tokens)[0]

    def timed(fn, *args) -> float:
        """Milliseconds a token (a call a layer) of a dispatch of
        ``w_sz`` tokens, best of 5."""
        jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(1 if rehearse else 5):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t)
        return best * 1e3 / w_sz

    def rate(columns: int, ms: float, col_bytes: int, layers: int) -> dict:
        return {"columns": columns, "ms_per_token": round(ms, 4),
                "gb_per_s": round(columns * col_bytes * layers / ms / 1e6,
                                  1)}

    dense_rate = functools.partial(            # a column of both halves
        rate, col_bytes=2 * hkv * d * dense["k"].dtype.itemsize,
        layers=n_dl)

    dense_rates: dict = {"served_block": dense_attention.BLOCK}
    want = {}
    for mix, lens in mixes.items():
        want[mix] = jax.jit(functools.partial(xla_tokens, lens, 1))(
            dense, qd8)
        dense_rates[mix] = {
            "live_columns": sum(lens),
            "xla_prefix": dense_rate(slots * bucket(lens), timed(jax.jit(
                functools.partial(xla_tokens, lens, w_sz)), dense, qd8))}
    try:
        for width in (128, 256, 512):
            dense_attention.BLOCK = width
            for mix, lens in mixes.items():
                got = jax.jit(functools.partial(kernel_tokens, lens, True,
                                                1))(dense, qd8)
                compare(f"dense_decode_attention/{mix}/block={width}",
                        got[:len(lens)], want[mix][:len(lens)])
                dense_rates[mix][f"block_{width}"] = dense_rate(
                    sum(dense_attention.blocks_read(0, n, ext)
                        for n in lens),
                    timed(jax.jit(functools.partial(kernel_tokens, lens,
                                                    False, w_sz)),
                          dense, qd8))
    finally:
        dense_attention.BLOCK = dense_rates["served_block"]
    say(f"dense decode attention, {w_sz} tokens of {n_dl} layer calls a "
        f"dispatch: {dense_rates}")

    # -- absorbed latent attention over live blocks (attention="mla" on
    # a TPU, ops/latent_attention.py), at the served cell's shapes: 8
    # slots x 16,384 columns of 576 (the first 512 the value), 32 heads,
    # 12 layers; the cell's mix of lengths, a free slot and a full one
    # among them. The kernel's partial folded with the dispatch's own
    # rows against the XLA route that scores every slot's whole extent;
    # then a dispatch's 8 tokens of 12 layer calls under one jit, timed:
    # the XLA route whole, and the kernel ALONE at each block width
    # tried (the served one is ``latent_attention.BLOCK``) ---------------
    from copilot_for_consensus_tpu.ops import latent_attention

    n_ll, lh, lr, lrope, lext = (2, 4, 32, 8, 2048) if rehearse \
        else (12, 32, 512, 64, 16384)
    lwidth = lr + lrope
    lens_l = [16375, 15600, 15900, 2500, 4300, 7700, 13100]
    if rehearse:
        lens_l = [n // 8 for n in lens_l]
    pos_l = jnp.asarray(lens_l + [lext] * (slots - len(lens_l)), jnp.int32)
    latents = jax.random.normal(jax.random.PRNGKey(7),
                                (n_ll, slots, lwidth, lext), dtype)
    ql, curl = normal(slots, lh, lwidth), normal(slots, lwidth)
    winl = normal(slots, w_sz, lwidth)
    one = lambda a: a[:, None]  # noqa: E731

    def xla_latent(tokens, latents, q):
        def layer(acc, cache_l):
            rows = one(cache_l.transpose(0, 2, 1))
            return acc + decode_attention_prefix_window(
                q, rows, rows, one(winl), one(winl), one(curl), one(curl),
                pos_l, w_at)[..., :lr], None

        def token(acc, _):
            return jax.lax.scan(layer, acc, latents)[0], None

        return jax.lax.scan(token, jnp.zeros((slots, lh, lr), q.dtype),
                            None, length=tokens)[0]

    def kernel_latent(folded, tokens, latents, q):
        plan = latent_attention.plan_blocks(pos_l, extent=lext)
        local = decode_window_partial(
            one(q), one(winl), one(winl[..., :lr]), one(curl),
            one(curl[..., :lr]), pos_l, w_at)

        def layer(acc, li):
            part = latent_attention.live_partial(
                q, latents, li, plan, rank=lr, interpret=interpret)
            if not folded:
                return acc + part[0], None
            return acc + combine_partials(
                [tuple(one(a) for a in part), local], q.dtype)[:, 0], None

        def token(acc, _):
            return jax.lax.scan(layer, acc, jnp.arange(n_ll))[0], None

        return jax.lax.scan(
            token, jnp.zeros((slots, lh, lr),
                             q.dtype if folded else jnp.float32),
            None, length=tokens)[0]

    def timed_latent(fn) -> float:
        return timed(jax.jit(fn), latents, ql)

    rate_latent = functools.partial(
        rate, col_bytes=lwidth * latents.dtype.itemsize, layers=n_ll)

    want_l = jax.jit(functools.partial(xla_latent, 1))(latents, ql)
    latent_rates: dict = {
        "served_block": latent_attention.BLOCK,
        "live_columns": sum(lens_l),
        "xla_whole_extent": rate_latent(slots * lext, timed_latent(
            functools.partial(xla_latent, w_sz)))}
    try:
        for width in (256, 512, 1024):
            latent_attention.BLOCK = width
            got = jax.jit(functools.partial(kernel_latent, True, 1))(
                latents, ql)
            compare(f"mla_decode_attention/block={width}",
                    got[:len(lens_l)], want_l[:len(lens_l)])
            latent_rates[f"block_{width}"] = rate_latent(
                sum(latent_attention.blocks_read(n, lext) for n in lens_l),
                timed_latent(functools.partial(kernel_latent, False,
                                               w_sz)))
            latent_rates[f"block_{width}"]["folded_ms_per_token"] = round(
                timed_latent(functools.partial(kernel_latent, True, w_sz)),
                4)
    finally:
        latent_attention.BLOCK = latent_rates["served_block"]
    say(f"latent decode attention, {w_sz} tokens of {n_ll} layer calls a "
        f"dispatch: {latent_rates}")

    # -- the learned selection over the latent cache (attention="mla"
    # with cfg.index_topk, models/xing.py), at the served cell's shapes:
    # 8 slots x 32,768 columns of 576 + 128 (latent row, index key), 64
    # heads, 32 index heads, the 2,048 best kept, 6 layers; the cell's
    # mix of lengths, a free slot among them. (1) the threshold
    # (ops/sparse_select.py) keeps exactly ``jax.lax.top_k``'s set;
    # (2) the latent kernel walking live blocks under the selection's
    # mask against the XLA route over the whole extent under the same
    # mask; then a dispatch's 8 tokens of 6 layer calls, timed: indexer
    # and selection alone, and attention on either route ------------------
    from copilot_for_consensus_tpu.models import xing
    from copilot_for_consensus_tpu.models.configs import decoder_config
    from copilot_for_consensus_tpu.ops import sparse_select

    gcfg = decoder_config("tiny-glm") if rehearse else dataclasses.replace(
        decoder_config("tiny-glm"), kv_lora_rank=512, qk_rope_head_dim=64,
        n_heads=64, index_n_heads=32, index_head_dim=128, index_topk=2048)
    n_gl, gext = (2, 2048) if rehearse else (6, 32768)
    gr, gw = gcfg.kv_lora_rank, xing.latent_width(gcfg)
    gh, ghi, gdi = gcfg.n_heads, gcfg.index_n_heads, gcfg.index_head_dim
    lens_g = [31744, 24303, 4096, 17022, 29040, 9977, 11922]
    if rehearse:
        lens_g = [n // 16 for n in lens_g]
    pos_g = jnp.asarray(lens_g + [gext] * (slots - len(lens_g)), jnp.int32)
    glat = jax.random.normal(jax.random.PRNGKey(11),
                             (n_gl, slots, gw, gext), dtype)
    gidx = jax.random.normal(jax.random.PRNGKey(12),
                             (n_gl, slots, gdi, gext), dtype)
    gq, gcur, gwin = normal(slots, gh, gw), normal(slots, gw), \
        normal(slots, w_sz, gw)
    gqi, gwi = normal(slots, ghi, gdi), normal(slots, ghi).astype(
        jnp.float32)
    gki, gwin_i = normal(slots, gdi), normal(slots, w_sz, gdi)

    def keep_of(idx_l):
        return xing.select_step(gqi, gwi, gki, idx_l, gwin_i, pos_g, w_at,
                                gcfg)

    keep_c, keep_o = jax.jit(keep_of)(gidx[0])
    own = jnp.concatenate([gwin_i, gki[:, None]], axis=1)
    scores = jnp.concatenate([
        xing.index_scores(gqi[:, None], gwi[:, None], gidx[0])[:, 0],
        xing.index_scores(gqi[:, None], gwi[:, None],
                          own.transpose(0, 2, 1))[:, 0]], axis=-1)
    col = np.arange(gext + w_sz + 1)
    live = np.where(col < gext, col[None] < np.asarray(pos_g)[:, None],
                    (col - gext)[None] < int(w_at)) | (col == gext + w_sz)
    for b, n in enumerate(lens_g):
        kk = min(gcfg.index_topk, n + int(w_at) + 1)
        _, top = jax.lax.top_k(jnp.where(live[b], scores[b], -jnp.inf), kk)
        got_set = np.flatnonzero(np.concatenate(
            [np.asarray(keep_c[b]), np.asarray(keep_o[b])]))
        check(set(got_set.tolist()) == set(np.asarray(top).tolist()),
              f"sparse_select/slot={b}: the threshold keeps "
              f"{len(got_set)} columns, not top_k's {kk}")

    def kept_tokens(route, tokens, glat, gidx):
        plan = latent_attention.plan_blocks(pos_g, extent=gext)

        def layer(acc, xs):
            li, cache_l, idx_l = xs
            keep = keep_of(idx_l)
            if route == "select":
                return acc + keep[0].sum(-1, dtype=jnp.float32)[
                    :, None, None], None
            o = xing._kept_attention(
                gq, gcur, cache_l, gwin,
                (glat, li, plan) if route == "kernel" else None, keep, gr)
            return acc + o.astype(jnp.float32), None

        def token(acc, _):
            return jax.lax.scan(
                layer, acc,
                (jnp.arange(n_gl), None if route == "kernel" else glat,
                 gidx))[0], None

        return jax.lax.scan(token, jnp.zeros((slots, gh, gr), jnp.float32),
                            None, length=tokens)[0]

    kept = {r: jax.jit(functools.partial(kept_tokens, r, 1))(glat, gidx)
            for r in ("xla", "kernel")}
    compare("mla_decode_attention/kept", kept["kernel"][:len(lens_g)],
            kept["xla"][:len(lens_g)])
    kept_rates = {
        "live_columns": sum(lens_g),
        "kept_columns": sum(min(n + 4, gcfg.index_topk) for n in lens_g),
        **{f"{r}_ms_per_token": round(timed(jax.jit(functools.partial(
            kept_tokens, r, w_sz)), glat, gidx), 4)
           for r in ("select", "xla", "kernel")}}
    say(f"selected latent decode attention, {w_sz} tokens of {n_gl} layer "
        f"calls a dispatch (indexer + selection alone, then with "
        f"attention on either route): {kept_rates}")
    del glat, gidx

    # -- the admission threshold (ops/select_threshold.py on a TPU): a
    # query tile's sort keys in VMEM, the rounds of counting there ------
    threshold_rates = select_threshold_rates(rehearse)
    say(f"admission threshold, a wave of 2 x 2,048 queries: "
        f"{threshold_rates}")

    # -- what a round's expansion costs beside the fold kernel ---------
    expand_rates = latent_expand_rates(rehearse)
    say(f"latent admission attention, a round's expansion beside its "
        f"fold: {expand_rates}")

    # -- admission attention over latents (attention="mla" on a TPU,
    # ops/latent_prefill_attention.py): a round's scores stay in VMEM --
    prefill_rates = admission_attention_rates(rehearse, compare)
    say(f"latent admission attention, a wave of 2 x 2,048 queries: "
        f"{prefill_rates}")

    # -- int4 matmul (what quantize="int4" routes to) ------------------
    for name, (din, dout) in (("up", (cfg.d_model, cfg.d_ff)),
                              ("down", (cfg.d_ff, cfg.d_model))):
        leaf = quant.quantize_tensor_int4(
            normal(din, dout).astype(jnp.float32) * din ** -0.5)
        x = normal(8, din)
        compare(f"int4_matmul/{name}",
                int4_matmul(x, leaf["q4"], leaf["scale"],
                            interpret=interpret),
                int4_matmul_xla(x, leaf["q4"], leaf["scale"]))

    # -- one short paged engine run: the route must be the kernel ------
    cut = dataclasses.replace(
        cfg, n_layers=min(cfg.n_layers, KERNEL_PHASE_LAYERS))
    eng = GenerationEngine(
        cut, None, num_slots=4, max_len=512, prefill_buckets=(64, 256),
        quantize="int8", dtype=jnp.bfloat16, kv_dtype="float8_e4m3fn",
        kv_pool_blocks=64, prefix_cache_blocks=16, kv_kernel="auto",
        decode_window=8, seed=0)
    want_route = "kernel" if on_tpu else "reference"
    check(eng._kv_route == want_route,
          f"paged engine route {eng._kv_route!r}, expected "
          f"{want_route!r} on {facts['platform']}")
    head = rng.integers(3, cut.vocab_size, size=128).tolist()
    prompts = [head + rng.integers(3, cut.vocab_size, size=64).tolist()
               for _ in range(4)]
    new_tokens = 16
    for rnd in (1, 2):       # round 2 admits by pointer off the prefix trie
        comps = eng.generate(prompts, max_new_tokens=new_tokens)
        check(len(comps) == len(prompts), f"paged round {rnd}: lost requests")
        for c in comps:
            check(0 < len(c.tokens) <= new_tokens
                  and all(0 <= t < cut.vocab_size for t in c.tokens),
                  f"paged round {rnd}: bad completion {c}")
    stats = eng.kv_pool_stats()
    check(stats["zero_copy_admits"] > 0,
          f"paged round 2 made no zero-copy seeded admission: {stats}")
    check(eng.telemetry.errors == 0,
          f"paged engine telemetry errors {eng.telemetry.errors}")

    fact(phase="kernels", **facts,
         cache_files_after=_cache_files(facts["compile_cache"]),
         interpret=interpret, tolerance=KERNEL_TOL, errors=errors,
         model=cfg.name, paged_engine={
             "layers": cut.n_layers, "kv_route": eng._kv_route,
             "kv_dtype": "float8_e4m3fn",
             "zero_copy_admits": stats["zero_copy_admits"]},
         dense_decode_attention=dense_rates,
         mla_decode_attention=latent_rates,
         selected_latent_attention=kept_rates,
         select_threshold=threshold_rates,
         latent_expand=expand_rates,
         mla_prefill_attention=prefill_rates,
         flash_attention=flash_rates,
         grouped_qmatmul=grouped_rates,
         seconds=round(time.monotonic() - t0, 1))
    return 0


# ---------------------------------------------------------------------------
# child: serve phase (the real CLI, then a look at what it served with)
# ---------------------------------------------------------------------------


def phase_serve(config_path: str, rehearse: bool) -> int:
    facts = _bring_up(rehearse)

    import jax
    import jax.numpy as jnp

    from copilot_for_consensus_tpu import __main__ as cli
    from copilot_for_consensus_tpu.services import bootstrap

    # _cmd_serve keeps the server to itself; hold on to it so the
    # engine can be inspected after the drain
    held = {}
    build = bootstrap.serve_pipeline

    def holding(*args, **kwargs):
        held["server"] = build(*args, **kwargs)
        return held["server"]

    bootstrap.serve_pipeline = holding
    # prints the "serving" event, waits for the parent's SIGTERM, drains
    # and prints the "drained" event
    rc = cli.main(["serve", "--config", config_path,
                   "--host", "127.0.0.1", "--port", "0"])
    check(rc == 0, f"serve returned {rc}")

    pipeline = held["server"].pipeline
    eng = pipeline.summarization.summarizer.engine
    embed_eng = pipeline.embedding.provider._engine
    for name, e in (("generation", eng), ("embedding", embed_eng)):
        check(e.telemetry.errors == 0,
              f"{name} engine telemetry errors {e.telemetry.errors}")
    summaries = pipeline.store.query_documents("summaries")
    check(bool(summaries), "no summaries in the store")
    for doc in summaries:
        check(doc["completion_tokens"] > 0,
              f"summary {doc['summary_id']} has no generated tokens")

    # Prove the compiled route rather than trusting attn_impl="auto":
    # lower the prefill dispatch at every shape it was served with and
    # look for the Mosaic custom call (flash attention).
    shapes = sorted({(r.batch, r.padded_tokens // r.batch)
                     for r in eng.telemetry.recorder.records()
                     if r.kind == "prefill"})
    check(bool(shapes), "the flight recorder holds no prefill dispatch")
    i32 = jnp.int32
    mosaic = {}
    for n, bucket in shapes:
        text = eng._admit_fn.lower(
            eng.params, jax.ShapeDtypeStruct((n, bucket), i32),
            jax.ShapeDtypeStruct((n,), i32), eng._cache,
            jax.ShapeDtypeStruct((n,), i32), eng._key).as_text()
        mosaic[f"{n}x{bucket}"] = "tpu_custom_call" in text
    check(rehearse or all(mosaic.values()),
          f"served prefill program carries no Mosaic tpu_custom_call: "
          f"{mosaic}")

    fact(phase="serve-engine", **facts,
         cache_files_after=_cache_files(facts["compile_cache"]),
         model=eng.cfg.name, layers=eng.cfg.n_layers,
         num_slots=eng.num_slots, max_len=eng.max_len,
         kv_dtype=jnp.dtype(eng.kv_dtype).name, quantize=eng.quant_mode,
         prefill_mosaic_custom_call=mosaic,
         summaries=len(summaries),
         generated_tokens=sum(d["completion_tokens"] for d in summaries))
    return 0


# ---------------------------------------------------------------------------
# parent: no jax here, children one after another
# ---------------------------------------------------------------------------


class Child:
    """One phase process: stdout lines are echoed and kept; the process
    never outlives ``close``."""

    def __init__(self, argv: list[str]):
        self.proc = subprocess.Popen(
            [sys.executable, str(REPO / "chip_smoke.py"), *argv],
            cwd=REPO, stdout=subprocess.PIPE, text=True)
        self.lines: "queue.Queue[str | None]" = queue.Queue()
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            print(line, end="", flush=True)
            self.lines.put(line)
        self.lines.put(None)

    def next_json(self, deadline: float, what: str, **match) -> dict:
        """Next stdout line that parses as a JSON object carrying the
        ``match`` items (logger lines share the stream)."""
        while True:
            left = deadline - time.monotonic()
            check(left > 0, f"timed out waiting for {what}")
            try:
                line = self.lines.get(timeout=left)
            except queue.Empty:
                continue
            if line is None:        # stdout closed: the phase is gone
                raise SystemExit(f"chip_smoke FAILED: phase exited (rc "
                                 f"{self.proc.wait()}) before {what}")
            if line.lstrip().startswith("{"):
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if all(obj.get(k) == v for k, v in match.items()):
                    return obj

    def wait(self, deadline: float, what: str) -> int:
        try:
            return self.proc.wait(
                timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            raise SystemExit(f"chip_smoke FAILED: timed out waiting "
                             f"for {what}") from None

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._reader.join(timeout=5.0)


def http(url: str, body: dict | None = None, timeout: float = 60.0):
    req = urllib.request.Request(
        url, method="POST" if body is not None else "GET",
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        if "json" in resp.headers.get("Content-Type", ""):
            return resp.status, json.loads(raw)
        return resp.status, raw.decode()


def metric_total(text: str, name: str) -> float:
    """Sum of every sample of one Prometheus series family."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def fixture_copy(i: int) -> bytes:
    """The committed fixture with every address and message-id domain
    rewritten: copy ``i`` is new mail (new message ids), some of it
    joining the first copy's threads by subject."""
    text = FIXTURE.read_text()
    if i:
        for dom in ("example.org", "example.net", "example.com",
                    "example.io", "nowhere.org"):
            text = text.replace(f"@{dom}", f"@r{i}.{dom}")
    return text.encode()


def settled(base: str, archives: int) -> list[dict] | None:
    """The reports, once ``archives`` archives are through the pipeline
    and every thread holds exactly one; None while work is in flight."""
    ops = http(f"{base}/api/ops")[1]
    # report.published is the outbound notification key: nothing in
    # this deployment consumes it, so its depth is not backlog
    if (ops["collections"].get("archives", 0) < archives
            or any(ops["pending"].values())
            or any(depth for key, depth in ops["queues"].items()
                   if key != "report.published")):
        return None
    reports = http(f"{base}/api/reports?limit=100")[1]["reports"]
    threads = http(f"{base}/api/threads?limit=100")[1]["threads"]
    if not threads or len(reports) != len(threads) or \
            {r["thread_id"] for r in reports} != \
            {t["thread_id"] for t in threads}:
        return None
    return reports


def drive_server(base: str, deadline: float) -> dict:
    """upload → one report per thread → semantic search → metrics,
    twice: the first archive pays every first-call compile (set-up),
    the second is served warm."""
    status, _ = http(f"{base}/readyz")
    check(status == 200, f"/readyz → {status}")
    seconds = {}
    for i, label in enumerate(("warmup_s", "serving_s")):
        t0 = time.monotonic()
        status, out = http(f"{base}/api/upload", {
            "filename": f"ietf-sample-{i}.mbox", "source_id": f"smoke{i}",
            "content_b64": base64.b64encode(fixture_copy(i)).decode()})
        check(status == 201 and out.get("status") == "ingested",
              f"upload {i} → {status} {out}")
        reports, quiet = None, 0
        while quiet < 2:         # settled on two polls in a row
            check(time.monotonic() < deadline,
                  f"timed out waiting for archive {i}'s reports")
            time.sleep(1.0)
            reports = settled(base, i + 1)
            quiet = quiet + 1 if reports is not None else 0
        check(len(reports) == FIXTURE_THREADS if i == 0
              else len(reports) > FIXTURE_THREADS,
              f"archive {i}: {len(reports)} reported threads")
        # (random weights decode to mostly unprintable ids, so the text
        # itself may be empty; the serve child checks generated tokens)
        seconds[label] = round(time.monotonic() - t0, 1)
        say(f"archive {i}: {len(reports)} threads, one report each, "
            f"after {seconds[label]}s")

    topic = urllib.parse.quote("congestion control draft")
    status, found = http(
        f"{base}/api/reports/search?topic={topic}&semantic=true")
    check(status == 200 and found["reports"],
          f"semantic search → {status} {found}")
    status, health = http(f"{base}/health")
    check(status == 200 and not health.get("degraded"),
          f"/health → {status} {health}")
    status, metrics = http(f"{base}/metrics")
    check(status == 200, f"/metrics → {status}")
    check(metric_total(
        metrics, "copilot_engine_tokens_total") > 0, "no engine tokens")
    for series in ("copilot_engine_errors_total",
                   "copilot_engine_fault_watchdog_trips_total",
                   "copilot_engine_fault_breaker_state",
                   "copilot_engine_recovery_replays_total",
                   "copilot_engine_recovery_failed_total"):
        check(metric_total(metrics, series) == 0,
              f"{series} is non-zero after a clean run")
    check(metric_total(metrics, "copilot_vectorstore_queries_total") > 0,
          "the on-device vector store answered no query")
    return seconds


def run_parent(rehearse: bool) -> int:
    t_start = time.monotonic()
    deadline = t_start + BUDGET_S
    for needed in (REPO / "copilot_for_consensus_tpu", FIXTURE):
        check(needed.exists(),
              f"{needed} is missing: chip_smoke.py runs from a checkout")
    flag = ["--rehearse"] if rehearse else []

    kernels = Child(["--phase", "kernels", *flag])
    try:
        k = kernels.next_json(deadline, "the kernels facts",
                              phase="kernels")
        check(kernels.wait(deadline, "the kernels phase to exit") == 0,
              "kernels phase failed")
    finally:
        kernels.close()

    model = "tiny" if rehearse else "mistral-7b"
    config = {
        "embedding": {"driver": "tpu",
                      "model": "tiny" if rehearse else "minilm-l6"},
        "vector_store": {"driver": "tpu"},
        # num_slots / max_len / kv_dtype stay at the factory defaults
        # (4 x 4096, compute-dtype KV); the serve-engine line prints them
        "llm": {"driver": "tpu", "model": model, "quantize": "int8",
                "max_new_tokens": 64, "pipelined": True,
                "supervisor": True, "deadline_s": 300},
        "lifecycle": {"drain_deadline_s": 60},
    }
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        cfg_path = pathlib.Path(tmp) / "pipeline.json"
        cfg_path.write_text(json.dumps(config))
        t0 = time.monotonic()
        server = Child(["--phase", "serve", "--config", str(cfg_path),
                        *flag])
        try:
            serving = server.next_json(deadline, "the serving event",
                                       event="serving")
            build_s = round(time.monotonic() - t0, 1)
            check(rehearse or serving.get("platform") == "tpu",
                  f"serving event names {serving.get('platform')!r}")
            seconds = drive_server(
                f"http://127.0.0.1:{serving['port']}", deadline)
            server.proc.send_signal(signal.SIGTERM)
            drained = server.next_json(deadline, "the drained event",
                                       event="drained")
            for key in ("readiness_flipped", "consumers_stopped",
                        "outbox_flushed"):
                check(drained.get(key) is True, f"drain: {key} {drained}")
            check(all(drained.get("engines", {}).values()),
                  f"drain left engine work behind: {drained}")
            s = server.next_json(deadline, "the serve-engine facts",
                                 phase="serve-engine")
            check(server.wait(deadline, "serve to exit") == 0,
                  "serve exited non-zero")
        finally:
            server.close()

    device = {"platform": s["platform"], "kind": s["device_kind"],
              "count": s["device_count"]}
    check(device == {"platform": k["platform"], "kind": k["device_kind"],
                     "count": k["device_count"]},
          "the two phases saw different devices")
    fact(phase="summary", device=device, versions=s["versions"],
         compile_cache=s["compile_cache"],
         cache_files_before=k["cache_files_before"],
         cache_files_after=s["cache_files_after"],
         setup_seconds={"kernels_phase": k["seconds"],
                        "serve_build": build_s,
                        "serve_warmup": seconds["warmup_s"]},
         serving_seconds=seconds["serving_s"],
         total_seconds=round(time.monotonic() - t_start, 1))
    if rehearse:
        say(f"rehearsal finished on {device['platform']}: every phase "
            f"ran, which proves the script, not the chip — no result")
        return REHEARSAL_EXIT
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny models on whatever platform JAX was told "
                         "to use; never reports a pass")
    ap.add_argument("--phase", choices=("kernels", "serve"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--config", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase == "kernels":
        return phase_kernels(args.rehearse)
    if args.phase == "serve":
        return phase_serve(args.config, args.rehearse)
    return run_parent(args.rehearse)


if __name__ == "__main__":
    raise SystemExit(main())
