#!/usr/bin/env python3
"""How often the served program and the plain reference choose
differently, for a configuration whose attention is SELECTED
(``glm5-744b-a40b-int8``): one prompt of ``--tokens`` seeded ids is
admitted piece by piece through the program's own admission function
(``xing.prefill_piece``, the engine's sizes and types), then decoded
for one dispatch, and every layer's chosen set is fetched (the mask
``piece_attention`` and ``absorbed_attention`` are handed) and held
against the reference's boolean selection over prompt + served tokens.
Reports, per layer: queries whose sets differ, positions that differ a
query, and how far the reference's score of each differing position
stands from the row's k-th largest, in units of the row's spread: a
sound program differs only within rounding of it. Not part of a
benchmark run.

  glm_selection.py --workload W --seed 5 --tokens 8192 [--rehearse]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import correct, spec
    from copilot_for_consensus_tpu.models import xing
    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
    )

    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    builder = spec.module("builders", cell["config_data"]["builder"])
    ref = spec.module("reference", cell["config_data"]["reference"])
    system = builder.System(cell, args.seed, args.rehearse,
                            lambda m: print(f"[sel] {m}", flush=True))
    eng, cfg = system.engine, system.engine.cfg
    print(f"[sel] platform: {jax.devices()[0].platform}", flush=True)
    piece, steps, t = eng.buckets[-1], eng.decode_window, eng.max_len
    n = min(args.tokens, t - steps) // piece * piece
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
    masks: dict = {}

    real_attn, real_step = xing.piece_attention, xing.select_step
    blk = min(xing.KV_BLOCK, t)

    def attn(q, cache_a, li, slots, q_pos, kv_len, n_blocks, layer, c,
             keep=None):
        sel = jnp.concatenate([keep(j) for j in range(t // blk)], axis=-1)
        jax.debug.callback(
            lambda a, i, p0, depth=cache_a.shape[0]: masks.__setitem__(
                ("piece", int(p0), depth, int(i)), np.asarray(a[0])),
            sel, li, q_pos[0, 0])
        return real_attn(q, cache_a, li, slots, q_pos, kv_len, n_blocks,
                         layer, c, keep)

    step_rows: list = []       # in the order run: step by step, and
    #                            within a step layer by layer

    def step(q_i, w_i, k_cur, idx_l, idx_win_l, pos0, w, c):
        keep_c, keep_o = real_step(q_i, w_i, k_cur, idx_l, idx_win_l,
                                   pos0, w, c)
        jax.debug.callback(
            lambda a, b, ww: step_rows.append(
                (int(ww), np.asarray(a[0]), np.asarray(b[0]))),
            keep_c, keep_o, w, ordered=True)
        return keep_c, keep_o

    xing.piece_attention, xing.select_step = attn, step
    try:
        cache = eng._cache
        admit = jax.jit(lambda p, tk, ln, p0, sl, ca: xing.prefill_piece(
            p, tk, ln, p0, sl, cfg, ca), donate_argnums=(5,))
        for at in range(0, n, piece):
            logits, cache, _ = admit(
                eng.params, jnp.asarray(prompt[None, at:at + piece]),
                jnp.asarray([piece]), jnp.asarray([at]), jnp.asarray([0]),
                cache)
        first = int(np.asarray(logits[0]).argmax())
        pos = np.full((eng.num_slots,), t, np.int32)
        pos[0] = n
        tok = np.zeros((eng.num_slots,), np.int32)
        tok[0] = first
        greedy = lambda lg, _k: jnp.argmax(lg, -1).astype(jnp.int32)  # noqa: E731,E501
        toks, cache, _ = jax.jit(lambda p, tk, ps, ca: xing.decode_tokens(
            p, tk, ps, cfg, ca, jax.random.PRNGKey(0), greedy, steps=steps,
            max_len=t, live_blocks=eng._reads_latent_blocks()),
            donate_argnums=(3,))(eng.params, jnp.asarray(tok),
                                 jnp.asarray(pos), cache)
        served = [first] + [int(v) for v in np.asarray(toks)[:-1, 0]]
        jax.effects_barrier()
    finally:
        xing.piece_attention, xing.select_step = real_attn, real_step
    del cache
    eng._cache = None
    correct.free_device_memory(system.weights)

    seq = np.concatenate([prompt, np.asarray(served, np.int32)])
    selected, margins = [], []
    ref.hidden_states(system.weights, system.dims, seq, selected=selected,
                      margins=margins)
    # the program's sets, layer by layer in the order of the stacks (a
    # stack is told by its depth: the leading dense layers are fewer
    # than the expert layers in every cut this tool is run on)
    layer_of = [(depth, li) for depth in xing.stacks(cfg).values()
                for li in range(depth)]
    by_step = [[x for x in step_rows if x[0] == w] for w in range(steps)]
    report = []
    for layer, (depth, li) in enumerate(layer_of):
        have = np.zeros((len(seq), len(seq)), bool)
        for at in range(0, n, piece):
            have[at:at + piece, :n] = masks[
                ("piece", at, depth, li)][:, :n]
        for w, keep_c, keep_o in (rows[layer] for rows in by_step):
            p = n + w
            have[p, :n] = keep_c[:n]
            have[p, n:n + w] = keep_o[:w]
            have[p, p] = keep_o[-1]
        want = selected[layer][:len(seq), :len(seq)]
        diff = have != want
        per_query = diff.sum(-1)
        far = np.abs(margins[layer][:len(seq), :len(seq)][diff])
        report.append({
            "layer": layer, "queries": int(len(seq)),
            "queries_that_differ": int((per_query > 0).sum()),
            "positions_that_differ": int(diff.sum()),
            "most_in_one_query": int(per_query.max()),
            "decode_queries_that_differ": int((per_query[n:] > 0).sum()),
            "selected_a_query": int(want[-1].sum()),
            "margin_max": float(far.max()) if far.size else 0.0,
            "margin_p50": float(np.median(far)) if far.size else 0.0,
            "margin_p99": float(np.quantile(far, 0.99)) if far.size
            else 0.0})
        print("[sel] " + json.dumps(report[-1]), flush=True)
    total = sum(r["positions_that_differ"] for r in report)
    pairs = sum(int(selected[i][:len(seq), :len(seq)].sum())
                for i in range(len(report)))
    print("[sel] " + json.dumps({
        "tokens": int(len(seq)), "differing_positions": total,
        "selected_pairs": pairs,
        "share_of_selected": total / 2 / max(pairs, 1),
        "margin_max": max(r["margin_max"] for r in report)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
