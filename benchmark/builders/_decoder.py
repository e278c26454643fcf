"""For builders that serve a decoder: the program's DecoderConfig from a
configuration file's published sizes, and the warm-up of exactly the
shapes a plan's requests reach."""

from __future__ import annotations

import numpy as np


#: what a configuration file says about itself, its serving and its
#: checks: every other key is the model's, as its source publishes it
FILE_KEYS = frozenset((
    "name", "source", "builder", "reference", "serving", "engine",
    "reduced", "assumed", "correct", "rehearsal", "why"))


def dims_of(cfg_data: dict, rehearse: bool) -> dict:
    """The model's keys, numbers or not: a layer pattern, a list of
    layer types or a nested ``rope_scaling`` reaches the builder and
    the reference as the file has it."""
    dims = {k: v for k, v in cfg_data.items() if k not in FILE_KEYS}
    if rehearse:
        dims.update(cfg_data["rehearsal"]["model"])
    return dims


def decoder_config(dims: dict, name: str):
    from copilot_for_consensus_tpu.models.configs import DecoderConfig

    derived = dims["hidden_size"] // dims["num_attention_heads"]
    return DecoderConfig(
        name=name, vocab_size=dims["vocab_size"],
        d_model=dims["hidden_size"], n_layers=dims["num_hidden_layers"],
        n_heads=dims["num_attention_heads"],
        n_kv_heads=dims["num_key_value_heads"],
        d_ff=dims["intermediate_size"], rope_theta=dims["rope_theta"],
        max_seq_len=dims["max_position_embeddings"],
        sliding_window=dims["sliding_window"],
        norm_eps=dims["rms_norm_eps"],
        head_dim_override=(0 if dims["head_dim"] == derived
                           else dims["head_dim"]))


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    return rng.integers(3, vocab, size=n).tolist()


def warm_engine(engine, shapes, rng, log) -> dict:
    """Drive every program the shapes can reach through the engine's
    public ``generate``: each admission wave (rows padded to a power of
    two x prompt bucket, under the admission token budget) and one
    decode dispatch in every 128-token stretch of cache the requests
    pass through. ``shapes`` is a list of (prompt_len, new_tokens)."""
    vocab = engine.cfg.vocab_size
    limit = engine.prompt_limit
    buckets = engine.buckets
    prompts = sorted({min(p, limit) for p, _ in shapes})
    used = sorted({next(b for b in buckets if b >= p) for p in prompts})
    waves = 0
    for bucket in used:
        longest = max(p for p in prompts if p <= bucket)
        rows_max = max(1, min(engine.num_slots,
                              engine.admission_token_budget // bucket))
        n = 1
        while True:
            rows = min(n, rows_max)
            engine.generate([token_ids(rng, longest, vocab)
                             for _ in range(rows)], 1)
            waves += 1
            if n >= rows_max:
                break
            n *= 2
    lo = min(p for p in prompts)
    hi = max(min(p, limit) + n for p, n in shapes)
    step = engine.decode_window * engine.windows_per_dispatch
    decodes = 0
    for start in range(lo - lo % 128, min(hi, limit + 1), 128):
        engine.generate([token_ids(rng, max(lo, min(start, limit)),
                                   vocab)], 2)
        decodes += 1
    if hi > limit:
        engine.generate([token_ids(rng, limit, vocab)],
                        hi - limit + step)
        decodes += 1
    log(f"warm-up: {waves} admission waves over buckets {used}, "
        f"{decodes} decode stretches from {lo} to {hi} tokens")
    return {"waves": waves, "decode_stretches": decodes}
