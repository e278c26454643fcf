"""Builder for EvaByte (``model_type`` ``evabyte``, ``attention_class``
``eva``) behind the same ``GenerationEngine`` and runner as the engine
builder: the model's keys, its seeded weights and the program's config
object are this file's; tap, runner, load, warm-up and outcome are
inherited. ``collect`` adds to each step record the three counts the
program writes for this architecture alone (``windows_compacted``,
``window_tokens``, ``summary_tokens``), which the tap, written before
them, does not copy."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmark.builders._decoder import dims_of, token_ids
from benchmark.builders.engine import System as EngineSystem
from benchmark.harness import weights as W

EVA_COUNTS = ("windows_compacted", "window_tokens", "summary_tokens")


class System(EngineSystem):
    def make_dims(self, data: dict, rehearse: bool) -> dict:
        dims = dims_of(data, rehearse)
        if dims["attention_class"] != "eva" or dims["rope_scaling"]:
            raise ValueError(f"{data['name']}: not a shape this builder "
                             f"serves")
        # the source gives no head_dim; the harness's functions ask
        dims["head_dim"] = (dims["hidden_size"]
                            // dims["num_attention_heads"])
        return dims

    def make_weights(self, seed: int):
        """The decoder generator's int8 matrices, embedding and norms
        (``harness/weights.py``), shaped for this model: an output
        matrix of ``num_pred_heads`` x ``vocab_size`` columns, norm
        gains as offsets from one (the generator draws them around
        one), and ``mu``/``phi`` ``[L, H, Dh]`` in bfloat16 at the
        scale ``Dh ** -0.5``."""
        dims = self.dims
        vocab, heads = dims["vocab_size"], dims["num_pred_heads"]
        base = W.decoder_weights(dict(dims, vocab_size=vocab * heads),
                                 seed)
        shape = (dims["num_hidden_layers"], dims["num_attention_heads"],
                 dims["head_dim"])

        def small(norms, emb, key):
            k_mu, k_phi = jax.random.split(key)
            scale = shape[-1] ** -0.5
            mu, phi = (
                (scale * jax.random.normal(k, shape, jnp.float32)
                 ).astype(jnp.bfloat16) for k in (k_mu, k_phi))
            return [g - 1 for g in norms], emb[:vocab], mu, phi

        # the large leaves (the int8 matrices) pass by untouched
        names = ("attn_norm", "ffn_norm")
        norms, emb, mu, phi = jax.jit(small)(
            [base["layers"][n] for n in names] + [base["final_norm"]],
            base["tok_emb"], jax.random.fold_in(W.seed_key(seed), 0xE7A))
        layers = dict(base["layers"], mu=mu, phi=phi,
                      **dict(zip(names, norms)))
        return dict(base, tok_emb=emb, layers=layers, final_norm=norms[2])

    def program_config(self, name: str):
        from copilot_for_consensus_tpu.models.configs import DecoderConfig

        d = self.dims
        return DecoderConfig(
            name=name, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"],
            n_heads=d["num_attention_heads"],
            n_kv_heads=d["num_key_value_heads"],
            d_ff=d["intermediate_size"], rope_theta=float(d["rope_theta"]),
            max_seq_len=d["max_position_embeddings"],
            norm_eps=d["rms_norm_eps"], attention=d["attention_class"],
            window_size=d["window_size"], chunk_size=d["chunk_size"],
            num_pred_heads=d["num_pred_heads"],
            norm_unit_offset=d["norm_add_unit_offset"])

    def warm(self, plan: dict) -> None:
        """Every program the plan can reach, through the engine's
        public ``generate``: an admission wave for each bucket a piece
        of one of the plan's prompts falls in, at every row count
        (a power of two, under the admission token budget), and both
        decode programs (away from a window's edge, and across it)."""
        eng, rng = self.engine, self._rng
        vocab, w = eng.cfg.vocab_size, eng.cfg.window_size
        t0 = time.monotonic()
        sizes = set()
        for p_len in {it["prompt_len"] for it in plan["items"]}:
            at = 0
            while at < p_len:       # the engine's rule for a piece
                n = min(p_len - at, w - at % w, eng.buckets[-1])
                sizes.add(n)
                at += n
        used = sorted({next(b for b in eng.buckets if b >= n)
                       for n in sizes})
        waves = 0
        for bucket in used:
            rows = 1
            while (rows <= eng.num_slots
                   and (rows == 1 or rows * bucket
                        <= eng.admission_token_budget)):
                eng.generate([token_ids(rng, bucket, vocab)
                              for _ in range(rows)], 1)
                waves += 1
                rows *= 2
        steps = eng.decode_window
        eng.generate([token_ids(rng, w // 2, vocab)], 1 + steps)
        eng.generate([token_ids(rng, w - 3, vocab)], 1 + 2 * steps)
        self.parts["warm_s"] = time.monotonic() - t0
        self.log(f"warm-up: {waves} admission waves over buckets {used}, "
                 f"2 decode programs")

    def collect(self, plan: dict) -> dict:
        records = super().collect(plan)
        by_seq = {r.seq: r
                  for r in self.engine.telemetry.recorder.records()}
        for step in records["steps"]:
            rec = by_seq.get(step["seq"])
            for name in EVA_COUNTS:
                step[name] = getattr(rec, name, 0)
        return records
