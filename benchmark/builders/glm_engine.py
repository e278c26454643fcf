"""Builder for GLM-5 (``model_type`` ``glm_moe_dsa``: latent attention
that reads a learned selection of the cached positions, sparse experts
beside a shared one of which this chip holds a share, one residual
stream) behind the same ``GenerationEngine`` and runner as the Xing4.0
builder, whose warm-up (every admission program a piece of one of the
plan's prompts can fall into, the one decode program) and closed-loop
burst it inherits: the model's keys, its seeded weights and the
program's config object are this file's. ``collect`` adds to each step
record the counts the program writes for this architecture (the
routing's over the held experts, counted on the device; the latent
rows and index keys read; the positions live and selected)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders._decoder import dims_of
from benchmark.builders.xing_engine import System as XingSystem
from benchmark.harness import weights as W

#: How far the seeded matrices that set a sublayer's size stand from
#: unit scale (every other matrix keeps activations at unit scale), so
#: that the one residual stream looks like a trained model's to the
#: comparison that decides ``correct``: Xing4.0's draw
#: (``builders/xing_engine.py:DRAWN``), for its reasons, with two
#: differences. ``wq_b`` stands at 3 where Xing4.0's stands at 1.5:
#: there YaRN's ``mscale ** 2`` = 2 doubles the scores, here nothing
#: does, and the scores' std is 3 in both, under which a query's
#: softmax rests on a few of its 2,048 chosen positions and attention
#: writes as much (0.3-0.45 of the stream, ``wo`` at 0.45) whether
#: those few hold different tokens or one token repeated. The first
#: draw of this PR had scores of std 1.5 and ``wo`` at 3 (a softmax
#: over some two hundred positions, whose mean is small): a decoded
#: sequence that fell to repeating itself then made attention's values
#: coherent, its write fifteen times larger, the next token certain
#: whatever the precision, and three of six replayed requests read no
#: gap at all under ANY control, int4 weights included, while the
#: other three read ``logit_gap_mean`` 0.004-0.009 with the 8-bit
#: control only 1.6-1.8 times above them (PERF.md section 6, PR 37).
#: The held experts' ``we_down`` stands at 0.2 where Xing4.0's 0.06:
#: of a token's 8 experts one is held here on average, and its term is
#: still 0.04 of the stream, so one expert chosen otherwise is seen.
DRAWN = {"tok_emb": 1.0, "wq_b": 3.0, "wo": 0.45, "w_down": 0.25,
         "we_down": 0.2}

GLM_COUNTS = ("experts_touched", "expert_rows", "expert_rows_max",
              "window_tokens", "state_tokens_read", "attn_pairs",
              "index_tokens_read", "selected_tokens", "live_tokens")


def seeded_weights(dims: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The program's layout for this model (two stacks of layers,
    ``dense`` and ``moe``; an int8 leaf is ``{"q", "scale"}`` with one
    float32 scale per output channel, per expert and channel in an
    expert stack; the expert stacks hold the ``n_routed_experts``
    experts of this chip's share, the router all
    ``held.router_experts`` columns), made on the device in one jitted
    call, in the types the configuration's ``serving`` states."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    rq, r, dr = (dims["q_lora_rank"], dims["kv_lora_rank"],
                 dims["qk_rope_head_dim"])
    dn, dv = dims["qk_nope_head_dim"], dims["v_head_dim"]
    hi, di = dims["index_n_heads"], dims["index_head_dim"]
    e_held, fe = dims["n_routed_experts"], dims["moe_intermediate_size"]
    e_all = dims["held"]["router_experts"]
    k0 = min(dims["first_k_dense_replace"], dims["num_hidden_layers"])

    def int8(key, shape, times=1.0):
        *lead, rows, cols = shape
        base = times * rows ** -0.5 / 73.3   # uniform int8 has std ~73.3

        def one(k, group=()):
            """One layer's matrix, or all of a layer's experts."""
            kq, ks = jax.random.split(k)
            # four int8 out of every 32 random bits
            words = jax.random.bits(kq, (*group, rows, cols // 4),
                                    jnp.uint32)
            q = jax.lax.bitcast_convert_type(words, jnp.int8)
            q = jnp.maximum(q.reshape(*group, rows, cols), -127)
            scale = base * jax.random.uniform(
                ks, (*group, 1, cols), jnp.float32, 0.5, 1.5)
            return {"q": q, "scale": scale}

        if not lead:
            return one(key)
        # layer by layer, so the scratch is one layer's
        return jax.lax.map(lambda k: one(k, tuple(lead[1:])),
                           jax.random.split(key, lead[0]))

    def normal(key, shape, std, dt):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)

    def gain(key, shape):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def stack(key, count: int, moe: bool) -> dict:
        keys = iter(jax.random.split(key, 32))
        out = {
            "attn_norm": gain(next(keys), (count, d)),
            "ffn_norm": gain(next(keys), (count, d)),
            "wq_a": int8(next(keys), (count, d, rq)),
            "q_norm": gain(next(keys), (count, rq)),
            "wq_b": int8(next(keys), (count, rq, h * (dn + dr)),
                         DRAWN["wq_b"]),
            "wkv_a": int8(next(keys), (count, d, r + dr)),
            "kv_norm": gain(next(keys), (count, r)),
            "wkv_b": normal(next(keys), (count, r, h * (dn + dv)),
                            r ** -0.5, dtype),
            "wo": int8(next(keys), (count, h * dv, d), DRAWN["wo"]),
            # the indexer: unit scale throughout; head weights of both
            # signs, a LayerNorm with a gain about one and a small bias
            "wq_idx": int8(next(keys), (count, rq, hi * di)),
            "wk_idx": int8(next(keys), (count, d, di)),
            "k_idx_gain": gain(next(keys), (count, di)),
            "k_idx_bias": normal(next(keys), (count, di), 0.1, dtype),
            "w_idx": normal(next(keys), (count, d, hi), d ** -0.5, dtype),
        }
        f = fe * max(dims["n_shared_experts"], 1) if moe \
            else dims["intermediate_size"]
        out.update(w_gate=int8(next(keys), (count, d, f)),
                   w_up=int8(next(keys), (count, d, f)),
                   w_down=int8(next(keys), (count, f, d), DRAWN["w_down"]))
        if moe:
            out.update(
                router=normal(next(keys), (count, d, e_all), d ** -0.5,
                              jnp.float32),
                e_bias=normal(next(keys), (count, e_all), 0.01,
                              jnp.float32),
                we_gate=int8(next(keys), (count, e_held, d, fe)),
                we_up=int8(next(keys), (count, e_held, d, fe)),
                we_down=int8(next(keys), (count, e_held, fe, d),
                             DRAWN["we_down"]))
        return out

    def build(key):
        k_emb, k_norm, k_head, k_dense, k_moe = jax.random.split(key, 5)
        out = {
            "tok_emb": (jax.random.truncated_normal(
                k_emb, -2, 2, (dims["vocab_size"], d), jnp.float32)
                * DRAWN["tok_emb"]).astype(dtype),
            "final_norm": gain(k_norm, (d,)),
            "lm_head": int8(k_head, (d, dims["vocab_size"])),
        }
        if k0:
            out["dense"] = stack(k_dense, k0, False)
        if dims["num_hidden_layers"] > k0:
            out["moe"] = stack(k_moe, dims["num_hidden_layers"] - k0, True)
        return out

    return jax.jit(build)(W.seed_key(seed))


class System(XingSystem):
    def make_dims(self, data: dict, rehearse: bool) -> dict:
        # before any weight is made: a program without the selection
        from copilot_for_consensus_tpu.models.configs import DecoderConfig

        if not hasattr(DecoderConfig, "index_topk"):
            raise SystemExit(
                f"{data['name']}: the program in this checkout cannot "
                f"serve a learned selection over the latent cache "
                f"(DecoderConfig has no index_topk)")
        dims = dims_of(data, rehearse)
        held = dims["held"]
        if dims["model_type"] != "glm_moe_dsa" \
                or not dims["norm_topk_prob"] \
                or dims["scoring_func"] != "sigmoid" \
                or dims["n_group"] != 1 or dims["topk_group"] != 1 \
                or not dims["rope_interleave"] \
                or not dims["indexer_rope_interleave"] \
                or dims["rope_parameters"]["rope_type"] != "default" \
                or held["first_expert"] + dims["n_routed_experts"] \
                > held["router_experts"]:
            raise ValueError(f"{data['name']}: not a shape this builder "
                             f"serves")
        return dims

    def make_weights(self, seed: int):
        return seeded_weights(self.dims, seed)

    def program_config(self, name: str):
        from copilot_for_consensus_tpu.models.configs import DecoderConfig

        d = self.dims
        return DecoderConfig(
            name=name, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"],
            n_heads=d["num_attention_heads"],
            n_kv_heads=d["num_key_value_heads"],
            d_ff=d["intermediate_size"],
            rope_theta=float(d["rope_parameters"]["rope_theta"]),
            max_seq_len=d["max_position_embeddings"],
            norm_eps=d["rms_norm_eps"], attention="mla",
            q_lora_rank=d["q_lora_rank"], kv_lora_rank=d["kv_lora_rank"],
            qk_nope_head_dim=d["qk_nope_head_dim"],
            qk_rope_head_dim=d["qk_rope_head_dim"],
            v_head_dim=d["v_head_dim"],
            n_routed_experts=d["held"]["router_experts"],
            held_experts=(d["held"]["first_expert"],
                          d["n_routed_experts"]),
            n_shared_experts=d["n_shared_experts"],
            experts_per_token=d["num_experts_per_tok"],
            moe_intermediate_size=d["moe_intermediate_size"],
            first_k_dense_replace=d["first_k_dense_replace"],
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            hc_mult=1, index_n_heads=d["index_n_heads"],
            index_head_dim=d["index_head_dim"],
            index_topk=d["index_topk"])

    def collect(self, plan: dict) -> dict:
        records = super().collect(plan)
        by_seq = {r.seq: r
                  for r in self.engine.telemetry.recorder.records()}
        for step in records["steps"]:
            rec = by_seq.get(step["seq"])
            for name in GLM_COUNTS:
                step[name] = getattr(rec, name, 0)
        return records
