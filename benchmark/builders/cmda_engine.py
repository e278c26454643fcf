"""Builder for command-a-plus (``model_type`` ``cohere2_moe``: window
and global layers in one model, each kind with a cache of its own;
grouped queries; attention and sparse experts side by side on one
LayerNorm; averaged shared experts; a tied head; a held share of the
experts) behind the same ``GenerationEngine`` and runner as the Xing4.0
builder, whose warm-up (every admission program a piece of one of the
plan's prompts can fall into, the one decode program) and closed-loop
burst it inherits: the model's keys, its seeded weights and the
program's config object are this file's. ``collect`` adds to each step
record the counts the program writes for this architecture (the
routing's over the held experts, counted on the device; the columns a
layer of each kind read; the query-key pairs each kind scored)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders._decoder import dims_of
from benchmark.builders.xing_engine import System as XingSystem
from benchmark.harness import weights as W

#: How far the seeded matrices that set a sublayer's size stand from
#: unit scale (every other matrix keeps activations at unit scale), so
#: that the stream looks like a trained model's to the comparison that
#: decides ``correct``: GLM-5's draw (``builders/glm_engine.py:DRAWN``)
#: for its reasons, but for the embedding. ``wq`` at 3: scores of std
#: 3, under which a query's softmax rests on a few of its positions and
#: attention writes as much (``wo`` at 0.45: 0.3-0.45 of a unit stream
#: a layer) after a long prompt as after a short one, in a window layer
#: as in a global one. The held experts' ``we_down`` at 0.2: of a
#: token's 8 experts one is held here on average, and its term is
#: still 0.04. The shared experts' ``w_down`` stays at unit scale:
#: their four outputs are averaged, a quarter of what the one wide
#: SwiGLU sums, 0.15. ``tok_emb`` TIMES ``hidden_size ** -0.5``,
#: because the head is the embedding: the logit of the token a
#: position holds is ``LN(x) . e = |e|^2 / std(x)``, and with a unit
#: embedding under writes of 1.35 in all (eight layers) that is 64
#: standard deviations of the other tokens' logits at d = 4,096, so
#: every position predicts itself and every request decodes its
#: prompt's last token over and over, at any precision: the first
#: draw of this PR read ``logit_gap_mean`` 0.0 exactly, sound and under
#: both controls (PERF.md section 6, PR 43). A trained tied model
#: learns that away; seeded, the embedding stands at ``d ** -0.5`` a
#: value, where the token's own logit is 0.7 standard deviations, one
#: contender among the others, and the stream is what the layers
#: wrote. (``builders/xing_engine.py`` warns against such an embedding
#: where the routed sum is most of every write: here attention's 0.45
#: and the shared experts' 0.15 are, and one held expert chosen
#: otherwise moves a tenth of a layer's write.)
DRAWN = {"tok_emb": 1.0, "wq": 3.0, "wo": 0.45, "w_down": 1.0,
         "we_down": 0.2}

CMDA_COUNTS = ("experts_touched", "expert_rows", "expert_rows_max",
               "expert_group_rows", "expert_tile_rows", "window_tokens",
               "state_tokens_read", "window_tokens_read", "live_tokens",
               "window_live_tokens", "attn_pairs", "window_attn_pairs")


def seeded_weights(dims: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The program's layout for this model (``models/mixed.py``: one
    stack of all layers, both kinds holding the same matrices; an int8
    leaf is ``{"q", "scale"}`` with one float32 scale per output
    channel, per expert and channel in an expert stack; the expert
    stacks hold the ``num_experts`` experts of this chip's share, the
    router all ``held.router_experts`` columns; the shared experts side
    by side in one SwiGLU; no head: the embedding is it), made on the
    device in one jitted call, in the types the configuration's
    ``serving`` states."""
    d, dh = dims["hidden_size"], dims["head_dim"]
    h, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    fe, n = dims["intermediate_size"], dims["num_hidden_layers"]
    f = fe * dims["num_shared_experts"]
    e_held, e_all = dims["num_experts"], dims["held"]["router_experts"]

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

        # layer by layer, so the scratch is one layer's
        return jax.lax.map(lambda k: one(k, tuple(lead[1:])),
                           jax.random.split(key, lead[0]))

    def gain(key, shape):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def build(key):
        k_emb, k_norm, k_layers = jax.random.split(key, 3)
        keys = iter(jax.random.split(k_layers, 16))
        return {
            "tok_emb": (jax.random.truncated_normal(
                k_emb, -2, 2, (dims["vocab_size"], d), jnp.float32)
                * DRAWN["tok_emb"] * d ** -0.5).astype(dtype),
            "final_norm": gain(k_norm, (d,)),
            "layers": {
                "norm": gain(next(keys), (n, d)),
                "wq": int8(next(keys), (n, d, h * dh), DRAWN["wq"]),
                "wk": int8(next(keys), (n, d, hkv * dh)),
                "wv": int8(next(keys), (n, d, hkv * dh)),
                "wo": int8(next(keys), (n, h * dh, d), DRAWN["wo"]),
                "w_gate": int8(next(keys), (n, d, f)),
                "w_up": int8(next(keys), (n, d, f)),
                "w_down": int8(next(keys), (n, f, d), DRAWN["w_down"]),
                "router": (d ** -0.5 * jax.random.normal(
                    next(keys), (n, d, e_all), jnp.float32)),
                "we_gate": int8(next(keys), (n, e_held, d, fe)),
                "we_up": int8(next(keys), (n, e_held, d, fe)),
                "we_down": int8(next(keys), (n, e_held, fe, d),
                                DRAWN["we_down"]),
            },
        }

    return jax.jit(build)(W.seed_key(seed))


def pattern(dims: dict) -> tuple[int, int]:
    """(period, which member of it is the global layer) of the layers
    that are run, or a ValueError: not whole periods of one pattern."""
    kinds = dims["layer_types"][:dims["num_hidden_layers"]]
    if "full_attention" not in kinds:
        raise ValueError("no full_attention layer among those run")
    first = kinds.index("full_attention")
    period = kinds.index("full_attention", first + 1) - first \
        if kinds.count("full_attention") > 1 else len(kinds)
    want = ["full_attention" if i % period == first
            else "sliding_attention" for i in range(len(kinds))]
    if kinds != want or len(kinds) % period:
        raise ValueError(f"layer_types {kinds} are not whole periods of "
                         f"one pattern")
    return period, first


class System(XingSystem):
    def make_dims(self, data: dict, rehearse: bool) -> dict:
        # before any weight is made: a program without the two caches
        from copilot_for_consensus_tpu.models.configs import DecoderConfig

        if not hasattr(DecoderConfig, "layer_period"):
            raise SystemExit(
                f"{data['name']}: the program in this checkout cannot "
                f"serve window and global layers in one model "
                f"(DecoderConfig has no layer_period)")
        dims = dims_of(data, rehearse)
        held = dims["held"]
        if dims["model_type"] != "cohere2_moe" \
                or not dims["norm_topk_prob"] \
                or dims["expert_selection_fn"] != "sigmoid" \
                or dims["position_embedding_type"] != "rope_gptj" \
                or dims["rotary_pct"] != 1 or dims["first_k_dense_replace"] \
                or not dims["use_parallel_block"] or dims["use_qk_norm"] \
                or dims["attention_bias"] \
                or not dims["tie_word_embeddings"] \
                or dims["shared_expert_combination_strategy"] not in (
                    "average", "sum") \
                or held["first_expert"] + dims["num_experts"] \
                > held["router_experts"]:
            raise ValueError(f"{data['name']}: not a shape this builder "
                             f"serves")
        pattern(dims)
        return dims

    def make_weights(self, seed: int):
        return seeded_weights(self.dims, seed)

    def program_config(self, name: str):
        from copilot_for_consensus_tpu.models.configs import DecoderConfig

        d = self.dims
        period, member = pattern(d)
        return DecoderConfig(
            name=name, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"],
            n_heads=d["num_attention_heads"],
            n_kv_heads=d["num_key_value_heads"],
            head_dim_override=d["head_dim"], d_ff=d["intermediate_size"],
            rope_theta=float(d["rope_theta"]),
            max_seq_len=d["max_position_embeddings"],
            sliding_window=d["sliding_window"],
            norm_eps=d["layer_norm_eps"], attention="mixed",
            layer_period=period, global_member=member, window_rope=True,
            global_rope=False, norm_kind="layer", parallel_block=True,
            n_routed_experts=d["held"]["router_experts"],
            held_experts=(d["held"]["first_expert"], d["num_experts"]),
            n_shared_experts=d["num_shared_experts"],
            shared_expert_combine=d["shared_expert_combination_strategy"],
            experts_per_token=d["num_experts_per_tok"],
            moe_intermediate_size=d["intermediate_size"],
            tie_embeddings=True, logit_scale=float(d["logit_scale"]))

    def collect(self, plan: dict) -> dict:
        records = super().collect(plan)
        by_seq = {r.seq: r
                  for r in self.engine.telemetry.recorder.records()}
        for step in records["steps"]:
            rec = by_seq.get(step["seq"])
            for name in CMDA_COUNTS:
                step[name] = getattr(rec, name, 0)
        return records
