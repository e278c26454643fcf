"""Operations and bytes a step of a Xing4.0-class decoder (latent
attention, sparse experts, a multi-stream residual) has to do, from
shapes and from the counts the program reports of its own routing.
Only what the algorithm needs is counted: each matrix outside the
experts once a decode step, the experts some live token chose, the
latent rows of the sequences that are decoding; real prompt tokens,
each expanded into keys and values once. Padding, the expansion of
earlier pieces' latents again, experts and latent rows the program
reads and masks count against the program, so a share can only read
under 100%."""

from __future__ import annotations


def expert_params(dims: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def attention_params(dims: dict) -> tuple[int, int]:
    """(int8 parameters, parameters of ``wkv_b``, which is served in
    bfloat16) of one layer's attention matrices."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    rq, r = dims["q_lora_rank"], dims["kv_lora_rank"]
    dn, dr, dv = (dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                  dims["v_head_dim"])
    return (d * rq + rq * h * (dn + dr) + d * (r + dr) + h * dv * d,
            r * h * (dn + dv))


def map_params(dims: dict) -> int:
    """One layer's two residual maps (float32)."""
    n = dims["hc_mult"]
    return 2 * n * dims["hidden_size"] * n * (n + 2)


def layer_counts(dims: dict) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    k0 = min(dims["first_k_dense_replace"], dims["num_hidden_layers"])
    return k0, dims["num_hidden_layers"] - k0


def latent_bytes_per_row(dims: dict, bytes_per_value: float) -> float:
    """One cached position of one sequence, all layers."""
    return (dims["num_hidden_layers"]
            * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"])
            * bytes_per_value)


def fixed_decode_bytes(dims: dict) -> float:
    """What every decode step reads whatever its tokens choose: the
    attention matrices, the dense layers' and the shared experts'
    SwiGLU and the output head at one byte a parameter, ``wkv_b`` at
    two, the routers and the residual maps at four. The embedding is a
    gather."""
    d = dims["hidden_size"]
    k0, km = layer_counts(dims)
    att8, att16 = attention_params(dims)
    shared = 3 * d * dims["moe_intermediate_size"] \
        * max(dims["n_shared_experts"], 1)
    return ((k0 + km) * (att8 + 2.0 * att16 + 4.0 * map_params(dims))
            + k0 * 3.0 * d * dims["intermediate_size"]
            + km * (shared + 4.0 * d * dims["n_routed_experts"])
            + d * dims["vocab_size"])


def decode_bytes(dims: dict, steps: int, experts_touched: int,
                 latent_rows: float, state_bytes_per_value: float,
                 part: str = "all") -> float:
    """A decode dispatch of ``steps`` steps: the fixed bytes a step,
    ``experts_touched`` experts (summed over layers and steps, as the
    program counts them) and ``latent_rows`` row reads (summed over the
    decoding sequences and steps). ``part`` ``"experts"`` or
    ``"latents"`` counts that term alone."""
    experts = float(experts_touched) * expert_params(dims)
    latents = latent_rows * latent_bytes_per_row(dims,
                                                 state_bytes_per_value)
    if part == "experts":
        return experts
    if part == "latents":
        return latents
    return steps * fixed_decode_bytes(dims) + experts + latents


def active_params(dims: dict) -> int:
    """Matrix parameters one token passes through, all layers, without
    the output head: attention (its own position's keys and values
    expanded once), the maps, the dense SwiGLU or the chosen experts,
    the shared expert and the router."""
    d = dims["hidden_size"]
    k0, km = layer_counts(dims)
    att8, att16 = attention_params(dims)
    moe = (dims["num_experts_per_tok"]
           + max(dims["n_shared_experts"], 1)) * expert_params(dims) \
        + d * dims["n_routed_experts"]
    return ((k0 + km) * (att8 + att16 + map_params(dims))
            + k0 * 3 * d * dims["intermediate_size"] + km * moe)


def prefill_flops(dims: dict, tokens: int, attn_pairs: int,
                  last_rows: int) -> float:
    """An admission wave: 2 FLOPs per active parameter and real token,
    the output head on the ``last_rows`` positions that yield a token,
    and expanded attention over ``attn_pairs`` query-key pairs (QK^T
    over ``qk_nope + qk_rope`` and PV over ``v_head_dim`` per head and
    layer)."""
    per_pair = 2.0 * dims["num_attention_heads"] * (
        dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
        + dims["v_head_dim"]) * dims["num_hidden_layers"]
    return (2.0 * active_params(dims) * tokens
            + 2.0 * dims["hidden_size"] * dims["vocab_size"] * last_rows
            + per_pair * attn_pairs)
