"""Operations and bytes a step of a GLM-5-class decoder (latent
attention read through a learned selection, an index key beside every
latent row, a held share of sparse experts, one residual stream) has to
do, from shapes and from the counts the program reports. Only what the
algorithm needs is counted, whatever implements it: each matrix outside
the experts once a decode step, the held experts some live token chose,
every live index key once a step and layer (the indexer has to score
them all), the ``index_topk`` or fewer latent rows a token attends to;
real prompt tokens, each expanded into keys and values once, index
scores for every query-position pair a prompt has, attention over the
selected pairs alone. Padding, latent rows walked and masked, index
keys read beyond a slot's length, the expansion of earlier pieces'
latents again and experts fetched for nobody count against the program,
so a share can only read under 100%."""

from __future__ import annotations


def expert_params(dims: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def attention_params(dims: dict) -> tuple[int, int]:
    """(int8 parameters, bfloat16 parameters) of one layer's attention
    and indexer: ``wkv_b`` and the indexer's head weights are served in
    bfloat16."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    rq, r = dims["q_lora_rank"], dims["kv_lora_rank"]
    dn, dr, dv = (dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                  dims["v_head_dim"])
    hi, di = dims["index_n_heads"], dims["index_head_dim"]
    return (d * rq + rq * h * (dn + dr) + d * (r + dr) + h * dv * d
            + rq * hi * di + d * di,
            r * h * (dn + dv) + d * hi)


def layer_counts(dims: dict) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    k0 = min(dims["first_k_dense_replace"], dims["num_hidden_layers"])
    return k0, dims["num_hidden_layers"] - k0


def state_bytes_per_row(dims: dict, bytes_per_value: float
                        ) -> tuple[float, float]:
    """One cached position of one sequence, all layers: (its latent
    rows, its index keys)."""
    n = dims["num_hidden_layers"] * bytes_per_value
    return (n * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"]),
            n * dims["index_head_dim"])


def _outside_experts(dims: dict) -> tuple[int, int]:
    """Per layer kind, what a token passes outside attention and the
    routed experts: (a dense layer's SwiGLU, an expert layer's shared
    expert) in int8 parameters; the router's float32 columns apart."""
    d = dims["hidden_size"]
    return (3 * d * dims["intermediate_size"],
            3 * d * dims["moe_intermediate_size"]
            * max(dims["n_shared_experts"], 1))


def fixed_decode_bytes(dims: dict) -> float:
    """What every decode step reads whatever its tokens choose: the
    attention and indexer matrices, the dense layers' and the shared
    experts' SwiGLU and the head over the vocabulary slice at one byte
    a parameter, ``wkv_b`` and the head weights at two, the routers
    (their full published width) at four. The embedding is a gather."""
    d = dims["hidden_size"]
    k0, km = layer_counts(dims)
    att8, att16 = attention_params(dims)
    dense, shared = _outside_experts(dims)
    return ((k0 + km) * (att8 + 2.0 * att16) + k0 * dense
            + km * (shared + 4.0 * d * dims["held"]["router_experts"])
            + d * dims["vocab_size"])


def decode_bytes(dims: dict, steps: int, experts_touched: int,
                 live_rows: float, selected_rows: float,
                 state_bytes_per_value: float, part: str = "all"
                 ) -> float:
    """A decode dispatch of ``steps`` steps: the fixed bytes a step,
    ``experts_touched`` held experts (summed over layers and steps, as
    the program counts them), the index keys of ``live_rows`` positions
    (every live position of every decoding sequence, summed over the
    steps) and the latent rows of ``selected_rows`` (the positions the
    tokens attend to, likewise). ``part`` ``"experts"``, ``"index"`` or
    ``"latents"`` counts that term alone."""
    latent, index = state_bytes_per_row(dims, state_bytes_per_value)
    terms = {"experts": float(experts_touched) * expert_params(dims),
             "index": live_rows * index,
             "latents": selected_rows * latent}
    if part != "all":
        return terms[part]
    return steps * fixed_decode_bytes(dims) + sum(terms.values())


def prefill_flops(dims: dict, tokens: int, index_pairs: int,
                  selected_pairs: int, expert_rows: int,
                  last_rows: int) -> float:
    """An admission wave: 2 FLOPs per parameter a real token passes
    (attention with its own position's keys and values expanded once,
    the indexer, the dense SwiGLU or the shared expert and the router;
    the held experts by the ``expert_rows`` token-expert pairs the
    program counted over all layers), the head on the ``last_rows``
    positions that yield a token, index scores over ``index_pairs``
    query-position pairs (every earlier position of the sequence, per
    layer ``index_n_heads`` dots of ``index_head_dim``) and expanded
    attention over the ``selected_pairs`` a query keeps (QK^T over
    ``qk_nope + qk_rope`` and PV over ``v_head_dim`` per head and
    layer)."""
    d = dims["hidden_size"]
    k0, km = layer_counts(dims)
    att8, att16 = attention_params(dims)
    dense, shared = _outside_experts(dims)
    per_token = ((k0 + km) * (att8 + att16) + k0 * dense
                 + km * (shared + d * dims["held"]["router_experts"]))
    layers = dims["num_hidden_layers"]
    per_index = 2.0 * dims["index_n_heads"] * dims["index_head_dim"]
    per_attn = 2.0 * dims["num_attention_heads"] * (
        dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
        + dims["v_head_dim"])
    return (2.0 * per_token * tokens
            + 2.0 * expert_params(dims) * expert_rows
            + 2.0 * d * dims["vocab_size"] * last_rows
            + layers * (per_index * index_pairs
                        + per_attn * selected_pairs))
