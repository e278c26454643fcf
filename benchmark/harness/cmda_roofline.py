"""Operations and bytes a step of a ``cohere2_moe``-class decoder
(window and global layers in one model, grouped queries, a held share
of sparse experts beside shared ones, a tied head) has to do, from
shapes and from the counts the program reports. Only what the
algorithm needs is counted, whatever implements it: each matrix outside
the routed experts once a decode step, the held experts some live token
chose, the keys and values of a sequence's live positions in the full
layers and of at most ``sliding_window`` of them in the window layers;
real prompt tokens, attention over the causal pairs inside the window
in the window layers and over all causal pairs in the full ones.
Padding, columns fetched and masked (a block's other columns, a ring's
columns behind the window), keys and values streamed again for every
query block and head of an admission piece, and experts fetched for
nobody count against the program, so a share can only read under
100%."""

from __future__ import annotations


def expert_params(dims: dict) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * dims["hidden_size"] * dims["intermediate_size"]


def attention_params(dims: dict) -> int:
    d, dh = dims["hidden_size"], dims["head_dim"]
    h, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    return 2 * d * h * dh + 2 * d * hkv * dh


def layer_counts(dims: dict) -> tuple[int, int]:
    """(window layers, full layers) among those run."""
    kinds = dims["layer_types"][:dims["num_hidden_layers"]]
    full = kinds.count("full_attention")
    return len(kinds) - full, full


def position_bytes(dims: dict, bytes_per_value: float) -> float:
    """One cached position of one sequence in ONE layer: keys and
    values of every key/value head."""
    return 2.0 * dims["num_key_value_heads"] * dims["head_dim"] \
        * bytes_per_value


def fixed_decode_bytes(dims: dict) -> float:
    """What every decode step reads whatever its tokens choose: the
    attention matrices and the shared experts at one byte a parameter,
    the routers (their full published width) at four, the tied matrix
    (this chip's rows) at two as the head. The embedding is a gather."""
    d = dims["hidden_size"]
    layers = dims["num_hidden_layers"]
    return (layers * (attention_params(dims)
                      + dims["num_shared_experts"] * expert_params(dims)
                      + 4.0 * d * dims["held"]["router_experts"])
            + 2.0 * d * dims["vocab_size"])


def decode_bytes(dims: dict, steps: int, experts_touched: int,
                 live_positions: float, window_positions: float,
                 state_bytes_per_value: float, part: str = "all"
                 ) -> float:
    """A decode dispatch of ``steps`` steps: the fixed bytes a step,
    ``experts_touched`` held experts (summed over layers and steps, as
    the program counts them) and the keys and values of the positions
    its tokens attend to, summed over the decoding sequences and the
    steps as the program counts them: ``live_positions`` in each full
    layer (a sequence's whole length), ``window_positions`` in each
    window layer (at most ``sliding_window`` of it). ``part``
    ``"experts"``, ``"window"`` or ``"full"`` counts that term
    alone."""
    n_window, n_full = layer_counts(dims)
    row = position_bytes(dims, state_bytes_per_value)
    terms = {"experts": float(experts_touched) * expert_params(dims),
             "window": n_window * window_positions * row,
             "full": n_full * live_positions * row}
    if part != "all":
        return terms[part]
    return steps * fixed_decode_bytes(dims) + sum(terms.values())


def prefill_flops(dims: dict, tokens: int, full_pairs: int,
                  window_pairs: int, expert_rows: int, last_rows: int
                  ) -> float:
    """An admission wave: 2 FLOPs per parameter a real token passes
    (attention's four matrices, the shared experts and the router; the
    held experts by the ``expert_rows`` token-expert pairs the program
    counted over all layers), the head on the ``last_rows`` positions
    that yield a token, and attention (QK^T and PV, 2 FLOPs a
    multiply-add, every query head) over the ``window_pairs`` causal
    pairs inside the window in each window layer and the ``full_pairs``
    causal pairs in each full layer."""
    d = dims["hidden_size"]
    n_window, n_full = layer_counts(dims)
    per_token = dims["num_hidden_layers"] * (
        attention_params(dims)
        + dims["num_shared_experts"] * expert_params(dims)
        + d * dims["held"]["router_experts"])
    per_pair = 4.0 * dims["num_attention_heads"] * dims["head_dim"]
    return (2.0 * per_token * tokens
            + 2.0 * expert_params(dims) * expert_rows
            + 2.0 * d * dims["vocab_size"] * last_rows
            + per_pair * (n_window * window_pairs + n_full * full_pairs))
