"""Bytes a decode step of an EVA-attention decoder (EvaByte class) has
to read, from shapes alone: every matrix once, and each live
sequence's own state — the exact keys and values of its open window
and the chunk summaries of the windows before it. Only what the
algorithm needs is counted: columns of a window buffer or of a summary
store that hold nothing live, which the program reads and masks, count
against the program, so a share can only read under 100%."""

from __future__ import annotations


def weight_params(dims: dict) -> int:
    """Parameters of the matrices one decode step multiplies by: four
    square projections and the SwiGLU's three in every layer, and the
    output matrix of all prediction heads. The embedding is a gather;
    ``mu`` and ``phi`` are read only when a window is compacted."""
    d, f = dims["hidden_size"], dims["intermediate_size"]
    hd = dims["num_attention_heads"] * dims["head_dim"]
    layer = 2 * d * hd + 2 * d * dims["num_key_value_heads"] \
        * dims["head_dim"] + 3 * d * f
    head = d * dims["vocab_size"] * dims["num_pred_heads"]
    return layer * dims["num_hidden_layers"] + head


def state_bytes_per_column(dims: dict, bytes_per_value: float) -> float:
    """One exact position, or one chunk summary, of one sequence: a key
    and a value per head and layer."""
    return (2.0 * dims["num_hidden_layers"] * dims["num_key_value_heads"]
            * dims["head_dim"] * bytes_per_value)


def decode_bytes(dims: dict, live_columns: float,
                 weight_bytes_per_param: float,
                 state_bytes_per_value: float) -> float:
    """One decode step: the weights once (``weight_bytes_per_param`` 0
    leaves them out) and ``live_columns`` of state, window columns and
    summaries of all sequences together."""
    return (weight_params(dims) * weight_bytes_per_param
            + live_columns * state_bytes_per_column(
                dims, state_bytes_per_value))
