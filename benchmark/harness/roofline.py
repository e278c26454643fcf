"""Operations and bytes a decoder step needs, from shapes alone, and
the table of peaks. Only what the algorithm needs is counted: real
tokens, live cache lengths, each weight byte once per pass. Padding,
recomputation and re-reads are the program's business and count
against it, so a share can only read under 100%."""

from __future__ import annotations

import json
import pathlib

_PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; "
            f"{_PEAKS.name} lists "
            f"{[k for k in table if not k.startswith('_')]}")
    return table[device_kind]


def matmul_params(dims: dict) -> tuple[int, int]:
    """(parameters of one layer's matrices, parameters of the output
    head). The embedding is a gather and costs no matmul."""
    d, dh = dims["hidden_size"], dims["head_dim"]
    hq, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    f = dims["intermediate_size"]
    layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f
    return layer, d * dims["vocab_size"]


def prefill_flops(dims: dict, prompt_lens) -> float:
    """Forward pass over real prompt tokens: 2 FLOPs per parameter and
    token in every layer, causal attention over each prompt's own
    prefix (QK^T and PV, 2 FLOPs per multiply-add, half the square
    under the causal mask, bounded by the sliding window), and the
    output head on the last position of each prompt only."""
    layer, head = matmul_params(dims)
    n_layers = dims["num_hidden_layers"]
    hq, dh = dims["num_attention_heads"], dims["head_dim"]
    window = dims.get("sliding_window") or 0
    total = 0.0
    for n in prompt_lens:
        total += 2.0 * layer * n_layers * n + 2.0 * head
        pairs = n * (n + 1) / 2
        if window and n > window:
            pairs -= (n - window) * (n - window + 1) / 2
        total += n_layers * 4.0 * hq * dh * pairs
    return total


def decode_bytes(dims: dict, live_lens, weight_bytes_per_param: float,
                 kv_bytes_per_value: float) -> float:
    """Bytes one decode step has to read: every matrix once (the
    output head included), and the keys and values of each live
    sequence's own cache prefix (bounded by the sliding window)."""
    layer, head = matmul_params(dims)
    n_layers = dims["num_hidden_layers"]
    hkv, dh = dims["num_key_value_heads"], dims["head_dim"]
    window = dims.get("sliding_window") or 0
    weights = (layer * n_layers + head) * weight_bytes_per_param
    kv = 0.0
    for n in live_lens:
        n = min(n, window) if window else n
        kv += 2.0 * n_layers * hkv * dh * n * kv_bytes_per_value
    return weights + kv
