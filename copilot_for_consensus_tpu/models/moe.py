"""Mixture-of-experts FFN (Mixtral-class: softmax top-2 routing).

GShard/Switch-style capacity-based dispatch: tokens are routed to experts
through dense one-hot dispatch/combine einsums, which XLA turns into MXU
matmuls and — when the expert axis is sharded over the ``ep`` mesh axis —
into all-to-all collectives over ICI. No data-dependent shapes, so the
whole layer stays jit-compatible (static capacity; overflow tokens drop,
standard for capacity-factor routing).

This is the TRAINING dispatch. Serving does not go through it: a token
over capacity is dropped, so a request's output would depend on its
batch-mates, which breaks the greedy bit-identity that replay, the
journal and the benchmark's ``correct`` rest on. The serving path's
experts are ``models/xing.py:routed_experts`` (dropless: token-expert
pairs sorted by expert, grouped matmuls over the experts held); the
two share ``_q_einsum``'s convention of an int8 scale per expert and
output channel, applied after the contraction.

The reference has no MoE anywhere (SURVEY.md §2.3 — Mixtral-8x7B appears
only as a BASELINE.json target config); this is new TPU-first capability.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.models.configs import DecoderConfig


def _q_einsum(spec: str, x: jax.Array, w, prefer_f32: bool = False
              ) -> jax.Array:
    """Expert einsum with transparent weight dequantization. int8 scales
    are per output channel, so they apply after the contraction; int4's
    group-wise scales do not commute with an einsum contraction, so the
    int4 path materializes the dequantized expert weight (experts are
    small relative to the dense stack). ``prefer_f32`` keeps fp32
    accumulation on the full-precision path."""
    from copilot_for_consensus_tpu.models.quant import (
        dequant_int4,
        quant_kind,
    )

    kind = quant_kind(w)
    if kind == "int4":
        return jnp.einsum(spec, x, dequant_int4(w, x.dtype))
    if kind == "int8":
        return (jnp.einsum(spec, x, w["q"].astype(x.dtype))
                * w["scale"].astype(x.dtype))
    if prefer_f32:
        return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, x, w)


def moe_capacity(n_tokens: int, cfg: DecoderConfig) -> int:
    cap = int(cfg.expert_capacity_factor * n_tokens
              * cfg.experts_per_token / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)         # round up to sublane multiple


def moe_ffn(x: jax.Array, layer: dict, cfg: DecoderConfig) -> jax.Array:
    """x: [B, S, D] → [B, S, D].

    layer: router [D, E]; w_gate/w_up [E, D, F]; w_down [E, F, D].
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = moe_capacity(t, cfg)
    xt = x.reshape(t, d)

    router_logits = (xt @ layer["router"]).astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Position of each (token, choice) inside its expert's capacity buffer.
    # Flatten choices in priority order (choice 0 of all tokens first) so
    # top-1 assignments win capacity over top-2 spillover.
    flat_idx = gate_idx.T.reshape(-1)                            # [k*T]
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)        # [k*T, E]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot - onehot  # 0-based
    pos = jnp.sum(pos_in_expert, axis=-1)                        # [k*T]
    keep = pos < cap

    # dispatch/combine: [T, E, C]
    disp_flat = (
        jax.nn.one_hot(flat_idx, e, dtype=x.dtype)[:, :, None]
        * jax.nn.one_hot(pos, cap, dtype=x.dtype)[:, None, :]
        * keep[:, None, None]
    )                                                            # [k*T, E, C]
    disp = disp_flat.reshape(k, t, e, cap)
    dispatch = jnp.sum(disp, axis=0)                             # [T, E, C]
    combine = jnp.einsum("ktec,kt->tec", disp, gate_vals.T.astype(x.dtype))

    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)          # [E, C, D]
    gate = jax.nn.silu(
        _q_einsum("ecd,edf->ecf", expert_in, layer["w_gate"],
                  prefer_f32=True).astype(jnp.float32))
    up = _q_einsum("ecd,edf->ecf", expert_in, layer["w_up"],
                   prefer_f32=True).astype(jnp.float32)
    h = (gate * up).astype(x.dtype)
    expert_out = _q_einsum("ecf,efd->ecd", h, layer["w_down"])   # [E, C, D]
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.reshape(b, s, d)


def moe_load_balancing_loss(router_logits: jax.Array, gate_idx: jax.Array,
                            n_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: mean fraction routed × mean prob."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], n_experts), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(frac_tokens * frac_probs)
