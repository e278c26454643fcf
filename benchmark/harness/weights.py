"""Seeded decoder weights, made on the device in one jitted call, in the
types they are served in (int8 matrices with a float32 scale per output
channel, bfloat16 embeddings and norms).

The layout is the program's checkpoint layout for a quantized decoder
(``tok_emb``, ``layers.{attn_norm,wq,wk,wv,wo,ffn_norm,w_gate,w_up,
w_down}``, ``final_norm``, ``lm_head``; a quantized leaf is
``{"q": int8, "scale": float32}`` with the scale broadcast over the
contraction axis). The benchmark makes them, hands the same arrays to
the system under test and to the plain reference; nothing the program
made is compared with itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds above 2**31 included)."""
    seed = int(seed)
    # "rbg": the generator the chip has in hardware. Seven billion
    # int8 draws cost seconds less of every run's set-up than with the
    # default threefry, and the same seed gives the same weights.
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def decoder_weights(dims: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    n, d = dims["num_hidden_layers"], dims["hidden_size"]
    dh = dims["head_dim"]
    hq, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    f, v = dims["intermediate_size"], dims["vocab_size"]
    mats = {"wq": (n, d, hq * dh), "wk": (n, d, hkv * dh),
            "wv": (n, d, hkv * dh), "wo": (n, hq * dh, d),
            "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}

    def quantized(key, shape):
        rows, cols = shape[-2:]
        # uniform int8 has std ~73.3; a per-channel scale around
        # 1/sqrt(fan_in) that differs from channel to channel, so a
        # scale applied to the wrong axis does not pass
        base = rows ** -0.5 / 73.3

        def one(k):
            kq, ks = jax.random.split(k)
            # four int8 out of every 32 random bits: the generator
            # makes words, and a byte each would hold four times the
            # scratch (14 GB for this model)
            words = jax.random.bits(kq, (rows, cols // 4), jnp.uint32)
            q = jax.lax.bitcast_convert_type(words, jnp.int8)
            q = jnp.maximum(q.reshape(rows, cols), -127)
            scale = base * jax.random.uniform(ks, (1, cols), jnp.float32,
                                              0.5, 1.5)
            return {"q": q, "scale": scale}

        if len(shape) == 2:
            return one(key)
        # layer by layer, so the scratch is one layer's
        return jax.lax.map(one, jax.random.split(key, shape[0]))

    def norm(key, shape):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def build(key):
        keys = iter(jax.random.split(key, 16))
        layers = {name: quantized(next(keys), shape)
                  for name, shape in mats.items()}
        layers["attn_norm"] = norm(next(keys), (n, d))
        layers["ffn_norm"] = norm(next(keys), (n, d))
        emb = (jax.random.truncated_normal(next(keys), -2, 2, (v, d),
                                           jnp.float32)
               * d ** -0.5).astype(dtype)
        return {"tok_emb": emb, "layers": layers,
                "final_norm": norm(next(keys), (d,)),
                "lm_head": quantized(next(keys), (d, v))}

    return jax.jit(build)(seed_key(seed))
