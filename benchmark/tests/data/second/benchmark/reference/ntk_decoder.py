"""Plain reference of the test-only second configuration: pre-norm
blocks with RMS norm, grouped-query FULL causal attention (no window)
whose rotary base is the published one scaled by
``rope_scaling.factor`` ("NTK-aware": ``theta * factor ** (d / (d -
2))``), SwiGLU, untied head. float32 at ``highest`` precision, one
sequence, eager, no cache; it imports nothing of the program and
nothing of ``reference/decoder.py``. It reads the benchmark's seeded
int8 weights (``harness/weights.py``). ``lower="int4"`` re-quantizes
every matrix to 4 bits: the control."""

import jax
import jax.numpy as jnp
import numpy as np

LOWERS = ("int4",)


def padded_len(n: int) -> int:
    return n                # eager: no compiled length to share


def _matrix(leaf, lower, layer=None):
    q, scale = leaf["q"], leaf["scale"]
    if layer is not None:
        q, scale = q[layer], scale[layer]
    q, scale = q.astype(jnp.float32), scale.astype(jnp.float32)
    if lower == "int4":
        q, scale = jnp.clip(jnp.round(q * 7 / 127), -7, 7), scale * 127 / 7
    return q * scale


def _norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rotate(x, base):
    """x [S, H, D]: pairs (i, i + D/2) turned by position * base^(-2i/D)."""
    s, _, d = x.shape
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] / base ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def logits_at(weights, dims, tokens, positions, lower=None, pad_to=0):
    hq, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    dh, eps = dims["head_dim"], dims["rms_norm_eps"]
    if lower not in (None,) + LOWERS:
        raise ValueError(f"no control {lower!r}")
    base = dims["rope_theta"] * dims["rope_scaling"]["factor"] ** (
        dh / (dh - 2))
    n = len(tokens)
    causal = jnp.tril(jnp.ones((n, n), bool))
    layers = weights["layers"]
    with jax.default_matmul_precision("highest"):
        x = weights["tok_emb"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        for li in range(dims["num_hidden_layers"]):
            w = lambda name: _matrix(layers[name], lower, li)  # noqa: E731
            h = _norm(x, layers["attn_norm"][li], eps)
            q = _rotate((h @ w("wq")).reshape(n, hq, dh), base)
            k = _rotate((h @ w("wk")).reshape(n, hkv, dh), base)
            v = (h @ w("wv")).reshape(n, hkv, dh)
            k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
            score = jnp.einsum("shd,thd->hst", q, k) / dh ** 0.5
            score = jnp.where(causal[None], score, -jnp.inf)
            mixed = jnp.einsum("hst,thd->shd", jax.nn.softmax(score, -1), v)
            x = x + mixed.reshape(n, hq * dh) @ w("wo")
            h = _norm(x, layers["ffn_norm"][li], eps)
            x = x + (jax.nn.silu(h @ w("w_gate")) * (h @ w("w_up"))) \
                @ w("w_down")
        rows = _norm(x[jnp.asarray(positions, jnp.int32)],
                     weights["final_norm"], eps)
        return np.asarray(rows @ _matrix(weights["lm_head"], lower))
