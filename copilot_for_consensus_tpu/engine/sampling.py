"""Token sampling: greedy / temperature / top-k / nucleus, jit-friendly.

All branches are static (config-time) choices so the decode step compiles
to one fused program; only the PRNG key and logits are traced.

Two entry points share one filtering pipeline:

* :func:`sample` — one token per row (the decode / admission paths).
* :func:`verify_draft` — exact speculative verification of k drafted
  tokens per row against k+1 scored positions (the engine's ``_verify``
  dispatch; see ``docs/SPEC_DECODE.md``). Greedy verification is
  bit-identical to stepwise :func:`sample`; sampled verification uses
  the rejection rule of Leviathan et al. (ICML 2023) specialized to a
  deterministic (prompt-lookup) draft, so the emitted distribution is
  exactly the one :func:`sample` draws from.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.obs.profile import scope


@dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0      # 0 → greedy
    top_k: int = 0                # 0 → disabled
    top_p: float = 1.0            # 1 → disabled


def _filter_logits(logits: jax.Array, cfg: SamplingConfig) -> jax.Array:
    """Temperature scaling + top-k / top-p masking over the last axis.

    The distribution every sampled token is drawn from — shared by
    ``sample`` and ``verify_draft`` so speculative verification scores
    drafts against EXACTLY the serving distribution. Works on any
    leading batch shape ([B, V] decode rows, [B, S, V] verify rows).
    Callers guarantee ``cfg.temperature > 0``.
    """
    logits = logits / cfg.temperature
    if cfg.top_k > 0:
        # top_k beyond the vocab keeps everything (the sort has no
        # ``-top_k``-th element to threshold on — clamping avoids an
        # out-of-range index silently snapping to the minimum).
        k = min(cfg.top_k, logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with cumulative mass ≥ top_p.
        cutoff_idx = jnp.sum(cum < cfg.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[..., None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@scope("sample")
def sample(logits: jax.Array, key: jax.Array,
           cfg: SamplingConfig) -> jax.Array:
    """logits: [B, V] fp32 → [B] int32 token ids."""
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, _filter_logits(logits, cfg), axis=-1).astype(jnp.int32)


@scope("sample")
def verify_draft(logits: jax.Array, draft: jax.Array,
                 draft_lens: jax.Array, key: jax.Array,
                 cfg: SamplingConfig) -> tuple[jax.Array, jax.Array]:
    """Exact acceptance of prompt-lookup drafts over one verify dispatch.

    ``logits``: [B, S, V] raw model logits at the S = k_max+1 scored
    positions — row j is the distribution of the token FOLLOWING fed
    token j (token 0 is the stream's committed next token, tokens
    1..k its draft). ``draft``: [B, S-1] proposed tokens, right-padded;
    ``draft_lens``: [B] valid draft counts per row (0 = the row rides
    the dispatch as a plain single decode step).

    Returns ``(tokens_out [B, S] int32, n_accept [B] int32)``: row b
    emits ``tokens_out[b, :n_accept[b] + 1]`` — the accepted draft
    tokens followed by one model-sampled token (the correction at the
    first rejection, or the free bonus token after a fully accepted
    draft). Columns past that are garbage and must be ignored.

    Greedy (``temperature <= 0``): accept while the argmax matches the
    draft — the emitted tokens are the argmax chain itself, so the
    sequence is bit-identical to stepwise greedy decode. Sampled: the
    standard speculative rejection rule with a point-mass draft
    distribution — accept d with probability p(d) under the FILTERED
    serving distribution p, otherwise resample from p with d removed
    (renormalized) — which leaves the emitted distribution exactly p at
    every position.
    """
    b, s, v = logits.shape
    jpos = jnp.arange(s - 1)[None, :]
    within = jpos < draft_lens[:, None]                    # [B, S-1]
    if cfg.temperature <= 0.0:
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, S]
        ok = (out[:, :-1] == draft) & within
        n_accept = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                           axis=1)
        return out, n_accept.astype(jnp.int32)
    f = _filter_logits(logits, cfg)                        # [B, S, V]
    p = jax.nn.softmax(f, axis=-1)
    k_u, k_res, k_plain = jax.random.split(key, 3)
    p_draft = jnp.take_along_axis(
        p[:, :-1], draft[..., None].astype(jnp.int32), axis=-1)[..., 0]
    u = jax.random.uniform(k_u, (b, s - 1))
    ok = (u < p_draft) & within
    n_accept = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                       axis=1).astype(jnp.int32)
    # Correction draw at a rejection: p with the drafted token removed,
    # renormalized (categorical over the masked logits does both). A
    # p(d)=1 point mass never rejects, so its all -inf row is unused.
    res_logits = jnp.where(
        jnp.arange(v)[None, None, :] == draft[..., None].astype(jnp.int32),
        -jnp.inf, f[:, :-1])
    res = jax.random.categorical(k_res, res_logits,
                                 axis=-1).astype(jnp.int32)   # [B, S-1]
    # Plain draw from p: the bonus token after a fully accepted draft
    # (and what a 0-draft row emits — exactly ``sample``'s draw).
    plain = jax.random.categorical(k_plain, f,
                                   axis=-1).astype(jnp.int32)  # [B, S]
    head = jnp.where(within,
                     jnp.where(ok, draft.astype(jnp.int32), res),
                     plain[:, :-1])
    out = jnp.concatenate([head, plain[:, -1:]], axis=1)
    return out, n_accept
