"""Pipeline parallelism over the ``pp`` mesh axis (SPMD GPipe).

SURVEY.md §2.3 lists layer-pipeline parallelism as the TPU-native
equivalent of multi-slice scaling: when a model's layer stack exceeds one
slice's HBM, stages hold contiguous layer spans and microbatches stream
through. Built the SPMD way — NOT a per-stage program: every device runs
the SAME jitted program under ``shard_map``; ``lax.axis_index('pp')``
selects the stage's behavior, activations hop stage→stage over ICI via
``ppermute``, and bubble steps compute-and-discard (masking is cheaper
than idling inside one traced program). This is the schedule jax/praxis
use for TPU pipelining, and gradients flow through ``ppermute``
automatically, so the same function trains.

Schedule: M microbatches over P stages take M + P - 1 steps; each step
every stage runs its local L/P layers once. The last stage's outputs are
masked-psum'd back to all devices (cheap at [B, S, D] test scale; a
multi-slice deployment would leave them stage-local for the loss).

Layer weights shard their leading (layer-stack) axis over ``pp`` — the
``layers`` logical axis below. With ``tp_axis`` set, each stage ALSO
tensor-parallelizes its layers Megatron-style inside the shard_map
body: qkv and gate/up are column-parallel (no communication), wo and
w_down are row-parallel, and the two partial products psum over ``tp``
per layer — heads and ffn width divide across the tp ranks, so a
pp×tp mesh holds 1/(pp·tp) of the stack per device.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from copilot_for_consensus_tpu.analysis.contracts import checkable
from copilot_for_consensus_tpu.models import decoder
from copilot_for_consensus_tpu.models.configs import DecoderConfig
from copilot_for_consensus_tpu.parallel.sharding import (
    DEFAULT_RULES,
    shard_pytree,
)

PIPELINE_RULES = dict(DEFAULT_RULES, layers="pp")


def pipeline_logical_axes(cfg: DecoderConfig) -> Any:
    """decoder.logical_axes with the layer-stack axis named ``layers`` so
    it shards over pp (the serving tables leave it None = replicated)."""
    axes = decoder.logical_axes(cfg)
    axes["layers"] = {
        k: ("layers",) + tuple(v[1:]) for k, v in axes["layers"].items()
    }
    return axes


def shard_params_for_pipeline(params: Any, cfg: DecoderConfig,
                              mesh: Mesh) -> Any:
    return shard_pytree(params, pipeline_logical_axes(cfg), mesh,
                        PIPELINE_RULES)


def _block_tp(x, layer, cfg_local, lengths, impl, tp_axis):
    """One transformer block with its heads/ffn width SPLIT over
    ``tp_axis`` (the leaves in ``layer`` are already the local shards).
    The wo and w_down products are partial sums — ``decoder.block``'s
    ``reduce`` hook psums each (the standard column→row Megatron
    schedule: two collectives per layer), so the block body itself
    stays single-sourced in decoder.py."""
    return decoder.block(x, layer, cfg_local, lengths, impl,
                         reduce=lambda t: jax.lax.psum(t, tp_axis))


def _pp_shard(layers_local, x_mb, lengths, *, axis, cfg, impl,
              tp_axis=None):
    """Per-device body. layers_local: this stage's layer span (leading dim
    L/P; head/ffn axes further split over ``tp_axis`` when set);
    x_mb: [M, mb, S, D] microbatched embeddings (replicated);
    lengths: [M, mb] (replicated)."""
    pp = jax.lax.psum(1, axis)
    stage = jax.lax.axis_index(axis)
    m = x_mb.shape[0]
    steps = m + pp - 1
    perm = [(i, i + 1) for i in range(pp - 1)]       # no wraparound

    vary = lambda t: jax.lax.pcast(
        t, (axis,), to="varying")  # noqa: E731

    if tp_axis is not None:
        import dataclasses

        tp = jax.lax.psum(1, tp_axis)
        cfg_local = dataclasses.replace(
            cfg, n_heads=cfg.n_heads // tp,
            n_kv_heads=cfg.n_kv_heads // tp, d_ff=cfg.d_ff // tp,
            head_dim_override=cfg.head_dim)

        def run_stage(x, mb_lengths):
            def body(x, layer):
                return _block_tp(x, layer, cfg_local, mb_lengths, impl,
                                 tp_axis), None
            x, _ = jax.lax.scan(body, x, layers_local)
            return x
    else:
        def run_stage(x, mb_lengths):
            def body(x, layer):
                return decoder.block(x, layer, cfg, mb_lengths, impl), None
            x, _ = jax.lax.scan(body, x, layers_local)
            return x

    def body(t, carry):
        recv, out_buf = carry
        # Stage 0 pulls the next microbatch from the queue; later stages
        # consume what the previous stage sent last step.
        mb_idx = jnp.clip(t, 0, m - 1)
        inp = jnp.where(stage == 0, vary(x_mb)[mb_idx], recv)
        mb_lengths = vary(lengths)[jnp.clip(t - stage, 0, m - 1)]
        y = run_stage(inp, mb_lengths)
        # The last stage finished microbatch t-(pp-1) this step.
        w = t - (pp - 1)
        valid = (stage == pp - 1) & (w >= 0)
        upd = jax.lax.dynamic_update_slice_in_dim(
            out_buf, y[None], jnp.clip(w, 0, m - 1), axis=0)
        out_buf = jnp.where(valid, upd, out_buf)
        recv = jax.lax.ppermute(y, axis, perm)
        return recv, out_buf

    recv0 = vary(jnp.zeros(x_mb.shape[1:], x_mb.dtype))
    out0 = vary(jnp.zeros_like(x_mb))
    _, out_buf = jax.lax.fori_loop(0, steps, body, (recv0, out0))
    # Only the last stage's buffer is real; psum broadcasts it.
    return jax.lax.psum(
        jnp.where(stage == pp - 1, out_buf, jnp.zeros_like(out_buf)),
        axis)


#: which axis of each layer leaf splits over tp (column-parallel out
#: axes for qkv/gate/up, row-parallel in axes for wo/down); norms stay
#: replicated.
_TP_LEAF_AXIS = {"wq": 2, "wk": 2, "wv": 2, "w_gate": 2, "w_up": 2,
                 "wo": 1, "w_down": 1}


def pipeline_forward(params: Any, tokens: jax.Array, cfg: DecoderConfig,
                     mesh: Mesh, *, n_microbatches: int,
                     lengths: jax.Array | None = None,
                     axis: str = "pp", tp_axis: str | None = None,
                     attn_impl: str = "auto") -> jax.Array:
    """[B, S] tokens → [B, S, V] fp32 logits with the layer stack
    pipelined over ``axis`` and (optionally) each stage's heads/ffn
    width tensor-parallel over ``tp_axis``. Embed/unembed run
    replicated outside the pipeline (they are one matmul each; the
    stack dominates)."""
    b, s = tokens.shape
    m = n_microbatches
    if b % m:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    if cfg.n_layers % mesh.shape[axis]:
        raise ValueError(
            f"{cfg.n_layers} layers not divisible by {axis}="
            f"{mesh.shape[axis]} stages")
    if tp_axis is not None:
        tp = mesh.shape[tp_axis]
        if cfg.is_moe:
            raise ValueError("intra-stage tp does not cover MoE layers")
        for dim, nm in ((cfg.n_heads, "n_heads"),
                        (cfg.n_kv_heads, "n_kv_heads"),
                        (cfg.d_ff, "d_ff")):
            if dim % tp:
                raise ValueError(f"{nm}={dim} not divisible by "
                                 f"{tp_axis}={tp}")
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)

    x = params["tok_emb"][tokens]                     # [B, S, D]
    x_mb = x.reshape(m, b // m, s, x.shape[-1])
    len_mb = lengths.reshape(m, b // m)

    def leaf_spec(path, leaf):
        name = path[-1].key
        dims = [axis] + [None] * (leaf.ndim - 1)
        if tp_axis is not None and name in _TP_LEAF_AXIS:
            dims[_TP_LEAF_AXIS[name]] = tp_axis
        return P(*dims)

    layer_specs = jax.tree_util.tree_map_with_path(
        leaf_spec, params["layers"])
    fn = shard_map(
        functools.partial(_pp_shard, axis=axis, cfg=cfg, impl=attn_impl,
                          tp_axis=tp_axis),
        mesh=mesh,
        in_specs=(layer_specs, P(), P()),
        out_specs=P(),
    )
    y = fn(params["layers"], x_mb, len_mb)
    y = y.reshape(b, s, -1)
    return decoder._unembed(y, params, cfg)


def pipeline_greedy_decode(params: Any, prompt: jax.Array,
                           cfg: DecoderConfig, mesh: Mesh, *,
                           n_new_tokens: int, n_microbatches: int = 1,
                           axis: str = "pp", tp_axis: str | None = None,
                           attn_impl: str = "auto") -> jax.Array:
    """Greedy decode THROUGH the pp(×tp) pipeline: each step re-runs the
    pipelined forward over the grown sequence and appends the argmax
    token. prompt: [B, S] → returns [B, n_new_tokens].

    This is the prefill-style serving path for the pipelined stack
    (batch scoring / short generations where the layer stack doesn't
    fit one slice); a KV-cached windowed pp decode is the long-form
    follow-up. The sequence buffer is padded once so every step runs
    the SAME program shape (one compile), with ``lengths`` masking the
    not-yet-generated tail."""
    b, s0 = prompt.shape
    buf = jnp.concatenate(
        [prompt, jnp.zeros((b, n_new_tokens), prompt.dtype)], axis=1)

    def step(carry, _):
        buf, n = carry
        lengths = jnp.full((b,), n, jnp.int32)
        logits = pipeline_forward(
            params, buf, cfg, mesh, n_microbatches=n_microbatches,
            lengths=lengths, axis=axis, tp_axis=tp_axis,
            attn_impl=attn_impl)
        # argmax at each row's last valid position
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        nxt = jnp.argmax(last, axis=-1).astype(buf.dtype)
        buf = jax.vmap(
            lambda row, pos, tok: jax.lax.dynamic_update_slice(
                row, tok[None], (pos,)))(buf, lengths, nxt)
        return (buf, n + 1), nxt

    (_, _), toks = jax.lax.scan(step, (buf, jnp.int32(s0)),
                                None, length=n_new_tokens)
    return toks.T                                     # [B, n_new]


def make_pipeline_train_step(cfg: DecoderConfig, optimizer, mesh: Mesh,
                             *, n_microbatches: int,
                             attn_impl: str = "xla"):
    """Training step with the layer stack pipelined — the pp counterpart
    of ``train.make_train_step`` (which supplies the loss and optimizer
    wiring; only the forward pass is swapped). Gradients flow through
    ppermute; jit it with params sharded by
    ``shard_params_for_pipeline``. Defaults to XLA attention: the Pallas
    flash kernel is forward-only (no JVP), see train.py."""
    from copilot_for_consensus_tpu import train

    def fwd(params, tokens, cfg, lengths=None, attn_impl=attn_impl):
        return pipeline_forward(params, tokens, cfg, mesh,
                                n_microbatches=n_microbatches,
                                lengths=lengths, attn_impl=attn_impl)

    return train.make_train_step(cfg, optimizer, attn_impl=attn_impl,
                                 forward_fn=fwd)


# ---------------------------------------------------------------------------
# shardcheck contracts (analysis/shardcheck.py)
# ---------------------------------------------------------------------------


@checkable("pipeline-forward")
def _shardcheck_pipeline_forward():
    """Trace the SPMD pipeline under a real pp(×tp) mesh: the
    axis_index / ppermute / psum collectives in ``_pp_shard`` (and the
    per-layer tp psums of ``_block_tp``) must bind axes the mesh has,
    and the PIPELINE_RULES layer-stack sharding must divide the layer
    leaves evenly. Param shapes come from eval_shape — nothing is
    allocated."""
    from copilot_for_consensus_tpu.analysis.contracts import (
        ContractCase,
        require_devices,
    )
    from copilot_for_consensus_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    require_devices(8)
    cfg = DecoderConfig(name="shardcheck-tiny", vocab_size=64,
                        d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
                        d_ff=64, max_seq_len=64)
    params = jax.eval_shape(
        lambda key: decoder.init_params(key, cfg), jax.random.PRNGKey(0))
    # pp2×tp2 (dp auto-fills to 2): layers 4 / pp 2, heads 4 & kv 2 &
    # ffn 64 / tp 2 — the divisibilities pipeline_forward relies on.
    mesh = build_mesh(MeshConfig(dp=0, pp=2, tp=2),
                      devices=jax.devices()[:8])
    tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    return [
        ContractCase(
            label="pp-only", mesh=mesh, rules=PIPELINE_RULES,
            logical=(("pipeline-params", params,
                      pipeline_logical_axes(cfg)),),
            fn=lambda p, t: pipeline_forward(
                p, t, cfg, mesh, n_microbatches=2, attn_impl="xla"),
            args=(params, tokens),
        ),
        ContractCase(
            label="pp-x-tp", mesh=mesh,
            fn=lambda p, t: pipeline_forward(
                p, t, cfg, mesh, n_microbatches=2, tp_axis="tp",
                attn_impl="xla"),
            args=(params, tokens),
        ),
    ]
