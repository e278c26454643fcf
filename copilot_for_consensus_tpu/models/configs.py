"""Model configuration registry.

What the registry holds: the published shapes the package was first
sized for (a MiniLM-class encoder for the embedding service;
Mistral-7B-class and Llama-3-8B-class dense decoders for summarization
and RAG Q&A; a Mixtral-8x7B-class MoE decoder, training dispatch only),
and a ``tiny-*`` preset for every kind of decoder the serving engine
runs, each the full code path at test sizes: dense (``tiny``,
``tiny-swa``), EVA (``tiny-eva``), latent attention with dropless
experts (``tiny-xing``, ``tiny-glm``), window and global layers mixed
(``tiny-mixed``), capacity-dropping experts (``tiny-moe``). The served
benchmark configurations are built from their own files
(``benchmark/configs/*.json``) by ``benchmark/builders/``, not from
here.
"""

from __future__ import annotations

from dataclasses import dataclass, replace


@dataclass(frozen=True)
class DecoderConfig:
    name: str = "decoder"
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 10000.0
    max_seq_len: int = 32768
    sliding_window: int = 0          # 0 = full causal attention
    norm_eps: float = 1e-5
    # MoE (0 experts = dense FFN)
    n_experts: int = 0
    experts_per_token: int = 2
    expert_capacity_factor: float = 1.25
    tie_embeddings: bool = False
    #: explicit per-head width; 0 derives d_model // n_heads. Needed by
    #: tensor-parallel stage-local views, where n_heads is divided by tp
    #: but each head keeps its full width.
    head_dim_override: int = 0
    #: attention class. "softmax": causal attention over one column of
    #: keys and values per position. "eva" (EvaByte's ``attention_class``;
    #: models/eva.py): exact attention inside an aligned window of
    #: ``window_size`` positions, every earlier window seen through one
    #: summary key and value per ``chunk_size`` positions, one softmax.
    attention: str = "softmax"
    window_size: int = 0
    chunk_size: int = 0
    #: prediction heads of ``vocab_size`` logits each on the output
    #: matrix; head i predicts the token i + 1 positions ahead
    num_pred_heads: int = 1
    #: RMS norm gains are stored as offsets from one: x̂ · (1 + g)
    norm_unit_offset: bool = False
    # attention == "mla" (models/xing.py), under the published keys'
    # names: queries through a ``q_lora_rank`` bottleneck; per position
    # ONE latent row of ``kv_lora_rank`` values plus one rotary key of
    # ``qk_rope_head_dim``, shared by all heads, from which every head's
    # ``qk_nope_head_dim`` key and ``v_head_dim`` value are expanded
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: YaRN, as (key, value) pairs of the published ``rope_scaling``
    rope_scaling: tuple = ()
    #: dropless sparse experts beside shared ones (sigmoid scores,
    #: ``noaux_tc`` choice of ``experts_per_token``) after
    #: ``first_k_dense_replace`` dense layers of width ``d_ff``
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    #: residual streams (manifold-constrained hyper-connections), the
    #: Sinkhorn rounds of their mixing matrix, its denominators'
    #: epsilon and the clamp on its logits
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    #: learned sparse attention over the latent cache (DSA,
    #: ``glm_moe_dsa`` / DeepSeek-V3.2; ``index_topk`` 0 = every query
    #: attends to every cached position): ``index_n_heads`` index
    #: queries of ``index_head_dim`` score ONE index key a position (a
    #: second cached row), and a query attends to the ``index_topk``
    #: positions of largest score alone
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    #: ``(first, count)``: the routed experts this device holds of
    #: ``n_routed_experts`` (the router keeps its width and chooses
    #: over all; only the held ones' terms are computed). () = all
    held_experts: tuple = ()
    # attention == "mixed" (models/mixed.py): layers of two KINDS in
    # one model, ``layer_period`` to a period, of which member
    # ``global_member`` (counted from 0) attends to every earlier
    # position and the others to the last ``sliding_window``; each kind
    # keeps a cache of its own (a ring beside a full extent). The rest
    # of what such a layer needs, as data: whether a kind's queries and
    # keys are rotated at all (interleaved pairs), the norm
    # (``"rms"``, or ``"layer"``: mean-centred, a gain, no bias), one
    # norm feeding attention and experts side by side
    # (``parallel_block``), how ``n_shared_experts`` outputs combine
    # (``"sum"`` | ``"average"``), and the scale of the logits of a
    # tied head (``tie_embeddings``)
    layer_period: int = 0
    global_member: int = 0
    window_rope: bool = True
    global_rope: bool = True
    norm_kind: str = "rms"
    parallel_block: bool = False
    shared_expert_combine: str = "sum"
    logit_scale: float = 1.0

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def is_mla(self) -> bool:
        return self.attention == "mla"

    @property
    def selects(self) -> bool:
        """Does attention read a learned selection of the cached
        positions (and the cache hold an index key beside each latent
        row)?"""
        return self.is_mla and self.index_topk > 0

    @property
    def is_mixed(self) -> bool:
        return self.attention == "mixed"

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_eva(self) -> bool:
        return self.attention == "eva"


@dataclass(frozen=True)
class EncoderConfig:
    name: str = "encoder"
    vocab_size: int = 30522
    d_model: int = 384
    n_layers: int = 6
    n_heads: int = 12
    d_ff: int = 1536
    max_positions: int = 512
    norm_eps: float = 1e-12

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


DECODER_CONFIGS: dict[str, DecoderConfig] = {
    # Mistral-7B class (BASELINE config 2): GQA 32/8, SWA 4096.
    "mistral-7b": DecoderConfig(
        name="mistral-7b", vocab_size=32000, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, rope_theta=1e6,
        max_seq_len=32768, sliding_window=4096,
    ),
    # Llama-3-8B class (BASELINE config 3): bigger vocab, theta 5e5.
    "llama-3-8b": DecoderConfig(
        name="llama-3-8b", vocab_size=128256, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, rope_theta=5e5,
        max_seq_len=8192,
    ),
    # Mixtral-8x7B class (BASELINE config 5): 8 experts, top-2.
    "mixtral-8x7b": DecoderConfig(
        name="mixtral-8x7b", vocab_size=32000, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, rope_theta=1e6,
        max_seq_len=32768, n_experts=8, experts_per_token=2,
    ),
    # Test-scale models: same code path, minutes-not-hours compile.
    "tiny": DecoderConfig(
        name="tiny", vocab_size=512, d_model=128, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=256, max_seq_len=512, sliding_window=0,
    ),
    "tiny-swa": DecoderConfig(
        name="tiny-swa", vocab_size=512, d_model=128, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=256, max_seq_len=512, sliding_window=64,
    ),
    # EvaByte class at test scale: four heads of 16, windows of 32
    # positions summarized in chunks of 4, eight prediction heads.
    "tiny-eva": DecoderConfig(
        name="tiny-eva", vocab_size=320, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=128, rope_theta=1e5, max_seq_len=512,
        attention="eva", window_size=32, chunk_size=4, num_pred_heads=8,
        norm_unit_offset=True,
    ),
    # Xing4.0 class at test scale: a latent cache of 32 + 8 values a
    # position, four residual streams, one dense layer and two layers
    # of 8 experts (2 a token) beside a shared one.
    "tiny-xing": DecoderConfig(
        name="tiny-xing", vocab_size=512, d_model=64, n_layers=3,
        n_heads=4, n_kv_heads=4, d_ff=160, rope_theta=1e4,
        max_seq_len=512, norm_eps=1e-6, attention="mla", q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, n_shared_experts=1,
        experts_per_token=2, moe_intermediate_size=32,
        first_k_dense_replace=1, routed_scaling_factor=2.0, hc_mult=4,
        rope_scaling=(("type", "yarn"), ("factor", 8.0),
                      ("original_max_position_embeddings", 64),
                      ("beta_fast", 32.0), ("beta_slow", 1.0),
                      ("mscale", 1.0), ("mscale_all_dim", 1.0)),
    ),
    # GLM-5 class (``glm_moe_dsa``) at test scale: a latent cache of
    # 32 + 8 values a position and an index key of 16 beside it, 2
    # index heads choosing 24 positions (well under the test lengths,
    # so selection is real), ONE residual stream, plain RoPE, one dense
    # layer and two layers of 8 experts (2 a token) beside a shared one.
    "tiny-glm": DecoderConfig(
        name="tiny-glm", vocab_size=512, d_model=64, n_layers=3,
        n_heads=4, n_kv_heads=4, d_ff=160, rope_theta=1e4,
        max_seq_len=512, norm_eps=1e-5, attention="mla", q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, n_routed_experts=8, n_shared_experts=1,
        experts_per_token=2, moe_intermediate_size=32,
        first_k_dense_replace=1, routed_scaling_factor=2.5, hc_mult=1,
        index_n_heads=2, index_head_dim=16, index_topk=24,
    ),
    # Window and global layers mixed (``cohere2_moe`` class) at test
    # scale: two periods of three window layers (16 positions, rotary)
    # and one global layer (no positional encoding), 8 query heads on 2
    # key/value heads, LayerNorm, attention and experts side by side on
    # one norm, 8 experts (2 a token) beside 2 shared ones whose
    # outputs are averaged, a tied head.
    "tiny-mixed": DecoderConfig(
        name="tiny-mixed", vocab_size=512, d_model=64, n_layers=8,
        n_heads=8, n_kv_heads=2, d_ff=32, rope_theta=5e4,
        max_seq_len=512, sliding_window=16, norm_eps=1e-5,
        head_dim_override=16, attention="mixed", layer_period=4,
        global_member=3, window_rope=True, global_rope=False,
        norm_kind="layer", parallel_block=True, n_routed_experts=8,
        n_shared_experts=2, shared_expert_combine="average",
        experts_per_token=2, moe_intermediate_size=32,
        tie_embeddings=True, logit_scale=1.0,
    ),
    "tiny-moe": DecoderConfig(
        name="tiny-moe", vocab_size=512, d_model=128, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=256, max_seq_len=512, n_experts=4,
        experts_per_token=2,
    ),
}

ENCODER_CONFIGS: dict[str, EncoderConfig] = {
    # all-MiniLM-L6-v2 class — the reference's default embedder
    # (sentence_transformer_provider.py:19), dim 384.
    "minilm-l6": EncoderConfig(
        name="minilm-l6", vocab_size=30522, d_model=384, n_layers=6,
        n_heads=12, d_ff=1536, max_positions=512,
    ),
    "tiny": EncoderConfig(
        name="tiny", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        d_ff=128, max_positions=128,
    ),
}


def decoder_config(name: str, **overrides) -> DecoderConfig:
    cfg = DECODER_CONFIGS[name]
    return replace(cfg, **overrides) if overrides else cfg


def encoder_config(name: str, **overrides) -> EncoderConfig:
    cfg = ENCODER_CONFIGS[name]
    return replace(cfg, **overrides) if overrides else cfg
