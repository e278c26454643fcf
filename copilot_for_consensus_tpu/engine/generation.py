"""Continuous-batching LLM generation engine.

The first-party replacement for the blocking single-request HTTP call the
reference makes per summary (``local_llm_summarizer.py:106-115`` — "THE
DOMINANT LATENCY" in SURVEY.md §3.2). Design:

* **Slot batch.** The decode state is a fixed batch of ``num_slots``
  sequences with a shared KV cache ``[L, slots, Hkv, max_len, Dh]``.
  Every decode step advances *all* active slots in one fused program —
  requests join and leave the batch without recompilation (continuous
  batching, the vLLM/Orca scheduling model, built TPU-style with static
  shapes).
* **Prefill/decode disaggregation.** Prompts are prefetched through a
  bucketed prefill (padded to the next bucket so XLA sees a handful of
  shapes), then their kv block is inserted into a free slot; decode is a
  single [slots]-wide matvec-bound step.
* **Sharding.** Params shard over the mesh per ``models.decoder
  .logical_axes`` (tp over heads/ffn/vocab); the cache shards its slot
  axis over dp and kv-head axis over tp. Collectives are emitted by XLA.
* **Prefix KV-cache reuse** (``prefix_cache_blocks`` > 0): a radix trie
  over token-block hashes maps each prompt's longest cached prefix to
  device-resident KV blocks; admission seeds the slot cache from the
  pool and prefills only the suffix, and completions publish their
  prompt-prefix blocks back. Design: ``docs/ENGINE_PREFIX_CACHE.md``.
* **Speculative decoding** (``spec_decode=True``): decode is pinned at
  the HBM weight-read wall (docs/PERF.md r3), so the only way past it
  is more tokens per weight pass. A host-side prompt-lookup n-gram
  index per stream (``tokenizer.NgramDraftIndex``) drafts copied
  spans from the stream's own context for free, and one ``_verify``
  dispatch — a short seeded prefill over the decode slots — scores
  k+1 positions per stream in a single weight pass, accepting exactly
  (greedy bit-identical; sampled via the rejection rule in
  ``sampling.verify_draft``). Design: ``docs/SPEC_DECODE.md``.

* **A second kind of sequence state** (``cfg.attention == "eva"``,
  EvaByte class; ``models/eva.py``): per slot an exact window of
  ``window_size`` positions beside one summary key and value per
  ``chunk_size`` positions of everything before it, in one cache
  manager. Admission goes piece by piece (``_admit_pieces``: a prompt of
  four windows is four waves, the first three leave only summaries),
  decode compacts a window that fills into its summaries on the
  device, mid-dispatch, for exactly the slots concerned. The options
  that assume one column of keys and values per position refuse it at
  construction (``_check_eva``).

* **A third kind of sequence state** (``cfg.attention == "mla"``,
  Xing4.0 and GLM-5 classes; ``models/xing.py``): per position ONE
  latent row shared by all heads, beside dropless sparse experts (all
  of them, or the share ``cfg.held_experts`` names) and, in the one
  class, a multi-stream residual. In the other, attention is SELECTED
  (``cfg.index_topk``): a second kind of per-position state, the
  index key, lies beside the latent row in the cache (written by the
  same admission wave, kept in the same dispatch's window buffer and
  merged with it), and every query reads only the positions its
  indexer scores highest. Admission goes piece by piece through the
  same ``_admit_pieces`` (each piece attends in expanded form to the
  slot's latents, expanded again), decode scores the latents in
  absorbed form, one program whatever the lengths (on a TPU each
  slot's live blocks of them, in place: ``_reads_latent_blocks``);
  the routing's counts come back with the tokens. What assumes a key
  and a value of ``[Hkv, Dh]`` per position, or one weight pass a
  step, refuses it at construction (``_check_mla``), for the index
  keys as for the latent rows.

* **Two kinds of sequence state side by side** (``cfg.attention ==
  "mixed"``, the ``cohere2_moe`` class; ``models/mixed.py``): layers
  that attend within a sliding window keep a RING of ``sliding_window +
  largest piece`` columns a slot, the layers that attend to everything
  a full extent, one array per kind in the one cache dict; attention
  and the experts (``models/xing.py``'s, shared) hang side by side on
  one norm. Admission goes through the same ``_admit_pieces``, decode
  is one program whatever the lengths (on a TPU each slot's live
  blocks of either cache in place: ``_reads_ring_blocks``). What
  assumes one cache layout for all layers refuses it at construction
  (``_check_mixed``).

The engine is synchronous and single-owner: services drive it through
``submit()`` + ``step()`` (or ``generate()`` for batch use) from their
consumer thread, mirroring how the reference's summarization service owns
its single LLM connection.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from copilot_for_consensus_tpu.analysis.contracts import (
    ContractCase,
    HloSpec,
    checkable,
)
from copilot_for_consensus_tpu.engine.faults import (
    InjectedFault,
    resolve_faults,
)
from copilot_for_consensus_tpu.engine.sampling import (
    SamplingConfig,
    sample,
    verify_draft,
)
from copilot_for_consensus_tpu.engine.scheduler import (
    jain_index,
    resolve_scheduler,
)
from copilot_for_consensus_tpu.engine.journal import resolve_journal
from copilot_for_consensus_tpu.engine.telemetry import resolve_telemetry
from copilot_for_consensus_tpu.engine.tokenizer import (
    NgramDraftIndex,
    Tokenizer,
)
from copilot_for_consensus_tpu.obs.profile import scope, step_annotation
from copilot_for_consensus_tpu.models import (
    decoder,
    eva,
    mixed,
    quant,
    xing,
)
from copilot_for_consensus_tpu.models.configs import DecoderConfig
from copilot_for_consensus_tpu.ops import dense_attention, latent_attention
from copilot_for_consensus_tpu.ops.eva_attention import blocks_read
from copilot_for_consensus_tpu.parallel.sharding import (
    DEFAULT_RULES,
    serving_param_rules,
    shard_pytree,
)

try:  # jax.sharding only needed when a mesh is provided
    from jax.sharding import Mesh
except Exception:  # pragma: no cover
    Mesh = Any  # type: ignore


@dataclass
class Request:
    request_id: int
    prompt: list[int]
    max_new_tokens: int
    decode_started_at: float = 0.0
    #: prefix-cache publish cap: how many LEADING prompt tokens may be
    #: published to the shared block pool on completion (None = whole
    #: prompt, 0 = never publish this request). Lookup/reuse is always
    #: unrestricted — this only bounds what the request contributes.
    cache_eligible_tokens: int | None = None
    #: memoized block digests (PrefixCache.prompt_digests) — the
    #: admission router re-checks every queued request every step, and
    #: hashing is the only per-token host cost on that path
    block_digests: list | None = None
    #: pipeline correlation id, carried end-to-end through the
    #: request's telemetry span and into flight-recorder dumps / error
    #: reports (engine/telemetry.py)
    correlation_id: str = ""
    #: multi-tenant scheduling (engine/scheduler.py): the fairness key
    #: ("" = the anonymous/default tenant) and the priority lane
    #: (interactive > batch; batch sheds first under SLO pressure)
    tenant: str = ""
    priority: str = "interactive"
    #: absolute monotonic deadline (engine/supervisor.py policy):
    #: expired work is DROPPED (finish_reason="deadline"), never
    #: computed — queued requests at step start, active slots at
    #: harvest. inf = no deadline.
    deadline_at: float = float("inf")
    #: where the time after the first token goes, summed as the work
    #: happens and copied to the RequestTrace at retire
    #: (engine/telemetry.py): the dispatches that advanced this
    #: request, and other requests' admission dispatches it sat
    #: through in its slot
    decode_s_own: float = 0.0
    decode_dispatches: int = 0
    stalled_s: float = 0.0


@dataclass
class Completion:
    request_id: int
    prompt_len: int
    tokens: list[int]
    finish_reason: str            # "eos" | "length" | "deadline"
    prefill_s: float = 0.0
    decode_s: float = 0.0


@dataclass
class PrefilledHandoff:
    """One finished prefill exported for the disaggregated KV handoff
    (prefill-role → decode-role). ``kv_k``/``kv_v`` are the slot's
    pool blocks gathered dense ``[L, 1, Hkv, NBpad*block, Dh]`` —
    device arrays, moved device-to-device by the importing engine's
    ``jax.device_put`` onto its own mesh; only the first
    ``prompt_len`` columns are live. The refcount story: the source
    slot's pins/blocks were released at export (after the shard trie
    adopted the prompt prefix), and the importing engine allocates
    fresh blocks whose sole owner is the new slot — ownership moves,
    never aliases."""

    request: Request
    first_token: int
    prompt_len: int
    kv_k: Any
    kv_v: Any
    blocks: int                   # live (un-padded) block count
    ready_at: float               # monotonic: when the prefill parked
    prefill_s: float = 0.0


def _next_bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


_KV_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float32": jnp.float32,
    "float8_e4m3fn": jnp.float8_e4m3fn,
    "float8_e5m2": jnp.float8_e5m2,
}


def resolve_kv_dtype(kv_dtype, default):
    """One place to accept/validate the kv cache dtype (config strings
    included) — a typo'd config key must fail here with the valid set,
    not as an opaque AttributeError deep in init_cache."""
    if not kv_dtype:          # None or "" (the schema default) = unset
        return default
    if isinstance(kv_dtype, str):
        try:
            return _KV_DTYPES[kv_dtype]
        except KeyError:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}; one of "
                f"{sorted(_KV_DTYPES)}") from None
    return kv_dtype


def _host_fetch(x) -> "np.ndarray":
    """Device→host for a program output that may be sharded across
    PROCESSES (multi-controller serving: dp shards the slot axis over
    ranks). ``device_get`` only works on fully-addressable arrays; a
    cross-process shard is all-gathered through the distributed
    runtime so every rank harvests the same full token block — which
    the SPMD lockstep requires anyway (each rank must observe the same
    retirements/admissions)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(jax.device_get(x))


def _selected(first: int, n: int, k: int) -> int:
    """Positions that ``n`` successive tokens read under a selection of
    ``k``, the first of them standing at position ``first``: the sum of
    ``min(k, first + i + 1)`` over ``i < n``."""
    grow = min(max(k - first, 0), n)         # tokens that still read all
    return grow * first + grow * (grow + 1) // 2 + (n - grow) * k


def _expert_counts(counts) -> dict:
    """A dispatch's routing counts (``xing.N_COUNTS``, summed over
    layers and steps on the device) as StepRecord fields."""
    touched, rows, busiest, grouped, tiled = (int(c) for c in counts)
    return {"experts_touched": touched, "expert_rows": rows,
            "expert_rows_max": busiest, "expert_group_rows": grouped,
            "expert_tile_rows": tiled}


class GenerationEngine:
    """Continuous-batching decoder serving. One instance per process/slice."""

    def __init__(
        self,
        cfg: DecoderConfig,
        params: Any | None = None,
        *,
        mesh: "Mesh | None" = None,
        num_slots: int = 8,
        max_len: int = 1024,
        prefill_buckets: tuple[int, ...] = (64, 128, 256, 512, 1024),
        sampling: SamplingConfig = SamplingConfig(),
        eos_id: int = 2,
        seed: int = 0,
        dtype=jnp.bfloat16,
        kv_dtype=None,
        attn_impl: str = "auto",
        quantize: bool | str = False,
        decode_window: int = 8,
        windows_per_dispatch: int = 1,
        admission_token_budget: int = 16384,
        prefill_chunk: int = 64,
        prefix_cache_blocks: int = 0,
        kv_pool_blocks: int = 0,
        kv_kernel: str = "auto",
        role: str = "both",
        handoff_high: int = 0,
        spec_decode: bool = False,
        spec_draft_lens: tuple[int, ...] = (0, 4, 8),
        spec_ngram: int = 3,
        spec_min_ngram: int = 2,
        profile_dir: str | None = None,
        int4_pallas_max_extent: int | None = 1536,
        telemetry: Any = True,
        scheduler: Any = None,
        faults: Any = None,
        journal: Any = None,
    ):
        self.profile_dir = profile_dir
        # Resilience plane (engine/faults.py + engine/supervisor.py;
        # docs/RESILIENCE.md): ``faults`` installs a deterministic
        # seeded fault injector at every host dispatch boundary
        # (``_dispatch_boundary`` — never inside jitted code); a
        # supervisor (attached by EngineSupervisor/AsyncEngineRunner)
        # gets watchdog begin/end + success/failure callbacks from the
        # same boundary, and may lower ``_slot_cap`` (resource breaker)
        # or veto the verify dispatch (spec breaker).
        self.faults = resolve_faults(faults)
        # Durable request journal (engine/journal.py;
        # docs/RESILIENCE.md#process-lifecycle): submits journal before
        # admission, accepted tokens checkpoint incrementally, retire
        # deletes — and a non-empty journal at construction warm-
        # restarts: unfinished requests resubmit as prompt+generated
        # continuations (the PR-7 replay identity: greedy bit-identical
        # at f32), so a serving-process SIGKILL costs latency, not work.
        self.journal = resolve_journal(journal)
        #: journal rows resubmitted at warm restart this process
        self.journal_replayed = 0
        #: journal rows that could NOT be resumed (continuation past
        #: prompt_limit) — honest loss accounting, never silent
        self.journal_abandoned = 0
        #: (new rid, correlation_id) pairs recovered at construction —
        #: callers that want to harvest/publish recovered completions
        #: read this to re-attach identities (the journal storm driver)
        self.journal_recovered: list[tuple[int, str]] = []
        #: rid → (original prompt_len, resumed token prefix): stitched
        #: back onto the continuation's completion at harvest
        self._journal_stitch: dict[int, tuple[int, list[int]]] = {}
        #: rid → generated-token count at last checkpoint (lag gauge)
        self._journal_ckpt: dict[int, int] = {}
        self._journal_steps = 0
        self._journal_recovering = False
        #: True while a resubmission's row is provided by an ATOMIC
        #: journal.supersede re-key instead of record_submit — the
        #: journal must never hold two live rows for one request
        self._journal_suppress = False
        self.supervisor: Any = None
        self._last_failed_kind = ""
        self._slot_cap = num_slots
        #: requests dropped un-computed because deadline_at passed
        self.deadline_expired = 0
        #: set the first time a deadline_s submit arrives — the
        #: per-step expiry sweep walks every queue, so engines that
        #: never see a deadline skip it entirely (hot-path economy)
        self._deadlines_in_use = False
        #: contained prefix-publish failures (completion still
        #: delivered; only the cache contribution was lost)
        self.prefix_publish_failures = 0
        #: (kind, *static shape key) of every program dispatched so
        #: far: what makes a StepRecord's ``first_use``
        self.programs_seen: set[tuple] = set()
        self._phase_span = None       # the open host phase (_phase)
        # Flight recorder + request-lifecycle spans + Prometheus export
        # (engine/telemetry.py). Default ON: pure host-side bookkeeping
        # around dispatches the engine already syncs on (<1% measured —
        # docs/OBSERVABILITY.md). False disables; an EngineTelemetry or
        # MetricsCollector shares a collector across engines/services.
        self.telemetry = resolve_telemetry(telemetry, engine="generation",
                                           num_slots=num_slots)
        self.cfg = cfg
        self.mesh = mesh
        self.num_slots = num_slots
        self.max_len = min(max_len, cfg.max_seq_len)
        self.buckets = tuple(
            b for b in sorted(set(min(b, self.max_len)
                                  for b in prefill_buckets)))
        self.sampling = sampling
        #: EVA attention (models/eva.py): a slot's state is an exact
        #: window beside chunk summaries, admission goes piece by piece
        #: (``_admit_pieces``), decode compacts on the device
        self._eva = cfg.is_eva
        if self._eva:
            self._check_eva(dict(
                mesh=mesh, prefix_cache_blocks=prefix_cache_blocks,
                kv_pool_blocks=kv_pool_blocks, spec_decode=spec_decode,
                kv_dtype=kv_dtype, quantize=quantize,
                windows_per_dispatch=windows_per_dispatch))
            # a piece of a prompt fills a bucket and ends at or before
            # its window's edge: buckets that divide the window
            w = cfg.window_size
            self.buckets = tuple(b for b in self.buckets
                                 if w % b == 0) or (w,)
        #: latent attention (models/xing.py): a slot's state is one
        #: latent row per position; admitted piece by piece as well
        self._mla = cfg.is_mla
        if self._mla:
            self._check_mla(dict(
                mesh=mesh, prefix_cache_blocks=prefix_cache_blocks,
                kv_pool_blocks=kv_pool_blocks, spec_decode=spec_decode,
                kv_dtype=kv_dtype, quantize=quantize,
                windows_per_dispatch=windows_per_dispatch))
        #: window and global layers in one model (models/mixed.py): a
        #: slot's state is a ring for the one kind and a full extent
        #: for the other; admitted piece by piece as well
        self._mixed = cfg.is_mixed
        if self._mixed:
            self._check_mixed(dict(
                mesh=mesh, prefix_cache_blocks=prefix_cache_blocks,
                kv_pool_blocks=kv_pool_blocks, spec_decode=spec_decode,
                kv_dtype=kv_dtype, quantize=quantize,
                windows_per_dispatch=windows_per_dispatch))
        #: admission advances every admitted prompt a piece a wave
        self._pieces = self._eva or self._mla or self._mixed
        # eos_id may be a list (Llama-3.1-style multi-EOS checkpoints).
        eos_list = list(eos_id) if isinstance(eos_id, (list, tuple)) \
            else [int(eos_id)]
        self.eos_id = int(eos_list[0])
        self._eos_set = frozenset(int(e) for e in eos_list)
        self.attn_impl = attn_impl
        self.decode_window = max(1, decode_window)
        # How many windows one dispatch chains in-program. >1 amortizes
        # the host↔device sync at the cost of coarser
        # retirement/admission granularity — right for batch
        # workloads, 1 for latency-sensitive serving.
        self.windows_per_dispatch = max(1, windows_per_dispatch)
        # Prompt tokens one admission wave may prefill: the wave's f32
        # swiglu transient is budget×d_ff×8 bytes (~0.9 GB at 16k), so
        # long-context engines (big caches) trade admission batching
        # for HBM headroom by lowering this.
        self.admission_token_budget = admission_token_budget
        # Tokens in one block of the prefix cache and of the KV pool.
        self.prefill_chunk = max(1, prefill_chunk)
        self._dispatch_steps = self.decode_window * self.windows_per_dispatch
        if self.max_len - self._dispatch_steps < 1:
            raise ValueError(
                f"decode_window {self.decode_window} x "
                f"{self.windows_per_dispatch} windows/dispatch leaves no "
                f"prompt room in max_len {self.max_len}")
        self._key = jax.random.PRNGKey(seed)

        # quantize: False | True ("int8") | "int8" | "int4". int4 packs
        # two nibbles per byte with group-wise scales — half the weight
        # HBM (and decode weight traffic) of int8 again.
        qmode = ("int8" if quantize is True else quantize) or None
        if qmode not in (None, "int8", "int4"):
            raise ValueError(f"unknown quantize mode {qmode!r}")
        self.quant_mode = qmode
        axes = decoder.logical_axes(cfg)
        if params is None and self._eva:
            params = eva.init_params(jax.random.PRNGKey(seed), cfg,
                                     dtype=dtype)
        if self._mla:
            # its own layout (two stacks of layers, expert stacks):
            # made and quantized by its own module
            if params is None:
                params = xing.init_params(jax.random.PRNGKey(seed), cfg,
                                          dtype=dtype)
            if qmode:
                params = xing.quantize_params(params)
        if self._mixed:
            if params is None:
                params = mixed.init_params(jax.random.PRNGKey(seed), cfg,
                                           dtype=dtype)
            if qmode:
                params = mixed.quantize_params(params)
        if params is None:
            if qmode:
                params = quant.init_random_quantized(
                    jax.random.PRNGKey(seed), cfg, dtype=dtype, mode=qmode)
            else:
                params = decoder.init_params(jax.random.PRNGKey(seed), cfg,
                                             dtype=dtype)
        if qmode and mesh is not None:
            # The fused Pallas quant kernels are not GSPMD-partitionable
            # yet; sharded engines fall back to the XLA dequant
            # expression, which partitions naturally over tp.
            quant.set_pallas_qmatmul(False)
        if params is not None and qmode \
                and not (self._mla or self._mixed) \
                and not quant.is_quantized(
                    params.get("layers", {}).get("wq")):
            # Caller provided full-precision weights: quantize on the fly.
            # (Real checkpoints should be quantized offline on the host —
            # this transient needs both copies in memory.)
            params = quant.quantize_params(params, mode=qmode)
        if qmode:
            axes = quant.quantize_logical_axes(axes, mode=qmode)
        # Long-extent int4 decode auto-route (r4 verdict, Weak 3): the
        # Pallas int4 decode path degrades far beyond its byte count at
        # long kv extents (measured 136 ms/step at 3072 vs the ~30 ms
        # bytes floor), exactly the capacity configuration int4 exists
        # for. Above this extent the DECODE program is traced with the
        # XLA dequant expression instead (thread-local override around
        # the decode dispatch; admission keeps the global route — the
        # prefill wave is MXU-bound and unaffected). None disables.
        self._decode_pallas_override: bool | None = None
        if (qmode == "int4" and int4_pallas_max_extent is not None
                and self.max_len > int4_pallas_max_extent
                and quant.pallas_qmatmul_enabled()):
            self._decode_pallas_override = False
        if (qmode == "int4" and mesh is None and not cfg.is_moe
                and quant.pallas_qmatmul_enabled()
                and jax.default_backend() == "tpu"):
            # Fused qkv / gate+up leaves: 4 Pallas calls per layer
            # instead of 7 — per-call overhead (~65 µs) is what erased
            # int4's halved-byte advantage. Single-chip serving only
            # (no sharding rules for the fused leaves). The fused
            # leaves stay compatible with the XLA dequant route (the
            # decode override above): int4_matmul_xla unpacks the same
            # packed layout.
            params = quant.fuse_int4_projections(params)
        if mesh is not None:
            # shard_pytree device_puts numpy leaves shard-by-shard, so a
            # host-resident (mmap'd) checkpoint never fully materializes
            # on one device. Head-structured axes tp does not divide
            # replicate instead of splitting within head_dim
            # (serving_param_rules — the PR-15 root cause of the mesh
            # bit-identity failure).
            params = shard_pytree(params, axes, mesh,
                                  serving_param_rules(cfg, mesh))
        else:
            params = jax.tree.map(jnp.asarray, params)
        self.params = params

        # kv_dtype below activation dtype (float8_e4m3fn) halves cache
        # HBM, doubling the slot count a chip fits — decode throughput is
        # weight-bandwidth-bound so tokens/step scales with slots. e4m3's
        # dynamic range covers KV activations; no per-tensor scales kept.
        self.kv_dtype = resolve_kv_dtype(kv_dtype, dtype)

        # ---- paged KV (kv_pool_blocks > 0): one block pool under
        # admission, decode, verify and chunked prefill ----------------
        # Slots stop reserving max_len columns each; positions map onto
        # pool blocks through per-slot block tables, blocks allocate on
        # demand, prefix hits are pointer handoffs, and the slot
        # ceiling lifts to whatever the pool holds. The contiguous
        # per-slot cache below is NOT allocated. Design: docs/
        # ENGINE_PREFIX_CACHE.md ("Paged KV") + ops/paged_attention.py.
        self.paged = bool(kv_pool_blocks)
        self._pool = None
        # Dispatch-route knob for the paged layout: the Pallas paged
        # kernel reads pool blocks IN PLACE by scalar-prefetched block
        # table (no working-set gather materializes), the XLA
        # reference route gathers the view the tables describe. "auto"
        # picks the kernel on TPU and the reference elsewhere (the
        # kernel still RUNS off-TPU via interpret mode — that is what
        # the parity gate exercises — but interpreted Pallas is not a
        # serving route). Explicit values pin a route for parity
        # tests and benches.
        if kv_kernel not in ("auto", "pallas", "reference"):
            raise ValueError(
                f"kv_kernel must be 'auto', 'pallas' or 'reference', "
                f"got {kv_kernel!r}")
        if kv_kernel != "auto" and not self.paged:
            raise ValueError(
                "kv_kernel selects the paged-attention dispatch route "
                "and requires kv_pool_blocks > 0")
        self.kv_kernel = kv_kernel
        if self.paged:
            #: resolved dispatch route, labeled on every StepRecord
            #: and the copilot_engine_kv_route gauge ("" = contiguous)
            self._kv_route = "kernel" if (
                kv_kernel == "pallas"
                or (kv_kernel == "auto"
                    and jax.default_backend() == "tpu")) \
                else "reference"
            if self.telemetry is not None:
                self.telemetry.gauge_kv_route(self._kv_route)
        else:
            self._kv_route = ""
        # Disaggregated serving role (engine/roles.py): "both" is the
        # co-located default; "prefill" parks finished prefills for a
        # block-granular KV handoff instead of decoding them, "decode"
        # additionally accepts handed-off timelines via
        # ``admit_prefilled``. Roles ride the paged layout — the block
        # pool IS the handoff substrate.
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', "
                f"got {role!r}")
        if role != "both" and not self.paged:
            raise ValueError(
                "prefill/decode roles require kv_pool_blocks: the "
                "block-granular KV handoff moves pool blocks, not "
                "contiguous slot caches")
        self.role = role
        #: slot → (request, first_token, prompt_len, ready_at) parked
        #: for handoff (prefill role); the slot's blocks keep the
        #: prompt KV until ``take_prefilled`` exports them
        self._handoff: dict[int, tuple] = {}
        self.handoff_exported = 0
        self.handoff_imported = 0
        #: release hold: the prefill role stops releasing scheduler
        #: waves when this many finished prefills await handoff
        #: (parked here + exported-but-unadmitted, reported by the
        #: wrapper via set_handoff_external — decode is the
        #: bottleneck; prefilling further ahead only pins pool
        #: blocks). Parked entries are slot-keyed so they cap at
        #: num_slots: the default fires at HALF the slots parked,
        #: which is reachable, not ornamental.
        self._handoff_high = int(handoff_high) or max(1,
                                                      num_slots // 2)
        #: handoffs exported but not yet admitted downstream (the
        #: DisaggregatedEngine reports its pending-queue depth here so
        #: the backlog signal covers the whole handoff pipeline)
        self._handoff_external = 0
        #: dp degree of the paged layout (1 when unsharded) — the
        #: block pool's shard count and the slot partition
        self._dp = 1
        self._slots_ps = num_slots
        if self.paged:
            from copilot_for_consensus_tpu.engine.kv_pool import (
                BlockPool,
            )
            if mesh is not None:
                # Sharded paged serving: dp splits the BLOCK axis (and
                # the slot partition), tp splits kv-heads inside each
                # block (replicated when indivisible). Axes beyond
                # dp×tp have no paged dispatch plumbing yet.
                for ax in ("pp", "sp", "ep"):
                    if mesh.shape.get(ax, 1) != 1:
                        raise ValueError(
                            f"kv_pool_blocks shards over dp×tp only; "
                            f"mesh has {ax}={mesh.shape[ax]}")
                self._dp = int(mesh.shape["dp"])
                if num_slots % self._dp:
                    raise ValueError(
                        f"kv_pool_blocks on a mesh requires num_slots "
                        f"({num_slots}) divisible by dp ({self._dp}): "
                        f"slots partition over the dp shards")
                self._slots_ps = num_slots // self._dp
            block = self.prefill_chunk
            if 128 % block:
                raise ValueError(
                    f"kv_pool_blocks requires prefill_chunk (the block "
                    f"size) to divide 128, got {block}: decode kv "
                    f"extents bucket to 128-aligned widths and every "
                    f"bucket must be block-aligned")
            if self.max_len % block:
                raise ValueError(
                    f"kv_pool_blocks requires max_len % prefill_chunk "
                    f"== 0, got {self.max_len} % {block}")
            self._block = block
            self._max_blocks = self.max_len // block
            #: per-dispatch write margin: a decode window, a verify
            #: wave, or a chunk continuation never writes further than
            #: this past a slot's committed length
            self._write_margin = max(
                self._dispatch_steps,
                max(spec_draft_lens, default=0) + 1)
            #: worst-case blocks one slot can ever hold (the free-block
            #: admission accounting's unit)
            if kv_pool_blocks < (self._max_blocks + 1) * self._dp:
                raise ValueError(
                    f"kv_pool_blocks={kv_pool_blocks} cannot hold even "
                    f"one max_len={self.max_len} slot "
                    f"({self._max_blocks} blocks) plus headroom per "
                    f"dp shard (dp={self._dp})")
            self._pool = BlockPool(cfg, num_blocks=kv_pool_blocks,
                                   block_size=block,
                                   kv_dtype=self.kv_dtype, mesh=mesh)
            #: slot → block table (pool block ids, position p lives at
            #: table[p // block] offset p % block) and the index where
            #: OWNED blocks start (entries before it are BORROWED from
            #: the prefix trie — shared, read-only, pinned via the
            #: request's PrefixMatch until retire)
            self._tables: list[list[int]] = [[] for _ in range(num_slots)]
            self._owned_from: list[int] = [0] * num_slots
            #: zero-copy admission ledger: seeded admits that appended
            #: matched block ids instead of gathering pool→slot copies
            self.zero_copy_admits = 0
            self.paged_admits = 0
            #: high-water mark of concurrently active streams
            self.peak_active = 0
            self._cache = None
        elif self._eva:
            # one manager for both kinds of state: per slot a window of
            # exact keys and values and a store of summaries, two
            # halves each, donated to every program and updated in
            # place (models/eva.py)
            self._cache = eva.init_cache(cfg, num_slots, self.max_len,
                                         dtype=self.kv_dtype,
                                         margin=self._dispatch_steps)
        elif self._mla:
            # one latent row per position and layer, all heads' (a
            # stack of layers an array: models/xing.py), donated to
            # every program and updated in place
            self._cache = xing.init_cache(cfg, num_slots, self.max_len,
                                          dtype=self.kv_dtype)
        elif self._mixed:
            # one array per layer KIND and half (models/mixed.py): a
            # ring for the window layers, a full extent for the others
            self._cache = mixed.init_cache(cfg, num_slots, self.max_len,
                                           self.buckets[-1],
                                           dtype=self.kv_dtype)
        else:
            cache = decoder.init_cache(cfg, num_slots, self.max_len,
                                       dtype=self.kv_dtype)
            if mesh is not None:
                # Replicate cache axes the mesh doesn't divide (e.g. tp
                # larger than the kv-head count — standard GQA serving
                # replicates kv).
                rules = dict(DEFAULT_RULES)
                if cfg.n_kv_heads % mesh.shape["tp"]:
                    rules["kv_heads"] = None
                if num_slots % mesh.shape["dp"]:
                    rules["batch"] = None
                cache = shard_pytree(cache, decoder.cache_logical_axes(),
                                     mesh, rules)
            self._cache = cache

        # ---- jitted programs -------------------------------------------
        impl = attn_impl

        def _insert_batch(cache, pref, slots):
            """Insert N prefilled kv blocks into their slots in one
            program. ``slots`` may contain out-of-range ids for padded
            prefill rows — 'drop' mode discards those updates."""
            s = pref["k"].shape[3]
            with scope("kv_write"):
                k = cache["k"].at[:, slots, :, :s, :].set(
                    pref["k"].astype(cache["k"].dtype), mode="drop")
                v = cache["v"].at[:, slots, :, :s, :].set(
                    pref["v"].astype(cache["v"].dtype), mode="drop")
            return {"k": k, "v": v}

        def _admit_fused(params, tokens, lengths, cache, slots, key):
            """Prefill + cache insert + first-token sample as ONE
            program — one dispatch and one sync per admission wave.
            The prefill scratch is born in the serving cache dtype:
            prefill attention uses the fresh bf16 k/v, the scratch only
            ferries them to the insert, and a bf16 scratch at full
            admission width was the largest admission-path transient
            (4.3 GB for 256×128 tokens)."""
            scratch = decoder.init_cache(cfg, tokens.shape[0],
                                         tokens.shape[1],
                                         dtype=self.kv_dtype)
            logits, scratch = decoder.prefill(params, tokens, lengths,
                                              cfg, scratch,
                                              attn_impl=impl)
            cache = _insert_batch(cache, scratch, slots)
            first = sample(logits, key, self.sampling)
            return first, cache

        self._admit_fn = jax.jit(_admit_fused, donate_argnums=(3,))

        # ---- prefix KV cache (cross-request reuse) ---------------------
        # Radix trie + device block pool (engine/prefix_cache.py). On a
        # hit the admission wave gathers the reused blocks from the
        # pool, scatters them into the slot's cache prefix, and
        # prefills ONLY the suffix — TTFT and admission FLOPs drop by
        # the shared-prefix fraction. Block size = prefill_chunk.
        #: one radix trie per dp shard (a zero-copy hit appends POINTERS
        #: into the slot's own shard's pool slice, so cached prefixes
        #: are shard-local by construction; dp=1 = one trie, the
        #: original design). ``_prefix`` below is the single-shard
        #: compatibility view.
        self._prefixes: list[Any] = []
        self._prefix_pins: dict[int, Any] = {}   # request_id → PrefixMatch
        #: prompt tokens actually prefilled / skipped via prefix reuse —
        #: the bench's savings accounting (prefix_stats()).
        self.prefill_tokens = 0
        self.prefill_tokens_saved = 0
        if prefix_cache_blocks:
            if mesh is not None and not self.paged:
                raise ValueError(
                    "prefix_cache_blocks on a mesh requires the paged "
                    "engine (kv_pool_blocks): the contiguous block "
                    "pool and a dp-sharded slot cache would live on "
                    "different shards; the paged pool shards WITH its "
                    "per-shard tries")
            if self._windowed():
                raise ValueError(
                    "prefix_cache_blocks requires full attention: a "
                    "reused prefix under a sliding window needs "
                    "absolute-timeline window masking the seeded "
                    "prefill path does not implement")
            from copilot_for_consensus_tpu.engine.prefix_cache import (
                PrefixCache,
            )
            # Paged engines share ONE pool between active slots and the
            # trie (prefix_cache_blocks acts as an enable flag; the
            # budget is kv_pool_blocks): publish is an adopt_blocks
            # refcount handoff, hits are pointer admissions.
            self._prefixes = [
                PrefixCache(
                    cfg, num_blocks=prefix_cache_blocks,
                    block_size=self.prefill_chunk,
                    kv_dtype=self.kv_dtype,
                    shared=self._pool if self.paged else None)
                for _ in range(self._dp if self.paged else 1)]

        def _admit_seeded(params, tokens, lengths, pool_k, pool_v,
                          bids_flat, pref_lens, cache, slots, key):
            """Admission wave with prefix-cache hits: gather reused
            blocks from the pool, seed them into the slot cache, prefill
            only the suffix (RoPE/attention offset by pref_lens), insert
            the suffix KV at the per-row offset, sample first tokens —
            still ONE program and one host sync per wave.

            tokens: [N, Sbuc] right-padded suffixes; bids_flat: [N*NB]
            pool block ids row-major (pad = pool size → gather clamps,
            scatter drops); pref_lens: [N] matched prefix tokens (0 =
            miss row — the same program serves mixed waves)."""
            n_l, hkv, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
            n, sbuc = tokens.shape
            nb = bids_flat.shape[0] // n
            blk = pool_k.shape[3]
            with scope("kv_prefix"):
                pk_flat = pool_k[:, bids_flat]  # [L, N*NB, Hkv, B, Dh]
                pv_flat = pool_v[:, bids_flat]
                pk = pk_flat.reshape(
                    n_l, n, nb, hkv, blk, dh).transpose(
                    0, 1, 3, 2, 4, 5).reshape(
                    n_l, n, hkv, nb * blk, dh)
                pv = pv_flat.reshape(
                    n_l, n, nb, hkv, blk, dh).transpose(
                    0, 1, 3, 2, 4, 5).reshape(
                    n_l, n, hkv, nb * blk, dh)
            scratch = decoder.init_cache(cfg, n, sbuc,
                                         dtype=self.kv_dtype)
            logits, scratch = decoder.prefill_seeded(
                params, tokens, lengths, pk, pv, pref_lens, cfg,
                scratch)
            # seed the reused prefix blocks into the slot cache: block
            # j of row i lands at positions [j*blk, (j+1)*blk) of
            # slots[i]; pad entries (OOB bid) get an OOB slot and drop.
            m = n * nb
            valid = bids_flat < pool_k.shape[1]
            sidx_b = jnp.where(valid, jnp.repeat(slots, nb),
                               self.num_slots)
            sidx_b = jnp.broadcast_to(sidx_b[:, None], (m, blk))
            pidx_b = (jnp.tile(jnp.arange(nb), n) * blk)[:, None] \
                + jnp.arange(blk)[None, :]
            with scope("kv_write"):
                upd_k = pk_flat.transpose(1, 3, 0, 2, 4)  # [M,B,L,H,D]
                upd_v = pv_flat.transpose(1, 3, 0, 2, 4)
                ck = cache["k"].at[:, sidx_b, :, pidx_b, :].set(
                    upd_k.astype(cache["k"].dtype), mode="drop")
                cv = cache["v"].at[:, sidx_b, :, pidx_b, :].set(
                    upd_v.astype(cache["v"].dtype), mode="drop")
                # insert the fresh suffix KV at the per-row prefix
                # offset
                sidx_s = jnp.broadcast_to(slots[:, None], (n, sbuc))
                pidx_s = pref_lens[:, None] + jnp.arange(sbuc)[None, :]
                ck = ck.at[:, sidx_s, :, pidx_s, :].set(
                    scratch["k"].transpose(1, 3, 0, 2, 4).astype(
                        ck.dtype), mode="drop")
                cv = cv.at[:, sidx_s, :, pidx_s, :].set(
                    scratch["v"].transpose(1, 3, 0, 2, 4).astype(
                        cv.dtype), mode="drop")
            first = sample(logits, key, self.sampling)
            return first, {"k": ck, "v": cv}

        self._admit_seeded_fn = jax.jit(_admit_seeded,
                                        donate_argnums=(7,))

        def _decode(params, tokens, positions, cache, key, *, kv_len,
                    n_windows=1):
            """``n_windows × decode_window`` steps fused in one program:
            decode → sample → feed back, all on-device. One dispatch and
            one host sync per program — the difference between
            dispatch-bound and HBM-bound decode (the per-step vs fused
            gap is not measured on the current chip). n_windows exists
            for the same reason: chaining windows IN-program amortizes
            the sync without growing the window buffers.

            The big KV cache stays OUT of the token loop altogether: a
            per-step carried cache is re-materialized by XLA every token
            (~2× cache bytes — measured 2778→1841 tok/s going max_len
            256→512 with identical attended work, before this design).
            The program touches it twice per dispatch: the prefix
            attention reads is cut out once, here, before the token
            scan (a slice taken inside the scan's body is a cache-sized
            copy per token — XLA does not hoist it), and fresh KV, which
            accumulates in small [L, B, Hkv, W, Dh] window buffers,
            merges in place once at the end. ``kv_len`` (static,
            bucketed by the caller) bounds that prefix and must cover
            all n_windows. Where attention reads each slot's live
            blocks in place (``_reads_live_blocks``) there is no cut:
            the step takes the cache whole, and the caller's ``kv_len``
            is always the full extent (one program)."""
            w_sz = self.decode_window
            n_l = cfg.n_layers
            b = tokens.shape[0]
            shape = (n_l, b, cfg.n_kv_heads, w_sz, cfg.head_dim)
            if self._reads_live_blocks():
                prefix, step = cache, decoder.decode_step_windowed_live
            else:
                prefix = decoder.cache_prefix(cache, kv_len)
                step = decoder.decode_step_windowed

            def run_window(tok, key, done):
                k_win = jnp.zeros(shape, self.kv_dtype)
                v_win = jnp.zeros(shape, self.kv_dtype)
                k_done, v_done = done

                def body(carry, w):
                    tok, k_win, v_win, key = carry
                    key, sub = jax.random.split(key)
                    logits, k_cols, v_cols = step(
                        params, tok, positions, w, cfg, prefix, k_win,
                        v_win, k_done=k_done, v_done=v_done)
                    k_win = decoder.put_window_column(k_win, k_cols, w)
                    v_win = decoder.put_window_column(v_win, v_cols, w)
                    nxt = sample(logits, sub, self.sampling)
                    return (nxt, k_win, v_win, key), nxt

                (tok, k_win, v_win, key), toks = jax.lax.scan(
                    body, (tok, k_win, v_win, key), jnp.arange(w_sz))
                return tok, key, toks, k_win, v_win

            # Chain windows WITHOUT touching the big cache in between:
            # completed windows ride along as a fourth attention piece
            # (k_done) and everything merges once at the end. Merging
            # per window makes the cache a loop variable, which XLA
            # ping-pong double-buffers — a second full cache allocation
            # (+2x at 128x512 fp8: the r2 "compile crash" at kv extents
            # > 256 was this OOM). Here the cache stays a read-only
            # invariant until the single final scatter.
            tok, done, outs, wins = tokens, (None, None), [], []
            for widx in range(n_windows):
                tok, key, toks, k_win, v_win = run_window(tok, key, done)
                outs.append(toks)
                wins.append((k_win, v_win))
                if widx + 1 < n_windows:
                    done = (jnp.concatenate([kw for kw, _ in wins], 3),
                            jnp.concatenate([vw for _, vw in wins], 3))
            if n_windows == 1:
                k_all, v_all = wins[0]
                toks_all = outs[0]
            else:
                k_all = jnp.concatenate([kw for kw, _ in wins], 3)
                v_all = jnp.concatenate([vw for _, vw in wins], 3)
                toks_all = jnp.concatenate(outs, axis=0)
            cache = decoder.merge_window(cache, k_all, v_all, positions,
                                         steps=n_windows * w_sz)
            return toks_all, cache      # toks: [windows*w_sz, slots]

        self._decode_fn = jax.jit(_decode, donate_argnums=(3,),
                                  static_argnames=("kv_len", "n_windows"))

        # ---- speculative decoding (prompt-lookup drafts) ---------------
        # Decode pays one full weight read per generated token; the
        # verify dispatch amortizes that read over k drafted tokens
        # scored in ONE pass. Draft lengths come from a STATIC bucket
        # set so retrace count stays bounded (one program per nonzero
        # bucket × kv bucket): a wave's k_max is the largest per-slot
        # bucketed draft, and slots with no hit ride the same program
        # in the k=0 lane (one real token, masked padding).
        self.spec_decode = bool(spec_decode)
        self.spec_draft_lens = tuple(sorted(
            {int(k) for k in spec_draft_lens} | {0}))
        if any(k < 0 for k in self.spec_draft_lens):
            raise ValueError(
                f"spec_draft_lens must be >= 0, got {spec_draft_lens}")
        self._spec_max_draft = max(self.spec_draft_lens)
        self.spec_ngram = int(spec_ngram)
        self.spec_min_ngram = int(spec_min_ngram)
        if self.spec_decode:
            if self._windowed():
                raise ValueError(
                    "spec_decode requires full attention: the verify "
                    "pass rides prefill_attention_seeded, which does "
                    "not implement absolute-timeline window masking")
            if self._spec_max_draft + 1 >= self.max_len:
                raise ValueError(
                    f"spec_draft_lens {spec_draft_lens} leave no cache "
                    f"room in max_len {self.max_len}")
        #: slot → NgramDraftIndex over (prompt + emitted tokens); built
        #: at admission, extended as tokens are accepted, dropped at
        #: retirement. Pure host state — the drafting side costs zero
        #: device work.
        self._draft_index: dict[int, NgramDraftIndex] = {}

        def _verify(params, tokens, qlens, positions, cache, key, *,
                    kv_len):
            """Score k+1 positions per slot in ONE weight pass and
            accept drafts exactly — the speculative-decoding dispatch.

            tokens: [B, S] (S = k_max+1): each row is the slot's
            committed next token followed by its drafted continuation,
            right-padded; qlens: [B] valid tokens per row (draft len
            + 1; 1 = the k=0 lane); positions: [B] committed cache
            prefix (free slots park out of range — their scatter rows
            drop). A short seeded prefill (``decoder.verify_seeded``)
            reads the slot cache as the seeded prefix, fresh KV for
            all S fed tokens scatters into the cache at the per-row
            offset in one ``merge_window`` (columns past the accept
            point are dead by the prefix-length masking and get
            overwritten by the next write at those positions — the
            same invalidation discipline the prefix-cache publish
            relies on), and ``verify_draft`` applies greedy
            (bit-identical) or rejection-rule (distribution-exact)
            acceptance in-program, so the host fetches only
            [B, S] + [B] ints."""
            logits, k_new, v_new = decoder.verify_seeded(
                params, tokens, qlens, positions, cfg, cache,
                kv_len=kv_len)
            cache = decoder.merge_window(cache, k_new, v_new, positions,
                                         steps=tokens.shape[1])
            out, n_accept = verify_draft(logits, tokens[:, 1:],
                                         qlens - 1, key, self.sampling)
            return out, n_accept, cache

        self._verify_fn = jax.jit(_verify, donate_argnums=(4,),
                                  static_argnames=("kv_len",))

        # ---- SLO-aware scheduler (engine/scheduler.py) -----------------
        # Admission policy owner: per-tenant weighted-DRR fairness with
        # priority lanes, closed-loop load shedding over the telemetry
        # signals, and CHUNKED PREFILL — prompts longer than the
        # configured chunk size split across continuation dispatches
        # co-scheduled with decode windows, so one long prompt costs
        # many small ITL bumps instead of a monolithic admission stall.
        # The continuation program below is the seeded-prefill path
        # (PR 1) generalized: ``decoder.verify_seeded`` reads the
        # slot's own partially-filled cache as the seeded prefix, the
        # chunk's fresh KV scatters in at the per-row fill offset, and
        # the FINAL chunk samples the first token from the last prompt
        # position — bit-identical (greedy) to the monolithic wave when
        # the cache dtype matches the compute dtype, same argument as
        # the prefix cache. Design: docs/SCHEDULER.md.
        self._sched = resolve_scheduler(scheduler,
                                        telemetry=self.telemetry)
        # Chunking rides prefill_attention_seeded, which (like spec
        # decode) does not implement absolute-timeline window masking.
        self._chunk_ok = not self._pieces and not self._windowed()
        ct = self.prompt_limit
        if self._sched is not None:
            ct = max(1, min(self._sched.cfg.chunk_tokens,
                            self.prompt_limit))
        #: static chunk-width bucket set — the retrace bound for the
        #: continuation program, exactly like the verify dispatch's
        #: draft-length buckets (shardcheck: scheduler-chunked-prefill)
        self._chunk_buckets = tuple(sorted(
            {min(b, ct) for b in self.buckets} | {ct}))
        #: released long prompts waiting for a slot to start chunking
        self._chunk_pending: list[Request] = []
        #: slot → [request, tokens filled so far, chunk-start time]
        self._chunking: dict[int, list] = {}
        #: chunked-prefill accounting (sched_stats())
        self.chunk_dispatches = 0
        self.chunk_prefill_tokens = 0
        self.chunk_s = 0.0

        def _prefill_chunk(params, tokens, qlens, positions, cache, key,
                           *, kv_len):
            """One chunked-prefill continuation dispatch: every
            chunking slot's next prompt chunk attends (its own cache
            prefix ++ fresh causal chunk) in ONE weight pass, fresh KV
            merges at the per-row fill offset, and each row samples a
            candidate first token from its last fed position (the host
            keeps it only for rows whose prompt completed this chunk).
            Non-chunking rows park at position max_len: their fresh KV
            drops in the merge and their logits are discarded — the
            same park-OOB discipline as the verify dispatch."""
            logits, k_new, v_new = decoder.verify_seeded(
                params, tokens, qlens, positions, cfg, cache,
                kv_len=kv_len)
            cache = decoder.merge_window(cache, k_new, v_new, positions,
                                         steps=tokens.shape[1])
            last = jnp.take_along_axis(
                logits, (qlens - 1)[:, None, None], axis=1)[:, 0]
            first = sample(last, key, self.sampling)
            return first, cache

        self._chunk_fn = jax.jit(_prefill_chunk, donate_argnums=(4,),
                                 static_argnames=("kv_len",))

        # ---- EVA programs (cfg.attention == "eva"; models/eva.py) ------
        # Two programs stand where the dense decoder has admission,
        # chunk continuation and decode: a piece of each admitted
        # prompt per row, and the decode dispatch. Static keys: rows x
        # bucket; whether a window can fill within the dispatch.

        def _admit_eva(params, tokens, lens, pos0, slots, cache, key):
            """One admission wave: each row's next piece (at most a
            bucket, never across a window edge) into its slot's window,
            the window compacted where the piece ends on its edge, and
            a candidate first token from each row's last position (the
            host keeps it for rows whose prompt ended)."""
            logits, cache = eva.prefill_piece(
                params, tokens, lens, pos0, slots, cfg, cache,
                attn_impl=impl)
            first = sample(logits[:, :cfg.vocab_size], key, self.sampling)
            return first, cache

        def _decode_eva(params, tokens, positions, cache, key, *,
                        may_close):
            """``decode_window`` steps for every slot against window
            and summaries, compaction included (eva.decode_tokens)."""
            return eva.decode_tokens(
                params, tokens, positions, cfg, cache, key,
                lambda logits, sub: sample(logits, sub, self.sampling),
                steps=self.decode_window, may_close=may_close,
                max_len=self.max_len)

        if self._eva:
            self._admit_eva_fn = jax.jit(_admit_eva, donate_argnums=(5,))
            self._decode_eva_fn = jax.jit(
                _decode_eva, donate_argnums=(3,),
                static_argnames=("may_close",))

        # ---- latent-attention programs (cfg.attention == "mla") --------
        # The same two programs for models/xing.py: a piece of each
        # admitted prompt per row (static key: rows x bucket), and ONE
        # decode dispatch whatever the lengths. Each hands back the
        # routing's counts beside its tokens.

        def _admit_mla(params, tokens, lens, pos0, slots, cache, key):
            logits, cache, counts = xing.prefill_piece(
                params, tokens, lens, pos0, slots, cfg, cache)
            return sample(logits, key, self.sampling), cache, counts

        def _decode_mla(params, tokens, positions, cache, key):
            return xing.decode_tokens(
                params, tokens, positions, cfg, cache, key,
                lambda logits, sub: sample(logits, sub, self.sampling),
                steps=self.decode_window, max_len=self.max_len,
                live_blocks=self._reads_latent_blocks())

        if self._mla:
            self._admit_mla_fn = jax.jit(_admit_mla, donate_argnums=(5,))
            self._decode_mla_fn = jax.jit(_decode_mla, donate_argnums=(3,))

        # ---- window and global layers (cfg.attention == "mixed") -------
        # The same two programs for models/mixed.py, of the same form
        # and with the same counts beside the tokens.

        def _admit_mixed(params, tokens, lens, pos0, slots, cache, key):
            logits, cache, counts = mixed.prefill_piece(
                params, tokens, lens, pos0, slots, cfg, cache)
            return sample(logits, key, self.sampling), cache, counts

        def _decode_mixed(params, tokens, positions, cache, key):
            return mixed.decode_tokens(
                params, tokens, positions, cfg, cache, key,
                lambda logits, sub: sample(logits, sub, self.sampling),
                steps=self.decode_window, max_len=self.max_len,
                live_blocks=self._reads_ring_blocks())

        if self._mixed:
            self._admit_mixed_fn = jax.jit(_admit_mixed,
                                           donate_argnums=(5,))
            self._decode_mixed_fn = jax.jit(_decode_mixed,
                                            donate_argnums=(3,))

        # ---- paged dispatch programs (kv_pool_blocks > 0) --------------
        # Two routes serve the same block-table semantics, selected by
        # ``kv_kernel`` into ``self._kv_route``:
        #
        # REFERENCE (``kv_kernel="reference"``, and "auto" off-TPU):
        # the contiguous program composed with the indirection of
        # ops/paged_attention.py — gather the working-set VIEW the
        # tables describe (a pure reordering, so greedy decode is
        # bit-identical at f32), run the UNCHANGED decoder program
        # over it, read the freshly merged columns back out of the
        # view, scatter them into the pool at host-built (block,
        # offset) maps. Simple and backend-portable, but it
        # materializes kv_len × rows working-set copies and a
        # view-sized round trip EVERY dispatch.
        #
        # KERNEL (``kv_kernel="pallas"``, and "auto" on TPU): the
        # Pallas paged kernel (ops.paged_attention.
        # paged_attention_partial_pallas) scores the committed pool
        # prefix IN PLACE — block tables ride the scalar-prefetch
        # lane, the traced layer index selects into the stacked pool
        # so no per-layer slice materializes either, and fp8 pools
        # dequantize on load inside the kernel. It emits flash
        # partials (acc, m, l) that ``ops.attention.combine_partials``
        # joins with the dispatch-local window/done/cur (decode) or
        # causal-suffix (seeded) pieces — one joint softmax, same
        # masking, parity-gated against the reference route under
        # interpret mode. Fresh KV then scatters as the SAME narrow
        # per-row write the reference route uses, but straight from
        # the window buffers: no view gather, no view read-back, no
        # full-pool-view round trip anywhere in the traced program
        # (pinned by a no-gather trace test).
        #
        # The pool halves are donated on both routes — they are the
        # one long-lived KV allocation and must never double-buffer.
        if self.paged:
            from copilot_for_consensus_tpu.ops.paged_attention import (
                paged_attention_partial_pallas,
                paged_gather_kv,
            )

            @scope("kv_write")
            def _pool_scatter(pool_k, pool_v, k_new, v_new, sbids,
                              soffs):
                """Scatter fresh KV [L, R, Hkv, S, Dh] into the pool at
                per-(row, column) maps [R, S]: column j of row i lands
                in pool block ``sbids[i, j]`` offset ``soffs[i, j]``.
                OOB block ids (parked rows, masked padding) drop."""
                k_upd = k_new.transpose(1, 3, 0, 2, 4)
                v_upd = v_new.transpose(1, 3, 0, 2, 4)
                pk = pool_k.at[:, sbids, :, soffs, :].set(
                    k_upd.astype(pool_k.dtype), mode="drop")
                pv = pool_v.at[:, sbids, :, soffs, :].set(
                    v_upd.astype(pool_v.dtype), mode="drop")
                return pk, pv

            @scope("kv_prefix")
            def _view_take(view, positions, steps):
                """Read the dispatch's freshly merged columns back out
                of the view: [L, B, Hkv, W, Dh]-shaped gather at
                positions + [0, steps) per row (parked rows clamp —
                their scatter map is OOB and drops)."""
                b = view.shape[1]
                s_v = view.shape[3]
                bidx = jnp.broadcast_to(jnp.arange(b)[:, None],
                                        (b, steps))
                pidx = jnp.clip(
                    positions[:, None] + jnp.arange(steps)[None, :],
                    0, s_v - 1)
                return view[:, bidx, :, pidx, :].transpose(2, 0, 3, 1, 4)

            if mesh is None:
                gather, scatter = paged_gather_kv, _pool_scatter
            else:
                # ---- mesh-sharded gather / scatter ------------------
                # The block-table INDIRECTION (pool gather / pool
                # scatter — the two ops GSPMD cannot partition: their
                # indices are per-shard-local by the allocator's
                # design) runs under shard_map with dp MANUAL: each
                # body sees its own pool slice, its own slot rows, and
                # the shard-local ids the host built. The decoder math
                # between them — the UNCHANGED contiguous programs —
                # runs under plain GSPMD over tp×dp inside the same
                # jit, exactly the partitioning the contiguous mesh
                # engine serves with (and the one the bit-identity
                # test pins). Every axis but dp stays AUTO inside the
                # shard_map pieces, so a tp-sharded kv-head axis passes
                # straight through (pp/sp/ep are size-1 here, checked
                # above). Both pool halves stay donated through the
                # outer jit — the one long-lived KV allocation must
                # never double-buffer, sharded or not.
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                manual = {"dp"}
                POOL = P(None, "dp", None, None, None)
                VIEW = P(None, "dp", None, None, None)  # batch on dp
                ROW2 = P("dp", None)

                gather = shard_map(
                    paged_gather_kv, mesh=mesh,
                    in_specs=(POOL, POOL, ROW2),
                    out_specs=(VIEW, VIEW),
                    axis_names=manual, check_vma=False)
                scatter = shard_map(
                    _pool_scatter, mesh=mesh,
                    in_specs=(POOL, POOL, VIEW, VIEW, ROW2, ROW2),
                    out_specs=(POOL, POOL),
                    axis_names=manual, check_vma=False)

            def _admit_paged(params, tokens, lengths, pool_k, pool_v,
                             sbids, soffs, key):
                """Paged admission wave: prefill + pool scatter + first
                token sample as ONE program. The scratch ferries the
                fresh KV straight into pool blocks — no per-slot
                contiguous cache exists to insert into."""
                scratch = decoder.init_cache(cfg, tokens.shape[0],
                                             tokens.shape[1],
                                             dtype=self.kv_dtype)
                logits, scratch = decoder.prefill(params, tokens,
                                                  lengths, cfg, scratch,
                                                  attn_impl=impl)
                pool_k, pool_v = scatter(
                    pool_k, pool_v, scratch["k"], scratch["v"], sbids,
                    soffs)
                first = sample(logits, key, self.sampling)
                return first, pool_k, pool_v

            def _admit_seeded_paged(params, tokens, lengths, pool_k,
                                    pool_v, bids, pref_lens,
                                    sbids, soffs, key):
                """Zero-copy seeded admission: the matched prefix is
                READ from its pool blocks for the suffix attention
                (pointer indirection — the blocks were appended to the
                slot's table host-side, nothing is copied into any
                per-slot cache), the suffix prefills at the per-row
                offset, and only the fresh suffix KV scatters into the
                slot's OWN blocks. ``bids``: [N, NB] — 2-D so the dp
                shard_map splits the row axis with its rows' block ids
                (shard-local under dp sharding)."""
                n, sbuc = tokens.shape
                pk, pv = gather(pool_k, pool_v, bids)
                scratch = decoder.init_cache(cfg, n, sbuc,
                                             dtype=self.kv_dtype)
                logits, scratch = decoder.prefill_seeded(
                    params, tokens, lengths, pk, pv, pref_lens, cfg,
                    scratch)
                pool_k, pool_v = scatter(
                    pool_k, pool_v, scratch["k"], scratch["v"], sbids,
                    soffs)
                first = sample(logits, key, self.sampling)
                return first, pool_k, pool_v

            def _decode_paged(params, tokens, positions, pool_k,
                              pool_v, gbids, sbids, soffs, key, *,
                              kv_len, n_windows=1):
                """Windowed decode over the block tables: gather the
                view ``gbids`` describes (wide enough for this
                dispatch's writes), run the contiguous window program
                over it unchanged, scatter the freshly merged columns
                back into the pool."""
                vk, vv = gather(pool_k, pool_v, gbids)
                toks, view = _decode(params, tokens, positions,
                                     {"k": vk, "v": vv}, key,
                                     kv_len=kv_len,
                                     n_windows=n_windows)
                steps = n_windows * self.decode_window
                k_new = _view_take(view["k"], positions, steps)
                v_new = _view_take(view["v"], positions, steps)
                pool_k, pool_v = scatter(pool_k, pool_v, k_new, v_new,
                                         sbids, soffs)
                return toks, pool_k, pool_v

            def _verify_paged(params, tokens, qlens, positions,
                              pool_k, pool_v, gbids, sbids, soffs,
                              key, *, kv_len):
                vk, vv = gather(pool_k, pool_v, gbids)
                out, n_accept, view = _verify(
                    params, tokens, qlens, positions,
                    {"k": vk, "v": vv}, key, kv_len=kv_len)
                k_new = _view_take(view["k"], positions,
                                   tokens.shape[1])
                v_new = _view_take(view["v"], positions,
                                   tokens.shape[1])
                pool_k, pool_v = scatter(pool_k, pool_v, k_new, v_new,
                                         sbids, soffs)
                return out, n_accept, pool_k, pool_v

            def _chunk_paged(params, tokens, qlens, positions, pool_k,
                             pool_v, gbids, sbids, soffs, key, *,
                             kv_len):
                vk, vv = gather(pool_k, pool_v, gbids)
                first, view = _prefill_chunk(
                    params, tokens, qlens, positions,
                    {"k": vk, "v": vv}, key, kv_len=kv_len)
                k_new = _view_take(view["k"], positions,
                                   tokens.shape[1])
                v_new = _view_take(view["v"], positions,
                                   tokens.shape[1])
                pool_k, pool_v = scatter(pool_k, pool_v, k_new, v_new,
                                         sbids, soffs)
                return first, pool_k, pool_v

            self._admit_paged_fn = jax.jit(
                _admit_paged, donate_argnums=(3, 4))
            self._admit_seeded_paged_fn = jax.jit(
                _admit_seeded_paged, donate_argnums=(3, 4))
            self._decode_paged_fn = jax.jit(
                _decode_paged, donate_argnums=(3, 4),
                static_argnames=("kv_len", "n_windows"))
            self._verify_paged_fn = jax.jit(
                _verify_paged, donate_argnums=(4, 5),
                static_argnames=("kv_len",))
            self._chunk_paged_fn = jax.jit(
                _chunk_paged, donate_argnums=(4, 5),
                static_argnames=("kv_len",))

            if self._kv_route == "kernel":
                # ---- Pallas kernel route ----------------------------
                # Rebinds the FOUR gathering dispatches (plain paged
                # admission never gathered — it is route-agnostic)
                # under the same attribute names, signatures,
                # donations and static args as the reference
                # assignments above, so every call site, retrace
                # bound and shardcheck contract case carries over
                # unchanged. ``kv_len // block`` committed blocks are
                # a STATIC slice of the dispatch's gather table (the
                # view table is always at least that wide): the
                # kernel only ever reads committed positions — fresh
                # KV rides the window/suffix buffers until the one
                # narrow scatter.
                if mesh is None:
                    def _partial_for(window):
                        def call(pool_k, pool_v, tables, li, q_rows,
                                 lengths, q_pos):
                            return paged_attention_partial_pallas(
                                q_rows, pool_k, pool_v, li, tables,
                                lengths, q_pos, window=window)
                        return call
                else:
                    # dp MANUAL exactly like gather/scatter above:
                    # the kernel indexes its shard-local pool slice
                    # with the shard-local ids the host built
                    # (per-shard OOB sentinel clamps in the wrapper,
                    # same park discipline as the gather). tp stays
                    # an AUTO axis — the pallas_call is opaque to
                    # GSPMD, so a tp-sharded kv-head axis replicates
                    # through it (docs/PERF.md "Kernel route" carries
                    # the honest accounting).
                    QROWS = P("dp", None, None, None)

                    def _partial_for(window):
                        def call(pool_k, pool_v, tables, li, q_rows,
                                 lengths, q_pos):
                            return paged_attention_partial_pallas(
                                q_rows, pool_k, pool_v, li, tables,
                                lengths, q_pos, window=window)
                        return shard_map(
                            call, mesh=mesh,
                            in_specs=(POOL, POOL, ROW2, P(), QROWS,
                                      P("dp"), P("dp")),
                            out_specs=(QROWS, QROWS, QROWS),
                            axis_names=manual, check_vma=False)

                partial_dec = _partial_for(cfg.sliding_window)
                partial_seed = _partial_for(0)

                def _decode_paged_kernel(params, tokens, positions,
                                         pool_k, pool_v, gbids,
                                         sbids, soffs, key, *,
                                         kv_len, n_windows=1):
                    """Kernel-route windowed decode: the reference
                    ``_decode`` body verbatim (same key-split/sample
                    order, so greedy token streams match) except the
                    committed pool prefix is scored IN PLACE per
                    layer and the window buffers scatter straight to
                    the pool — no view gather, no view read-back."""
                    tables = gbids[:, :kv_len // self._block]

                    def partial_fn(li, q_rows, lengths, q_pos):
                        return partial_dec(pool_k, pool_v, tables,
                                           li, q_rows, lengths,
                                           q_pos)

                    w_sz = self.decode_window
                    b = tokens.shape[0]
                    shape = (cfg.n_layers, b, cfg.n_kv_heads, w_sz,
                             cfg.head_dim)

                    def run_window(tok, key, done):
                        k_win = jnp.zeros(shape, self.kv_dtype)
                        v_win = jnp.zeros(shape, self.kv_dtype)
                        k_done, v_done = done

                        def body(carry, w):
                            tok, k_win, v_win, key = carry
                            key, sub = jax.random.split(key)
                            logits, k_cols, v_cols = \
                                decoder.decode_step_windowed_paged(
                                    params, tok, positions, w, cfg,
                                    partial_fn, k_win, v_win,
                                    k_done=k_done, v_done=v_done)
                            k_win = decoder.put_window_column(
                                k_win, k_cols, w)
                            v_win = decoder.put_window_column(
                                v_win, v_cols, w)
                            nxt = sample(logits, sub, self.sampling)
                            return (nxt, k_win, v_win, key), nxt

                        (tok, k_win, v_win, key), toks = jax.lax.scan(
                            body, (tok, k_win, v_win, key),
                            jnp.arange(w_sz))
                        return tok, key, toks, k_win, v_win

                    tok, done = tokens, (None, None)
                    outs, wins = [], []
                    for widx in range(n_windows):
                        tok, key, toks, k_win, v_win = run_window(
                            tok, key, done)
                        outs.append(toks)
                        wins.append((k_win, v_win))
                        if widx + 1 < n_windows:
                            done = (
                                jnp.concatenate(
                                    [kw for kw, _ in wins], 3),
                                jnp.concatenate(
                                    [vw for _, vw in wins], 3))
                    if n_windows == 1:
                        k_all, v_all = wins[0]
                        toks_all = outs[0]
                    else:
                        k_all = jnp.concatenate(
                            [kw for kw, _ in wins], 3)
                        v_all = jnp.concatenate(
                            [vw for _, vw in wins], 3)
                        toks_all = jnp.concatenate(outs, axis=0)
                    pool_k, pool_v = scatter(
                        pool_k, pool_v, k_all, v_all, sbids, soffs)
                    return toks_all, pool_k, pool_v

                self._decode_paged_fn = jax.jit(
                    _decode_paged_kernel, donate_argnums=(3, 4),
                    static_argnames=("kv_len", "n_windows"))

                def _admit_seeded_paged_kernel(params, tokens,
                                               lengths, pool_k,
                                               pool_v, bids,
                                               pref_lens, sbids,
                                               soffs, key):
                    """Zero-copy seeded admission, kernel route: the
                    matched prefix blocks are scored in place off
                    ``bids`` (never gathered into a view), the fresh
                    suffix KV scatters from compute dtype — the same
                    single compute→kv_dtype cast the reference
                    scratch takes."""
                    def partial_fn(li, q_rows, lns, q_pos):
                        return partial_seed(pool_k, pool_v, bids, li,
                                            q_rows, lns, q_pos)

                    logits, k_new, v_new = decoder.prefill_seeded_paged(
                        params, tokens, lengths, pref_lens, cfg,
                        partial_fn, all_logits=False)
                    pool_k, pool_v = scatter(
                        pool_k, pool_v, k_new, v_new, sbids, soffs)
                    first = sample(logits, key, self.sampling)
                    return first, pool_k, pool_v

                self._admit_seeded_paged_fn = jax.jit(
                    _admit_seeded_paged_kernel, donate_argnums=(3, 4))

                def _verify_paged_kernel(params, tokens, qlens,
                                         positions, pool_k, pool_v,
                                         gbids, sbids, soffs, key, *,
                                         kv_len):
                    tables = gbids[:, :kv_len // self._block]

                    def partial_fn(li, q_rows, lns, q_pos):
                        return partial_seed(pool_k, pool_v, tables,
                                            li, q_rows, lns, q_pos)

                    logits, k_new, v_new = decoder.prefill_seeded_paged(
                        params, tokens, qlens, positions, cfg,
                        partial_fn, all_logits=True)
                    pool_k, pool_v = scatter(
                        pool_k, pool_v, k_new, v_new, sbids, soffs)
                    out, n_accept = verify_draft(
                        logits, tokens[:, 1:], qlens - 1, key,
                        self.sampling)
                    return out, n_accept, pool_k, pool_v

                self._verify_paged_fn = jax.jit(
                    _verify_paged_kernel, donate_argnums=(4, 5),
                    static_argnames=("kv_len",))

                def _chunk_paged_kernel(params, tokens, qlens,
                                        positions, pool_k, pool_v,
                                        gbids, sbids, soffs, key, *,
                                        kv_len):
                    tables = gbids[:, :kv_len // self._block]

                    def partial_fn(li, q_rows, lns, q_pos):
                        return partial_seed(pool_k, pool_v, tables,
                                            li, q_rows, lns, q_pos)

                    # all_logits=False: the last-valid-position
                    # select happens BEFORE the lm_head inside
                    # prefill_seeded_paged — same values as the
                    # reference's take-last over [B, S, V], without
                    # unembedding S-1 discarded positions.
                    last, k_new, v_new = decoder.prefill_seeded_paged(
                        params, tokens, qlens, positions, cfg,
                        partial_fn, all_logits=False)
                    pool_k, pool_v = scatter(
                        pool_k, pool_v, k_new, v_new, sbids, soffs)
                    first = sample(last, key, self.sampling)
                    return first, pool_k, pool_v

                self._chunk_paged_fn = jax.jit(
                    _chunk_paged_kernel, donate_argnums=(4, 5),
                    static_argnames=("kv_len",))

            # ---- KV handoff programs (disaggregated roles) ---------
            # Export gathers a parked slot's blocks into one dense
            # [L, 1, Hkv, NB*blk, Dh] view (plain jit: GLOBAL block
            # ids — GSPMD reads the dp-sharded pool directly); import
            # scatters a handed-off view into freshly allocated
            # blocks of THIS engine's pool. Import donates both pool
            # halves (same no-double-buffer rule as every paged
            # dispatch); export copies out by design — the source
            # blocks are freed right after.
            def _export_kv(pool_k, pool_v, bids):
                return paged_gather_kv(pool_k, pool_v, bids)

            # deliberate non-donation: the export is a pure READ of
            # the LIVE pool — the source blocks keep serving, and are
            # freed host-side only after the handoff object exists —
            # so donating would invalidate buffers the very next
            # dispatch reads.
            # jaxlint: disable=donation
            self._export_fn = jax.jit(_export_kv)

            def _import_kv(pool_k, pool_v, k_new, v_new, sbids,
                           soffs):
                k_upd = k_new.transpose(1, 3, 0, 2, 4)
                v_upd = v_new.transpose(1, 3, 0, 2, 4)
                pk = pool_k.at[:, sbids, :, soffs, :].set(
                    k_upd.astype(pool_k.dtype), mode="drop")
                pv = pool_v.at[:, sbids, :, soffs, :].set(
                    v_upd.astype(pool_v.dtype), mode="drop")
                return pk, pv

            self._import_fn = jax.jit(_import_kv,
                                      donate_argnums=(0, 1))

        # ---- host-side slot state --------------------------------------
        self._free = list(range(num_slots))
        self._active: dict[int, Request] = {}          # slot → request
        self._generated: dict[int, list[int]] = {}     # slot → new tokens
        # Free slots, and slots a chunked prefill is still filling, park
        # at position max_len (out of range): every decode dispatch
        # advances ALL rows and merges their garbage KV at positions0+w,
        # and an out-of-range column drops — an in-range stale position
        # would overwrite what the slot's next occupant writes there.
        self._positions = np.full(num_slots, self.max_len,
                                  dtype=np.int32)
        self._next_tok = np.zeros(num_slots, dtype=np.int32)
        self._t_prefill: dict[int, float] = {}
        self._queue: list[Request] = []
        self._done: dict[int, Completion] = {}
        self._next_id = 0
        #: cumulative wall time spent in admission waves (prefill +
        #: insert + first-token sync) since engine build — benches
        #: snapshot it around a run to split admission from decode.
        self.admitted_s = 0.0
        #: decode dispatches and their wall time (benches read these)
        self.plain_s = 0.0
        self.plain_dispatches = 0
        #: speculative-decoding accounting (spec_stats()): lookups/hits
        #: count draft-index probes; drafted/accepted count draft
        #: tokens through verify; rows counts (slot, verify-dispatch)
        #: pairs; emitted counts tokens harvested from verify. The
        #: ``_row_*`` pair is the per-stream weight-pass ledger across
        #: BOTH decode paths (a verify dispatch is one weight pass per
        #: row; a plain dispatch is one per row per step), from which
        #: tokens_per_weight_pass — the number speculation exists to
        #: move — is computed.
        self.spec_lookups = 0
        self.spec_hits = 0
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_dispatches = 0
        self.spec_rows = 0
        self.spec_emitted_tokens = 0
        self.spec_s = 0.0
        self._row_tokens = 0
        self._row_passes = 0

        # Warm restart LAST: every queue/slot/scheduler structure above
        # must exist before recovered requests resubmit through the
        # normal submit() path (which rebuilds the scheduler ledgers
        # and telemetry spans as a side effect).
        if self.journal is not None and self.journal.depth():
            self._recover_from_journal()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    @classmethod
    def from_checkpoint(cls, path: str, *, dtype=jnp.bfloat16,
                        **engine_kw) -> "GenerationEngine":
        """Build an engine from a checkpoint directory — native (offline-
        quantized, mmap-fast) or HF safetensors (converted in memory).
        Replaces random-weight init as the serving path; the capability of
        the reference's ``factory.py:89-94`` driver dispatch to a real
        model."""
        import ml_dtypes

        from copilot_for_consensus_tpu import checkpoint as ckpt

        np_dtype = np.dtype(dtype) if dtype != jnp.bfloat16 else np.dtype(
            ml_dtypes.bfloat16)
        # Leaves stay numpy (mmap-backed): __init__ device-puts them —
        # shard-by-shard when a mesh is given, whole-tree otherwise.
        cfg, params, meta = ckpt.load_checkpoint(
            path, dtype=str(np_dtype))
        engine_kw.setdefault("eos_id", meta.get("eos_ids",
                                                meta.get("eos_id", 2)))
        return cls(cfg, params, dtype=dtype,
                   quantize=meta.get("quantized") or False, **engine_kw)

    @property
    def _prefix(self):
        """Single-trie compatibility view (every pre-mesh caller):
        shard-aware code paths index ``_prefixes`` by dp shard
        directly. With one shard (mesh=None, or dp=1) this IS the
        engine's prefix cache, unchanged."""
        return self._prefixes[0] if self._prefixes else None

    @property
    def prompt_limit(self) -> int:
        """Longest prompt served without tail-truncation (one decode
        window of cache headroom, capped by the largest prefill bucket).
        Callers with longer prompts should route to the long-context
        engine (``engine/longctx.py``)."""
        if self._pieces:
            # admitted piece by piece: no bucket bounds a prompt
            return self.max_len - self._dispatch_steps
        return min(self.max_len - self._dispatch_steps, self.buckets[-1])

    def submit(self, prompt: list[int], max_new_tokens: int = 256, *,
               cache_eligible_tokens: int | None = None,
               correlation_id: str = "", tenant: str = "",
               priority: str = "interactive",
               deadline_s: float | None = None) -> int:
        """Enqueue a tokenized prompt; returns a request id.

        ``cache_eligible_tokens`` caps how many leading prompt tokens
        the prefix cache may publish when this request completes (the
        summarization path marks its shared-template span here); None
        publishes the whole block-aligned prompt prefix.
        ``correlation_id`` tags the request's telemetry span (and any
        flight-recorder dump / error report naming it) with the
        pipeline event id that caused it. ``tenant``/``priority`` feed
        the scheduler's fairness/shedding policy when one is configured
        — an overloaded scheduler raises :class:`EngineOverloaded`
        HERE, at the door, instead of queueing work it cannot serve
        within SLO (the service layer maps it to HTTP 429 +
        Retry-After). ``deadline_s`` is the per-request wall-clock
        budget: once it expires the request is dropped (queued) or
        retired with its partial output (active), both with
        ``finish_reason="deadline"`` — expired work is never
        computed."""
        if not prompt:
            raise ValueError("empty prompt")
        limit = self.prompt_limit
        if len(prompt) > limit:
            # Keep the tail: instructions/questions sit at the end of RAG
            # prompts. The orchestrator budgets context to avoid this.
            prompt = prompt[-limit:]
            # the publish cap indexed the ORIGINAL prompt; the truncated
            # head no longer matches any cacheable span
            cache_eligible_tokens = 0 if cache_eligible_tokens \
                is not None else None
        if self._sched is not None and not self._journal_recovering:
            # Warm-restart resubmits bypass the shed gate: journaled
            # work was already admitted once, and shedding it at
            # restart would turn a crash into silent loss — exactly
            # what the journal exists to prevent. The recovered burst
            # still queues through the scheduler (fairness holds).
            self._sched.check_admission(
                tenant=tenant, priority=priority,
                prompt_tokens=len(prompt),
                correlation_id=correlation_id)
        rid = self._next_id
        self._next_id += 1
        if self.journal is not None:
            if not self._journal_suppress:
                # Journal BEFORE the request enters any queue: no
                # window where admitted work is journal-invisible.
                # Suppressed for continuation resubmits, whose row is
                # the atomic supersede re-key of the ORIGINAL row —
                # never insert-then-re-key, which would leave two live
                # rows if a crash landed between. Trace parent is
                # captured here so a restart's engine_replay span can
                # parent into the originating pipeline trace.
                from copilot_for_consensus_tpu.obs import (
                    trace as _trace,
                )

                ids = _trace.current_ids()
                self.journal.record_submit(
                    rid, prompt, max_new_tokens,
                    cache_eligible_tokens=cache_eligible_tokens,
                    correlation_id=correlation_id, tenant=tenant,
                    priority=priority,
                    deadline_wall=(time.time() + max(0.0, deadline_s)
                                   if deadline_s is not None else 0.0),
                    trace_id=ids[0] if ids else "",
                    span_id=ids[1] if ids else "")
            self._journal_ckpt[rid] = 0
        if deadline_s is not None:
            self._deadlines_in_use = True
        req = Request(
            rid, list(prompt), max_new_tokens,
            cache_eligible_tokens=cache_eligible_tokens,
            correlation_id=correlation_id, tenant=tenant,
            priority=priority,
            deadline_at=(time.monotonic() + max(0.0, deadline_s)
                         if deadline_s is not None else float("inf")))
        if self._sched is not None:
            self._sched.enqueue(req)
        else:
            self._queue.append(req)
        if self.telemetry is not None:
            self.telemetry.on_submit(rid, len(prompt), correlation_id)
        return rid

    def step(self) -> list[Completion]:
        """Admit queued requests into free slots, run one decode step for
        all active slots, retire finished ones. Returns completions.

        With a scheduler configured, admission is gated by it: the
        closed loop observes this step's signals, at most one wave's
        token budget is released (DRR order, interactive lane first),
        long prompts advance by ONE chunk dispatch, and only then does
        the decode window run — so the per-step prefill work, and with
        it ITL, stays bounded regardless of prompt mix."""
        # Host phases (obs/profile.py:HOST_PHASES): "plan" runs up to
        # each dispatch annotation, the dispatch helpers switch to
        # "commit"/"harvest" after their host fetch, "upkeep" closes
        # the step. One phase is open at a time, none during a dispatch.
        try:
            self._phase("plan", ahead=True)
            self._expire_deadlines()
            if self._sched is not None:
                self._sched_pump()
            if self._pieces:
                self._admit_pieces()
            else:
                self._admit()
            if not self._pieces and (self._chunk_pending
                                     or self._chunking):
                self._phase("plan", ahead=True)
                self._chunk_step()
            if self.paged:
                self.peak_active = max(self.peak_active, self._occupied)
            if self._active:
                self._phase("plan", ahead=True)
                self._decode_once()
            self._phase("upkeep")
            if self.journal is not None:
                self._journal_tick()
            if self.telemetry is not None:
                self.telemetry.gauge_queue(self.queue_depth,
                                           len(self._active))
                if self.role != "both":
                    self.telemetry.gauge_role_occupancy(
                        self.role, self._occupied / self.num_slots
                        if self.num_slots else 0.0)
                if self.paged:
                    # gauges straight off the pool counters — the full
                    # kv_pool_stats() (headroom walk over active slots
                    # + trie) is a stats/bench API, too heavy for
                    # every step
                    self.telemetry.gauge_kv_pool(
                        self._pool.free_blocks,
                        self._pool.pinned_blocks,
                        round(self._pool.fragmentation(
                            self._used_tokens()), 4))
            return self._drain_done()
        finally:
            self._phase(None)

    def _phase(self, name: str | None, *, ahead: bool = False) -> None:
        """Close the open host phase and open ``name`` (None: only
        close; the phase already open: keep it). ``ahead`` tags the
        span with the id the NEXT dispatch will take, else the last
        one's."""
        cur = self._phase_span
        if cur is not None:
            if cur.name == name:
                return
            cur.__exit__(None, None, None)
            self._phase_span = None
        if name is not None and self.telemetry is not None:
            self._phase_span = self.telemetry.host_span(name, ahead=ahead)
            self._phase_span.__enter__()

    def _first_use(self, *key) -> bool:
        """True the first time a dispatch kind runs with this static
        shape key: that step traced, compiled or loaded a program."""
        if key in self.programs_seen:
            return False
        self.programs_seen.add(key)
        return True

    def generate(self, prompts: list[list[int]],
                 max_new_tokens: int = 256, *,
                 cache_eligible_tokens: int | None = None
                 ) -> list[Completion]:
        """Batch convenience: submit all, run to completion, return in
        submission order. Captures a jax.profiler trace when the engine
        was built with ``profile_dir``."""
        from copilot_for_consensus_tpu.obs.profile import maybe_profile

        ids = [self.submit(p, max_new_tokens,
                           cache_eligible_tokens=cache_eligible_tokens)
               for p in prompts]
        results: dict[int, Completion] = {}
        with maybe_profile(self.profile_dir):
            try:
                while len(results) < len(ids):
                    for c in self.step():
                        results[c.request_id] = c
            except Exception as exc:
                # post-mortem before the stack unwinds: the flight
                # recorder names the in-flight requests (correlation
                # ids included) and the last N dispatches
                if self.telemetry is not None:
                    self.telemetry.record_error(exc)
                raise
        return [results[i] for i in ids]

    def generate_text(self, prompts: list[str], tokenizer: Tokenizer,
                      max_new_tokens: int = 256) -> list[str]:
        if self.faults is not None:
            # tokenization is a host boundary of the serving path too —
            # the chaos harness scripts faults against it like any
            # dispatch kind (the summarizer's encode does the same)
            self.faults.check("tokenize")
        comps = self.generate(
            [tokenizer.encode(p, add_bos=True) for p in prompts],
            max_new_tokens)
        return [tokenizer.decode(c.tokens) for c in comps]

    def prefix_stats(self) -> dict:
        """Prefix-cache counters for benches/metrics. ``hit_rate`` is
        over admission lookups; ``prefill_tokens``/``..._saved`` are
        engine-wide prompt-token accounting."""
        out = {
            "enabled": bool(self._prefixes),
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "publish_failures": self.prefix_publish_failures,
        }
        if self._prefixes:
            # aggregate across the per-dp-shard tries (one trie with
            # mesh=None — the original single-cache ledger, unchanged)
            agg: dict[str, int] = {}
            for p in self._prefixes:
                for k, v in p.stats.as_dict().items():
                    agg[k] = agg.get(k, 0) + v
            out.update(agg)
            out["hit_rate"] = (agg["hits"] / agg["lookups"]
                               if agg["lookups"] else 0.0)
            out["blocks_in_use"] = sum(p.blocks_in_use
                                       for p in self._prefixes)
        return out

    def spec_stats(self) -> dict:
        """Speculative-decoding counters for benches/metrics (mirrors
        ``prefix_stats``). ``draft_hit_rate`` is over draft-index
        probes; ``acceptance_rate`` over drafted tokens;
        ``mean_accepted_per_step`` is the per-row average accepted
        draft tokens per verify dispatch; ``tokens_per_weight_pass``
        is the per-stream decode ledger across BOTH paths (1.0 is the
        vanilla wall, >1 is what speculation buys)."""
        out = {
            "enabled": self.spec_decode,
            "lookups": self.spec_lookups,
            "hits": self.spec_hits,
            "draft_hit_rate": (self.spec_hits / self.spec_lookups
                               if self.spec_lookups else 0.0),
            "drafted_tokens": self.spec_drafted_tokens,
            "accepted_tokens": self.spec_accepted_tokens,
            "acceptance_rate": (
                self.spec_accepted_tokens / self.spec_drafted_tokens
                if self.spec_drafted_tokens else 0.0),
            "verify_dispatches": self.spec_dispatches,
            "verify_rows": self.spec_rows,
            "emitted_tokens": self.spec_emitted_tokens,
            "mean_accepted_per_step": (
                self.spec_accepted_tokens / self.spec_rows
                if self.spec_rows else 0.0),
            "weight_row_passes": self._row_passes,
            "weight_row_tokens": self._row_tokens,
            "tokens_per_weight_pass": (
                self._row_tokens / self._row_passes
                if self._row_passes else 0.0),
        }
        return out

    def journal_stats(self) -> dict:
        """Durable-journal counters for benches/metrics (mirrors
        ``prefix_stats``). ``replayed`` counts this process's
        warm-restart resubmissions; ``abandoned`` counts rows that
        could not be resumed (continuation past ``prompt_limit``);
        the rest come from :meth:`EngineJournal.stats`."""
        out = {
            "enabled": self.journal is not None,
            "replayed": self.journal_replayed,
            "abandoned": self.journal_abandoned,
        }
        if self.journal is not None:
            s = self.journal.stats()
            out["depth"] = s["depth"]
            out["journaled"] = s["journaled"]
            out["retired"] = s["retired"]
            out["checkpoints"] = s["checkpoints"]
        return out

    def sched_stats(self) -> dict:
        """Scheduler counters for benches/metrics (mirrors
        ``prefix_stats``/``spec_stats``). ``shed_rate`` is over all
        admission attempts; ``fairness_jain_index`` is Jain's index
        over per-tenant admitted tokens normalized by DRR weight (1.0
        = perfectly weighted-fair)."""
        out = {
            "enabled": self._sched is not None,
            "chunk_dispatches": self.chunk_dispatches,
            "chunk_prefill_tokens": self.chunk_prefill_tokens,
        }
        if self._sched is None:
            return out
        s = self._sched
        attempts = s.shed_total + s.submitted_total
        fairness = s.fairness_snapshot()
        out.update({
            "submitted": s.submitted_total,
            "shed": s.shed_total,
            "shed_rate": s.shed_total / attempts if attempts else 0.0,
            "overload_level": s.overload_level,
            "fairness": {t: round(v, 1) for t, v in fairness.items()},
            "fairness_jain_index": round(
                jain_index(fairness.values()), 4),
            "signals": dict(s.last_signals),
        })
        return out

    @property
    def queue_depth(self) -> int:
        n = (len(self._queue) + len(self._chunk_pending)
             + len(self._chunking))
        if self._sched is not None:
            n += self._sched.queued
        return n

    @property
    def active_count(self) -> int:
        return len(self._active)

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    @contextlib.contextmanager
    def _dispatch_boundary(self, kind: str):
        """The host-side dispatch boundary every device program runs
        under: the fault plane's injection point (engine/faults.py —
        strictly BEFORE the jitted call, never inside traced code) and
        the supervisor's watchdog/outcome surface
        (engine/supervisor.py). On failure the kind is recorded so
        containment can classify without parsing tracebacks."""
        sup = self.supervisor
        if sup is not None:
            sup.begin_dispatch(kind)
        try:
            if self.faults is not None:
                self.faults.check(kind)
            yield
            if sup is not None:
                sup.on_dispatch_ok(kind)
        except Exception as exc:
            self._last_failed_kind = kind
            if isinstance(exc, InjectedFault) \
                    and self.telemetry is not None:
                self.telemetry.on_fault_injected(kind, exc.mode)
            if sup is not None:
                sup.on_dispatch_error(kind, exc)
            raise
        finally:
            if sup is not None:
                sup.end_dispatch(kind)

    def set_slot_cap(self, cap: int) -> None:
        """Occupancy cap (≤ num_slots): admission paths stop filling
        slots beyond it. The supervisor's resource breaker lowers it
        after repeated device resource exhaustion and restores it via
        half-open probes; already-active slots above a lowered cap
        drain naturally."""
        self._slot_cap = max(1, min(self.num_slots, int(cap)))

    @property
    def _occupied(self) -> int:
        # handoff-parked slots hold blocks until exported — they count
        # against the occupancy cap like any live timeline
        return (len(self._active) + len(self._chunking)
                + len(self._handoff))

    def _expire_deadlines(self) -> None:
        """Drop every request whose ``deadline_at`` has passed —
        queued work un-computed (empty completion), active work with
        its partial output — all with ``finish_reason="deadline"``.
        Runs at step start so a deep queue cannot burn dispatches on
        work nobody is waiting for anymore."""
        if not self._deadlines_in_use:
            return    # no deadline ever submitted: skip the queue walk
        now = time.monotonic()
        expired: list[Request] = []
        if self._queue:
            live = [r for r in self._queue if r.deadline_at > now]
            if len(live) != len(self._queue):
                expired += [r for r in self._queue
                            if r.deadline_at <= now]
                self._queue = live
        if self._chunk_pending:
            live = [r for r in self._chunk_pending
                    if r.deadline_at > now]
            if len(live) != len(self._chunk_pending):
                expired += [r for r in self._chunk_pending
                            if r.deadline_at <= now]
                self._chunk_pending = live
        for slot in list(self._chunking):
            req = self._chunking[slot][0]
            if req.deadline_at <= now:
                del self._chunking[slot]
                self._positions[slot] = self.max_len
                if self.paged:
                    self._paged_release_slot(slot)
                self._free.append(slot)
                expired.append(req)
        for slot in list(self._handoff):
            req = self._handoff[slot][0]
            if req.deadline_at <= now:
                del self._handoff[slot]
                m = self._prefix_pins.pop(req.request_id, None)
                if m is not None and self._prefixes:
                    self._prefixes[0].release(m)
                self._paged_release_slot(slot)
                self._free.append(slot)
                expired.append(req)
        if self._sched is not None:
            expired += self._sched.drop_expired(now)
        for req in expired:
            self.deadline_expired += 1
            self._done[req.request_id] = Completion(
                request_id=req.request_id, prompt_len=len(req.prompt),
                tokens=[], finish_reason="deadline")
            if self.telemetry is not None:
                self.telemetry.on_deadline_expired()
                self.telemetry.on_retire(req.request_id, new_tokens=0,
                                         finish_reason="deadline")
        # active slots: retire with whatever was accepted so far (the
        # partial output is real work — only FUTURE compute is dropped)
        for slot, req in list(self._active.items()):
            if req.deadline_at <= now:
                self.deadline_expired += 1
                if self.telemetry is not None:
                    self.telemetry.on_deadline_expired()
                self._retire(slot, "deadline")

    def _admit(self) -> None:
        """Admit every queued request a free slot can take, as ONE
        batched prefill. The r1 per-request path cost a full weight pass
        plus a host sync per admission — on hardware where a device→host
        round trip is tens of ms, 32 admissions burned seconds. Now:
        one prefill over [N, bucket] (reads the weights once), one
        batched cache insert, one sample, one host fetch of the N first
        tokens."""
        if not (self._queue and self._free):
            return
        t0 = time.monotonic()
        batch: list[tuple[int, Request]] = []
        matches: list[Any] = []      # PrefixMatch | None, aligned w/ batch
        # Cap one admission wave at 128 rows AND ~16k prompt tokens:
        # prefill scratch + activations scale with rows × bucket (the
        # f32 swiglu transient is rows·bucket·d_ff·4 bytes — 0.9 GB at
        # 16k tokens, 7.5 GB if 128 rows of 2048-token prompts were
        # padded into one wave), and each extra wave costs a full
        # weight pass. 128×128 keeps the bench's all-at-once arrival in
        # one wave; long-prompt (RAG) waves chunk by token budget.
        # With the prefix cache the budget counts SUFFIX tokens — the
        # cached span never enters the prefill transient, which is
        # exactly why a shared-prefix wave packs more rows per dispatch.
        longest = 0
        # Free-BLOCK accounting (paged engines): the wave takes a
        # request only while its worst-case block footprint fits the
        # pool headroom (free + trie-evictable minus what active work
        # may still claim) — the slot count stops being the capacity
        # bound, the pool is. Sharded engines account PER DP SHARD and
        # place each request on a shard with a free slot, headroom for
        # its worst case, and (tie-break) the longest prefix match in
        # that shard's trie — prefix-aware shard placement.
        if self.paged:
            headroom = {s: self._shard_headroom(s)
                        for s in range(self._dp)}
            free_by_shard: dict[int, list[int]] = {
                s: [] for s in range(self._dp)}
            for sl in self._free:
                free_by_shard[self._slot_shard(sl)].append(sl)
        while (self._queue and self._free and len(batch) < 128
               and self._occupied + len(batch) < self._slot_cap):
            head = self._queue[0]
            digs = None
            if self._prefixes:
                # stat-free peek for the budget decision: a request the
                # budget defers would otherwise be looked up (and
                # counted in hits/tokens_matched) once per wave it
                # waits — inflating the stats the bench reports
                digs = self._req_digests(head)
            shard = 0
            match_len = 0
            if self.paged:
                # Charge the FULL worst case, borrowed prefix included:
                # admitting a seeded row pins its matched blocks (they
                # leave the evictable headroom this gate was computed
                # against), so discounting them would let the invariant
                # go negative by exactly the matched span — the
                # mid-decode KVPoolExhausted this accounting exists to
                # make unreachable.
                need = self._worst_blocks_total(head)
                cand = None
                for s in range(self._dp):
                    if not free_by_shard[s] or need > headroom[s]:
                        continue
                    mt = self._prefixes[s].match_tokens(
                        head.prompt, digests=digs) \
                        if self._prefixes else 0
                    if cand is None or mt > cand[1]:
                        cand = (s, mt)
                if cand is None:
                    break
                shard, match_len = cand
            elif self._prefix is not None:
                match_len = self._prefix.match_tokens(head.prompt,
                                                      digests=digs)
            suffix = len(head.prompt) - match_len
            longest = max(longest, suffix)
            if batch and (len(batch) + 1) * _next_bucket(
                    longest, self.buckets) > self.admission_token_budget:
                break
            m = None
            if self._prefixes:
                m = self._prefixes[shard].lookup(head.prompt,
                                                 digests=digs)
                if m.tokens == 0:       # miss: nothing pinned
                    m = None
            if self.paged:
                headroom[shard] -= self._worst_blocks_total(head)
                slot = free_by_shard[shard].pop(0)
                self._free.remove(slot)
            else:
                slot = self._free.pop(0)
            batch.append((slot, self._queue.pop(0)))
            matches.append(m)
        if not batch:
            return     # occupancy cap (supervisor resource breaker)
        plens = [len(req.prompt) for _, req in batch]
        suffix_lens = [plens[i] - (matches[i].tokens if matches[i]
                                   else 0) for i in range(len(batch))]
        bucket = _next_bucket(max(suffix_lens), self.buckets)
        # Pad N to the next power of two: bounds compile-shape count at
        # log2(num_slots) per bucket. Padded rows prefill garbage and are
        # dropped by the out-of-range slot id in the insert. Sharded
        # waves lay rows out [dp, rows_per_shard] row-major — the dp
        # shard_map splits the row axis, so a row MUST sit in the
        # stripe of the shard that owns its slot's blocks.
        if self.paged and self._dp > 1:
            by_shard: dict[int, list[int]] = {}
            for i, (slot, _req) in enumerate(batch):
                by_shard.setdefault(self._slot_shard(slot),
                                    []).append(i)
            rows_ps = 1
            while rows_ps < max(len(v) for v in by_shard.values()):
                rows_ps *= 2
            n = rows_ps * self._dp
            row_of = {}
            for s, idxs in by_shard.items():
                for j, i in enumerate(idxs):
                    row_of[i] = s * rows_ps + j
        else:
            n = 1
            while n < len(batch):
                n *= 2
            row_of = {i: i for i in range(len(batch))}
        tokens = np.zeros((n, bucket), dtype=np.int32)
        lengths = np.ones((n,), dtype=np.int32)
        slots = np.full((n,), self.num_slots, dtype=np.int32)  # OOB pad
        self._key, sub = jax.random.split(self._key)
        seeded = any(m is not None for m in matches)
        wave_kind = "prefill_seeded" if seeded else "prefill"
        seq = self.telemetry.next_step() if self.telemetry is not None \
            else None
        try:
            if self.paged:
                # Build the rows' block tables BEFORE the dispatch:
                # matched block ids are appended by POINTER (borrowed
                # from the trie, pinned via the row's PrefixMatch —
                # the zero-copy admission), suffix blocks allocate on
                # demand. All-or-nothing per row, so the unwind below
                # can free exactly what was taken.
                for i, (slot, req) in enumerate(batch):
                    tbl = list(matches[i].block_ids) \
                        if matches[i] is not None else []
                    self._owned_from[slot] = len(tbl)
                    need = self._pool.blocks_for(plens[i]) - len(tbl)
                    if need > 0:
                        tbl.extend(self._alloc_blocks(
                            need, self._slot_shard(slot)))
                    self._tables[slot] = tbl
            self._phase(None)
            with step_annotation(wave_kind, seq), \
                    self._dispatch_boundary(wave_kind):
                if seeded:
                    # Seeded wave: rows prefill only their suffix; the
                    # matched blocks gather from the pool inside the
                    # same program. NB pads to a power of two (same
                    # compile-count bounding as N). Paged engines carry
                    # SHARD-LOCAL ids with the per-shard OOB sentinel
                    # (the dp shard_map indexes local pool slices);
                    # the contiguous prefix pool keeps its own ids.
                    bps = self._pool.blocks_per_shard if self.paged \
                        else self._prefix.num_blocks
                    nb = 1
                    while nb < max(len(m.block_ids) for m in matches
                                   if m is not None):
                        nb *= 2
                    bids = np.full((n, nb), bps,
                                   dtype=np.int32)           # OOB pad
                    pref_lens = np.zeros((n,), dtype=np.int32)
                    for i, (slot, req) in enumerate(batch):
                        r = row_of[i]
                        suf = req.prompt[plens[i] - suffix_lens[i]:]
                        tokens[r, :len(suf)] = suf
                        lengths[r] = len(suf)
                        slots[r] = slot
                        if matches[i] is not None:
                            bids[r, :len(matches[i].block_ids)] = \
                                np.asarray(matches[i].block_ids,
                                           dtype=np.int32) % bps \
                                if self.paged \
                                else matches[i].block_ids
                            pref_lens[r] = matches[i].tokens
                    if self.paged:
                        rows = [(row_of[i], self._tables[slot],
                                 plens[i] - suffix_lens[i],
                                 suffix_lens[i])
                                for i, (slot, _r) in enumerate(batch)]
                        sbids, soffs = self._write_maps(rows, bucket, n)
                        first_dev, pk, pv = self._admit_seeded_paged_fn(
                            self.params, jnp.asarray(tokens),
                            jnp.asarray(lengths),
                            self._pool.k, self._pool.v,
                            jnp.asarray(bids),
                            jnp.asarray(pref_lens),
                            jnp.asarray(sbids), jnp.asarray(soffs),
                            sub)
                        self._pool.k, self._pool.v = pk, pv
                    else:
                        first_dev, self._cache = self._admit_seeded_fn(
                            self.params, jnp.asarray(tokens),
                            jnp.asarray(lengths),
                            self._prefix.pool["k"],
                            self._prefix.pool["v"],
                            jnp.asarray(bids.reshape(-1)),
                            jnp.asarray(pref_lens),
                            self._cache, jnp.asarray(slots), sub)
                else:
                    for i, (slot, req) in enumerate(batch):
                        r = row_of[i]
                        tokens[r, :plens[i]] = req.prompt
                        lengths[r] = plens[i]
                        slots[r] = slot
                    if self.paged:
                        rows = [(row_of[i], self._tables[slot], 0,
                                 plens[i])
                                for i, (slot, _r) in enumerate(batch)]
                        sbids, soffs = self._write_maps(rows, bucket, n)
                        first_dev, pk, pv = self._admit_paged_fn(
                            self.params, jnp.asarray(tokens),
                            jnp.asarray(lengths),
                            self._pool.k, self._pool.v,
                            jnp.asarray(sbids), jnp.asarray(soffs),
                            sub)
                        self._pool.k, self._pool.v = pk, pv
                    else:
                        first_dev, self._cache = self._admit_fn(
                            self.params, jnp.asarray(tokens),
                            jnp.asarray(lengths),
                            self._cache, jnp.asarray(slots), sub)
                first = _host_fetch(first_dev)     # the ONE host sync
        except Exception:
            # Lossless unwind (crash containment): the wave's requests
            # were popped from queue+free but never activated — put
            # them back at the queue head (order preserved) and release
            # the lookup pins, so an admit failure costs one retried
            # wave, never a lost request. (Retried lookups re-count in
            # the prefix stats; the savings ledger only counts
            # successful waves, so it stays honest.) Paged rows also
            # hand their freshly allocated owned blocks back.
            for i, (slot, req) in enumerate(batch):
                self._free.append(slot)
                if self.paged:
                    self._paged_release_slot(slot)
                if matches[i] is not None:
                    self._prefix.release(matches[i])
            self._queue[0:0] = [req for _slot, req in batch]
            raise
        prefill_s = time.monotonic() - t0
        self._phase("commit")
        self.admitted_s += prefill_s
        for req in self._active.values():
            req.stalled_s += prefill_s   # sat through this wave
        if self.telemetry is not None:
            self.telemetry.record_step(
                wave_kind, prefill_s, seq=seq, rows=len(batch),
                batch=n, tokens=sum(suffix_lens),
                padded_tokens=n * bucket, route=self._kv_route,
                t_start=t0, new_tokens=len(batch),
                prompt_tokens=sum(suffix_lens),
                first_use=self._first_use(
                    wave_kind, bucket, n, nb if seeded else 0))
        self.prefill_tokens += sum(suffix_lens)
        self.prefill_tokens_saved += sum(
            m.tokens for m in matches if m is not None)
        if self.paged:
            self.paged_admits += len(batch)
            hits = sum(1 for m in matches if m is not None)
            self.zero_copy_admits += hits
            if hits and self.telemetry is not None:
                self.telemetry.on_zero_copy_admits(hits)
        for i, (slot, req) in enumerate(batch):
            tok = int(first[row_of[i]])
            if matches[i] is not None:
                # pinned until retirement: an active slot's seeded
                # prefix blocks must not be evicted out from under a
                # publish that will re-walk the same path
                self._prefix_pins[req.request_id] = matches[i]
            if self.telemetry is not None:
                self.telemetry.on_admit(
                    req.request_id, wave_start=t0,
                    admit_kind="seeded" if matches[i] is not None
                    else "wave",
                    prefix_hit_tokens=(matches[i].tokens
                                       if matches[i] is not None
                                       else 0))
            if (self.role == "prefill" and tok not in self._eos_set
                    and req.max_new_tokens > 1):
                # Disaggregated prefill role: the prompt KV is done and
                # the first token sampled — park for the block-granular
                # handoff instead of decoding here. The slot (and its
                # blocks) stay held until ``take_prefilled`` exports.
                self._park_handoff(slot, req, tok, plens[i], prefill_s)
                continue
            self._active[slot] = req
            self._generated[slot] = [tok]
            self._spec_track(slot, req, tok)
            self._positions[slot] = plens[i]
            self._next_tok[slot] = tok
            self._t_prefill[slot] = prefill_s
            req.decode_started_at = time.monotonic()
            if tok in self._eos_set or req.max_new_tokens <= 1:
                self._retire(slot,
                             "eos" if tok in self._eos_set else "length")

    def _check_eva(self, asked: dict) -> None:
        """Refuse, at construction and by mechanism, every option that
        assumes a sequence's state is one column of keys and values
        per position. No silent fallback."""
        why = {
            "mesh": "the window and summary stores have no sharding "
                    "rules and eva.merge_dispatch's slabs were never "
                    "partitioned",
            "prefix_cache_blocks": "the prefix cache publishes and "
                    "seeds blocks of per-position keys and values; a "
                    "prefix here is summaries plus a partial window",
            "kv_pool_blocks": "the block pool pages one column per "
                    "position; it has no place for summaries",
            "spec_decode": "the verify pass writes k + 1 columns and "
                    "rolls rejected ones back by position; a window "
                    "that was compacted cannot be rolled back",
        }
        for name, reason in why.items():
            if asked[name]:
                raise ValueError(
                    f"{name} cannot serve attention='eva' "
                    f"({self.cfg.name}): {reason}")
        kv = resolve_kv_dtype(asked["kv_dtype"], None)
        if kv is not None and jnp.dtype(kv).itemsize < 2:
            raise ValueError(
                f"kv_dtype {asked['kv_dtype']!r} cannot serve "
                f"attention='eva': summaries pooled from 8-bit keys "
                f"were never held against the reference")
        if asked["quantize"] == "int4":
            raise ValueError(
                "quantize='int4' cannot serve attention='eva': the "
                "fused int4 projections assume the dense decoder's "
                "layer and output head")
        if asked["windows_per_dispatch"] != 1:
            raise ValueError(
                "windows_per_dispatch > 1 cannot serve attention='eva': "
                "a dispatch closes at most one window a slot")
        cfg, w = self.cfg, self.cfg.window_size
        if (cfg.n_kv_heads != cfg.n_heads or cfg.is_moe
                or cfg.sliding_window or w <= 0 or cfg.chunk_size <= 0
                or w % cfg.chunk_size or self.max_len % w):
            raise ValueError(
                f"attention='eva' needs one key/value head per head, a "
                f"dense FFN, window_size a multiple of chunk_size and "
                f"max_len ({self.max_len}) a multiple of window_size "
                f"({w})")

    def _check_mla(self, asked: dict) -> None:
        """Refuse, at construction and by mechanism, every option that
        assumes a key and a value of ``[Hkv, Dh]`` per position, or a
        dense feed-forward. No silent fallback."""
        why = {
            "mesh": "the latent cache and the expert stacks have no "
                    "sharding rules, and experts over a mesh need an "
                    "exchange of tokens that xing.routed_experts does "
                    "not have",
            "prefix_cache_blocks": "the prefix cache publishes and "
                    "seeds blocks of per-head keys and values; a "
                    "prefix here is latent rows shared by all heads "
                    "(and, under a selection, index keys beside them)",
            "kv_pool_blocks": "the block pool pages keys and values of "
                    "[Hkv, Dh]; it has no latent pages and none of "
                    "index keys",
            "spec_decode": "the verify pass scores k + 1 positions "
                    "through decoder.verify_seeded, which knows "
                    "neither latent attention nor the experts",
        }
        for name, reason in why.items():
            if asked[name]:
                raise ValueError(
                    f"{name} cannot serve attention='mla' "
                    f"({self.cfg.name}): {reason}")
        kv = resolve_kv_dtype(asked["kv_dtype"], None)
        if kv is not None and jnp.dtype(kv).itemsize < 2:
            raise ValueError(
                f"kv_dtype {asked['kv_dtype']!r} cannot serve "
                f"attention='mla': an 8-bit latent row feeds every "
                f"head's keys and values at once (and an 8-bit index "
                f"key moves which positions a query reads) and was "
                f"never held against the reference")
        if asked["quantize"] == "int4":
            raise ValueError(
                "quantize='int4' cannot serve attention='mla': the "
                "grouped expert matmul (ops/grouped_matmul.py) and "
                "xing.quantize_params know int8 with a scale per "
                "output channel only")
        if asked["windows_per_dispatch"] != 1:
            raise ValueError(
                "windows_per_dispatch > 1 cannot serve attention='mla': "
                "xing.decode_tokens keeps one window of latent rows a "
                "dispatch")
        cfg = self.cfg
        if (cfg.is_moe or cfg.sliding_window or cfg.hc_mult < 1
                or cfg.kv_lora_rank <= 0 or cfg.q_lora_rank <= 0
                or cfg.qk_rope_head_dim % 2
                or self.max_len % self.buckets[-1]
                or self.max_len % min(xing.KV_BLOCK, self.max_len)):
            raise ValueError(
                f"attention='mla' needs a query and a key/value latent "
                f"rank, an even rotary width, no sliding window, its "
                f"own experts (n_routed_experts, not n_experts) and "
                f"max_len ({self.max_len}) a multiple of the largest "
                f"prefill bucket ({self.buckets[-1]}) and of the "
                f"expansion block ({xing.KV_BLOCK}): a piece's rows "
                f"are written as one slab at a multiple of the bucket")
        first, count = xing.held_experts(cfg)
        if count < 1 or first < 0 or first + count > cfg.n_routed_experts:
            raise ValueError(
                f"held_experts {cfg.held_experts} is not a share of the "
                f"{cfg.n_routed_experts} routed experts")
        if cfg.index_topk and (
                cfg.index_n_heads < 1
                or cfg.index_head_dim < cfg.qk_rope_head_dim):
            raise ValueError(
                f"index_topk {cfg.index_topk} needs index heads "
                f"(index_n_heads {cfg.index_n_heads}) of at least the "
                f"rotary width (index_head_dim {cfg.index_head_dim}, "
                f"qk_rope_head_dim {cfg.qk_rope_head_dim})")

    def _windowed(self) -> bool:
        """Does some query see less than its whole sequence: is the
        sliding window shorter than the cache extent? (What rides
        ``prefill_attention_seeded`` has no absolute-timeline window
        masking and refuses then; the mixed layers need it true.)"""
        return 0 < self.cfg.sliding_window < self.max_len

    def _check_mixed(self, asked: dict) -> None:
        """Refuse, at construction and by mechanism, every option that
        assumes one cache layout for all layers, or a dense
        feed-forward. No silent fallback."""
        why = {
            "mesh": "the two kinds of cache and the expert stacks have "
                    "no sharding rules, and experts over a mesh need an "
                    "exchange of tokens that xing.routed_experts does "
                    "not have",
            "prefix_cache_blocks": "the prefix cache publishes and "
                    "seeds blocks of ONE cache layout; here a prefix is "
                    "whole in the full layers and only its last window "
                    "in the rings, which a later request cannot be "
                    "seeded from",
            "kv_pool_blocks": "the block pool pages one extent per "
                    "layer; it has no pages for a ring that turns over "
                    "beside a full extent",
            "spec_decode": "the verify pass scores k + 1 positions "
                    "through decoder.verify_seeded, which knows neither "
                    "the ring's columns nor the experts",
        }
        for name, reason in why.items():
            if asked[name]:
                raise ValueError(
                    f"{name} cannot serve attention='mixed' "
                    f"({self.cfg.name}): {reason}")
        kv = resolve_kv_dtype(asked["kv_dtype"], None)
        if kv is not None and jnp.dtype(kv).itemsize < 2:
            raise ValueError(
                f"kv_dtype {asked['kv_dtype']!r} cannot serve "
                f"attention='mixed': 8-bit keys and values under 16 "
                f"queries a group were never held against the "
                f"reference")
        if asked["quantize"] == "int4":
            raise ValueError(
                "quantize='int4' cannot serve attention='mixed': the "
                "grouped expert matmul (ops/grouped_matmul.py) and "
                "mixed.quantize_params know int8 with a scale per "
                "output channel only")
        if asked["windows_per_dispatch"] != 1:
            raise ValueError(
                "windows_per_dispatch > 1 cannot serve "
                "attention='mixed': mixed.decode_tokens keeps one "
                "window of columns a dispatch and turns the rings once")
        cfg, piece = self.cfg, self.buckets[-1]
        if (cfg.is_moe or not self._windowed() or not cfg.parallel_block
                or cfg.layer_period < 2
                or not 0 <= cfg.global_member < cfg.layer_period
                or cfg.n_layers % cfg.layer_period
                or cfg.head_dim % 2 or cfg.n_heads % cfg.n_kv_heads
                or cfg.n_shared_experts < 1 or not cfg.tie_embeddings
                or cfg.sliding_window % piece or self.max_len % piece):
            raise ValueError(
                f"attention='mixed' needs whole periods of layer_period "
                f"layers (one of them global), a sliding window shorter "
                f"than max_len, a parallel block, its own experts "
                f"(n_routed_experts, not n_experts) beside shared ones, a "
                f"tied head, and sliding_window ({cfg.sliding_window}) and max_len "
                f"({self.max_len}) multiples of the largest prefill "
                f"bucket ({piece}): a piece's columns are written as "
                f"one slab, in a ring of window + bucket columns as in "
                f"a full extent")
        first, count = xing.held_experts(cfg)
        if count < 1 or first < 0 or first + count > cfg.n_routed_experts:
            raise ValueError(
                f"held_experts {cfg.held_experts} is not a share of the "
                f"{cfg.n_routed_experts} routed experts")

    def _eva_live(self) -> tuple[int, int]:
        """(exact columns, summaries) held by all sequences in slots,
        decoding or mid-admission, right now."""
        held = [int(self._positions[s]) for s in self._active] \
            + [e[1] for e in self._chunking.values()]
        state = [eva.live_state(self.cfg, t) for t in held]
        return sum(a for a, _ in state), sum(b for _, b in state)

    def _eva_read(self) -> int:
        """Window columns and summaries under the blocks that decode
        attention reads for the decoding slots right now: each one's
        live extents rounded up to the kernel's blocks."""
        store = self.max_len // self.cfg.chunk_size
        return sum(
            sum(blocks_read(*eva.live_state(self.cfg,
                                            int(self._positions[s])),
                            self.cfg.window_size, store))
            for s in self._active)

    def _admit_pieces(self) -> None:
        """Admission for attention='eva', 'mla' and 'mixed': queued requests
        take free slots, and ONE wave advances every admitted prompt by
        its next piece — at most the largest bucket and, for 'eva',
        never across a window edge, so a piece that reaches the edge
        leaves summaries behind and the last piece stays exact in the
        window. A prompt of four windows (or four times the largest
        bucket) is four waves, each sharing its weight pass with the
        other rows' pieces and co-scheduled with the decode dispatches
        in between; one program per (rows, bucket), whatever the
        prompt length. Rows pad to a power of two with copies of the
        first row (the same writes twice) and a wave stays under the
        admission token budget."""
        while (self._queue and self._free
               and self._occupied < self._slot_cap):
            self._chunking[self._free.pop(0)] = [
                self._queue.pop(0), 0, time.monotonic()]
        if not self._chunking:
            return
        t0 = time.monotonic()
        # 'mla' and 'mixed' have no edge but the largest bucket's:
        # pieces start at its multiples
        w_sz = self.cfg.window_size if self._eva else self.buckets[-1]
        rows: list[tuple[int, int]] = []          # (slot, piece length)
        bucket = n_pad = 0
        for slot, (req, filled, _t) in self._chunking.items():
            n = min(len(req.prompt) - filled, w_sz - filled % w_sz,
                    self.buckets[-1])
            b = _next_bucket(max([n] + [m for _, m in rows]), self.buckets)
            pad = 1
            while pad < len(rows) + 1:
                pad *= 2
            if rows and pad * b > self.admission_token_budget:
                break
            rows.append((slot, n))
            bucket, n_pad = b, pad
        tokens = np.zeros((n_pad, bucket), dtype=np.int32)
        lens = np.ones((n_pad,), dtype=np.int32)
        pos0 = np.zeros((n_pad,), dtype=np.int32)
        slots = np.zeros((n_pad,), dtype=np.int32)
        for r, (slot, n) in enumerate(rows):
            req, filled, _t = self._chunking[slot]
            tokens[r, :n] = req.prompt[filled:filled + n]
            lens[r], pos0[r], slots[r] = n, filled, slot
        for r in range(len(rows), n_pad):
            tokens[r], lens[r] = tokens[0], lens[0]
            pos0[r], slots[r] = pos0[0], slots[0]
        win_tokens, sum_tokens = self._eva_live() if self._eva \
            else (int(pos0[:len(rows)].sum()), 0)
        self._key, sub = jax.random.split(self._key)
        seq = self.telemetry.next_step() if self.telemetry is not None \
            else None
        # On failure the _chunking entries are untouched (fills only
        # advance after the host fetch): the same pieces go again.
        self._phase(None)
        extra: dict = {}
        with step_annotation("prefill", seq), \
                self._dispatch_boundary("prefill"):
            args = (self.params, jnp.asarray(tokens), jnp.asarray(lens),
                    jnp.asarray(pos0), jnp.asarray(slots), self._cache,
                    sub)
            if self._mla or self._mixed:
                admit = self._admit_mla_fn if self._mla \
                    else self._admit_mixed_fn
                first_dev, self._cache, counts = admit(*args)
                extra = _expert_counts(_host_fetch(counts))
                extra["attn_pairs"] = sum(
                    n * int(pos0[r]) + n * (n + 1) // 2
                    for r, (_slot, n) in enumerate(rows))
                if self._mla:
                    # (padded rows are counted: their rounds are expanded)
                    extra["expand_bytes_moved"] = xing.expand_bytes_moved(
                        (pos0 + lens).tolist(), self.max_len, self.cfg,
                        self.params["tok_emb"].dtype.itemsize)
                if self._mixed:
                    # a window layer's queries read at most a window
                    extra["window_attn_pairs"] = sum(
                        _selected(int(pos0[r]), n,
                                  self.cfg.sliding_window)
                        for r, (_slot, n) in enumerate(rows))
                    (extra["attn_tiles_whole"], extra["attn_tiles_edge"],
                     extra["attn_tiles_dead"]) = mixed.piece_tiles(
                        pos0, lens, bucket, self.max_len,
                        self._cache["window_k"].shape[3], self.cfg)
                if self.cfg.selects:
                    # every pair is scored by the indexer; attention
                    # reads min(index_topk, position + 1) a query
                    extra["live_tokens"] = extra["attn_pairs"]
                    extra["selected_tokens"] = sum(
                        _selected(int(pos0[r]), n, self.cfg.index_topk)
                        for r, (_slot, n) in enumerate(rows))
                    # (padded rows are counted: their keys are read)
                    extra["select_keys_read"] = xing.threshold_keys_read(
                        (pos0 + lens).tolist(), bucket, self.max_len)
            else:
                first_dev, self._cache = self._admit_eva_fn(*args)
            first = _host_fetch(first_dev)
        step_s = time.monotonic() - t0
        self._phase("commit")
        self.admitted_s += step_s
        for req in self._active.values():
            req.stalled_s += step_s       # sat through this wave
        now = time.monotonic()
        fed = sum(n for _, n in rows)
        self.prefill_tokens += fed
        first_tokens = compacted = 0
        for r, (slot, n) in enumerate(rows):
            entry = self._chunking[slot]
            req, _filled, started = entry
            entry[1] += n
            compacted += self._eva and entry[1] % w_sz == 0
            if entry[1] < len(req.prompt):
                continue
            del self._chunking[slot]
            tok = int(first[r])
            first_tokens += 1
            if self.telemetry is not None:
                self.telemetry.on_admit(
                    req.request_id, wave_start=started,
                    admit_kind="wave" if n == len(req.prompt)
                    else "chunked")
            self._active[slot] = req
            self._generated[slot] = [tok]
            self._positions[slot] = len(req.prompt)
            self._next_tok[slot] = tok
            self._t_prefill[slot] = now - started
            req.decode_started_at = now
            if tok in self._eos_set or req.max_new_tokens <= 1:
                self._retire(slot,
                             "eos" if tok in self._eos_set else "length")
        if self.telemetry is not None:
            self.telemetry.record_step(
                "prefill", step_s, seq=seq, rows=len(rows), batch=n_pad,
                tokens=fed, padded_tokens=n_pad * bucket, t_start=t0,
                new_tokens=first_tokens, prompt_tokens=fed,
                first_use=self._first_use("prefill", bucket, n_pad),
                windows_compacted=compacted, window_tokens=win_tokens,
                summary_tokens=sum_tokens, **extra)

    def _req_digests(self, req: Request) -> list:
        if req.block_digests is None:
            req.block_digests = self._prefix.prompt_digests(req.prompt)
        return req.block_digests

    def _reads_live_blocks(self) -> bool:
        """Does the contiguous decode dispatch (``_decode``) read each
        slot's live cache blocks in place, by the slot's own length
        (``ops/dense_attention.py``)? On a TPU that holds the cache on
        one device, as EvaByte's route is chosen
        (``eva._reads_live_blocks``); under a mesh the cache is sharded
        and the XLA prefix route serves, as it does off a TPU, where it
        is what the tests hold the kernel to. Read when the program is
        traced and before every dispatch: it follows the backend, no
        option sets it."""
        return (self.mesh is None and not self.paged
                and not self._pieces
                and dense_attention.serves(self.max_len))

    def _live_blocks_read(self, steps: int) -> int:
        """Cache columns under the blocks that a decode dispatch of
        ``steps`` tokens reads for the decoding slots, summed over its
        steps (the flight recorder's ``state_tokens_read``). Without a
        sliding window every step reads the same blocks: what lay in
        the cache when the dispatch began."""
        pos0 = np.asarray([self._positions[s] for s in self._active],
                          dtype=np.int64)
        return sum(
            dense_attention.blocks_read(int(lo), int(hi), self.max_len)
            for t in range(steps)
            for lo, hi in zip(*dense_attention.live_range(
                pos0, pos0 + t, self.cfg.sliding_window, self.max_len)))

    def _reads_latent_blocks(self) -> bool:
        """Does the latent decode dispatch (``_decode_mla``) read each
        slot's live blocks of the latent cache in place
        (``ops/latent_attention.py``)? As ``_reads_live_blocks``: on a
        TPU (the latent cache is always on one device: ``_check_mla``
        refuses a mesh), read when the program is traced and before
        every dispatch; elsewhere every slot's whole extent is scored
        in XLA, which is what the tests hold the kernel to."""
        return self._mla and latent_attention.serves(self.max_len)

    def _reads_ring_blocks(self) -> bool:
        """Does the mixed decode dispatch (``_decode_mixed``) read each
        slot's live blocks of both kinds of cache in place
        (``ops/dense_attention.py``: a full layer's one range, a ring's
        one or two)? As ``_reads_live_blocks``: on a TPU (the caches
        are always on one device: ``_check_mixed`` refuses a mesh),
        read when the program is traced and before every dispatch;
        elsewhere every column of either cache is scored in XLA, which
        is what the tests hold the kernel's route to."""
        return self._mixed and dense_attention.serves(self.max_len) \
            and dense_attention.serves(
                mixed.ring_len(self.cfg, self.buckets[-1]))

    def _mixed_read(self, steps: int) -> dict:
        """What a mixed decode dispatch of ``steps`` tokens reads in
        ONE layer of each kind, over the decoding slots and summed over
        its steps. The positions a token attends to, itself included
        (``live_tokens`` in a full layer, ``window_live_tokens`` = at
        most ``sliding_window`` of them in a window layer), and the
        cache columns fetched for them (``state_tokens_read``,
        ``window_tokens_read``): on the kernel's route what lies under
        the blocks of the decoding slots (a ring: the blocks of the
        range a token's window leaves of the cached positions, in its
        two runs); on the XLA route every slot's whole extent, or
        ring."""
        ring = mixed.ring_len(self.cfg, self.buckets[-1])
        pos0 = [int(self._positions[s]) for s in self._active]
        live = {"live_tokens": sum(steps * p + steps * (steps + 1) // 2
                                   for p in pos0),
                "window_live_tokens": sum(
                    _selected(p, steps, self.cfg.sliding_window)
                    for p in pos0)}
        if not self._reads_ring_blocks():
            return {**live, "state_tokens_read": steps * self.num_slots
                    * self.max_len,
                    "window_tokens_read": steps * self.num_slots * ring}
        return {
            **live,
            "state_tokens_read": steps * sum(
                dense_attention.blocks_read(0, p, self.max_len)
                for p in pos0),
            "window_tokens_read": sum(
                dense_attention.blocks_read(a, b, ring)
                for p in pos0 for t in range(steps)
                for a, b in mixed.ring_ranges(
                    max(p + t + 1 - self.cfg.sliding_window, 0), p,
                    ring)),
        }

    def _latent_read(self, steps: int) -> int:
        """Latent columns that a decode dispatch of ``steps`` tokens
        reads, summed over its steps (the flight recorder's
        ``state_tokens_read``): on the kernel's route what lies under
        the blocks of the decoding slots, the same in every step (no
        sliding window); on the XLA route every slot's whole extent."""
        if not self._reads_latent_blocks():
            return steps * self.num_slots * self.max_len
        return steps * sum(
            latent_attention.blocks_read(int(self._positions[s]),
                                         self.max_len)
            for s in self._active)

    def _selection_counts(self, steps: int) -> dict:
        """What a decode dispatch of ``steps`` tokens reads under a
        learned selection (``cfg.index_topk``; nothing without one),
        summed over the decoding slots and the steps, the same in every
        layer: ``live_tokens`` the positions a token could read (its
        sequence's length, itself included), ``selected_tokens`` the
        ``min(index_topk, live)`` it does read (``xing.select_step``
        keeps exactly that many), ``index_tokens_read`` the index keys
        the indexer scores for it: every slot's whole extent, in XLA
        on every backend."""
        if not self.cfg.selects:
            return {}
        at = [int(self._positions[s]) for s in self._active]
        return {"live_tokens": sum(steps * n + steps * (steps + 1) // 2
                                   for n in at),
                "selected_tokens": sum(
                    _selected(n, steps, self.cfg.index_topk) for n in at),
                "index_tokens_read":
                    steps * self.num_slots * self.max_len}

    def _kv_bucket(self) -> int:
        """Static attention extent for the next decode dispatch: the
        occupied cache prefix rounded up to 128, so only a handful of
        decode programs ever compile. The dispatch's own fresh KV lives
        in the window/done buffers until the final merge, so the extent
        covers only what was in the cache BEFORE the dispatch. A plain
        decode dispatch that reads live blocks in place takes no cut
        and does not ask (``_decode_once``): its extent is the cache's,
        whatever the lengths, and its program the one."""
        hi = max([int(self._positions[s]) for s in self._active] + [0])
        return self._kv_extent(hi)

    def _kv_extent(self, hi: int) -> int:
        """Bucket an occupied-prefix extent to the 128-aligned static
        set (shared by the decode and chunked-prefill dispatches)."""
        if hi == 0:
            return min(128, self.max_len)
        bucket = min(-(-(hi + 1) // 128) * 128, self.max_len)
        # A bucket below the full extent makes the decode program slice
        # the cache's sequence axis — a STRIDED slice XLA materializes
        # as a full prefix copy, once per dispatch (4.3 GB of scratch at
        # 32x2304 — the rag2k OOM). Near the extent the read saving
        # cannot pay for that copy, so snap to the full cache (slice =
        # identity, zero-copy).
        if bucket * 8 >= self.max_len * 7:
            return self.max_len
        return bucket

    # ------------------------------------------------------------------
    # paged KV host plumbing (kv_pool_blocks > 0)
    # ------------------------------------------------------------------

    def _worst_blocks_total(self, req: Request) -> int:
        """Most blocks this request's slot can ever hold (borrowed +
        owned): its full timeline — prompt, generation budget, and the
        per-dispatch write margin — capped at the cache ceiling. The
        free-block admission accounting reserves this much headroom
        per admitted request, which is what makes mid-decode pool
        exhaustion structurally unreachable (the paged replacement for
        the contiguous engine's per-slot max_len reservation — an
        ACCOUNTING number now, not an allocation)."""
        span = min(len(req.prompt) + req.max_new_tokens
                   + self._write_margin, self.max_len)
        return self._pool.blocks_for(span)

    def _slot_shard(self, slot: int) -> int:
        """The dp shard a slot (and therefore every block in its
        table) lives on. Slots partition contiguously: shard s owns
        slots [s*slots_ps, (s+1)*slots_ps)."""
        return slot // self._slots_ps

    def _shard_headroom(self, shard: int) -> int:
        """Free + trie-evictable blocks of ONE dp shard minus what
        already-admitted work on that shard may still allocate.
        Admission (wave, seeded, chunked, handoff import) only places
        a request on a shard whose headroom fits its worst case."""
        need = 0
        for slot, req in self._active.items():
            if self._slot_shard(slot) == shard:
                need += max(0, self._worst_blocks_total(req)
                            - len(self._tables[slot]))
        for slot, entry in self._chunking.items():
            if self._slot_shard(slot) == shard:
                need += max(0, self._worst_blocks_total(entry[0])
                            - len(self._tables[slot]))
        evictable = self._prefixes[shard].evictable_blocks \
            if self._prefixes else 0
        return (self._pool.free_blocks_shard(shard) + evictable
                - need)

    def _block_headroom(self) -> int:
        """Pool-wide headroom: the sum of per-shard headrooms (one
        shard with mesh=None — the original global accounting)."""
        return sum(self._shard_headroom(s) for s in range(self._dp))

    def _alloc_blocks(self, n: int, shard: int = 0) -> list[int]:
        """Allocate ``n`` pool blocks on ``shard``, reclaiming idle
        prefix-cache leaves of THAT shard's trie first when its free
        list runs short — cached-but-idle prefixes yield to live
        timelines. Raises :class:`KVPoolExhausted` (classified as
        resource exhaustion by the supervisor) if the shard truly
        cannot serve, which the admission accounting makes
        unreachable on the serving path."""
        free = self._pool.free_blocks_shard(shard)
        if n > free and self._prefixes:
            self._prefixes[shard].reclaim(n - free)
        return self._pool.alloc(n, shard=shard)

    def _ensure_blocks(self, slot: int, upto: int) -> None:
        """Grow the slot's table to cover positions [0, upto) with
        blocks from the slot's own dp shard."""
        tbl = self._tables[slot]
        need = self._pool.blocks_for(upto) - len(tbl)
        if need > 0:
            tbl.extend(self._alloc_blocks(need,
                                          self._slot_shard(slot)))

    def _paged_release_slot(self, slot: int, keep=frozenset()) -> None:
        """Return the slot's OWNED blocks to the pool (minus any the
        trie adopted at publish) and clear its table. Borrowed entries
        are the trie's — the request's PrefixMatch release is their
        handback."""
        tbl = self._tables[slot]
        owned = [b for b in tbl[self._owned_from[slot]:]
                 if b not in keep]
        if owned:
            self._pool.free(owned)
        self._tables[slot] = []
        self._owned_from[slot] = 0

    # ------------------------------------------------------------------
    # disaggregated prefill/decode KV handoff (engine/roles.py)
    # ------------------------------------------------------------------

    def _park_handoff(self, slot: int, req: Request, first_tok: int,
                      prompt_len: int, prefill_s: float) -> None:
        """Prefill-role parking: the slot's blocks hold the finished
        prompt KV (plus the sampled first token on the host side)
        until ``take_prefilled`` exports them. Parked slots sit OOB
        for every decode dispatch, exactly like free slots."""
        self._positions[slot] = self.max_len
        self._handoff[slot] = [req, first_tok, prompt_len,
                               time.monotonic(), prefill_s]

    def set_handoff_external(self, n: int) -> None:
        """Report exported-but-unadmitted handoffs queued OUTSIDE this
        engine (the DisaggregatedEngine's pending list) so the
        release hold and the scheduler's ``handoff_backlog`` shed
        signal see the whole handoff pipeline's depth, not just the
        slot-capped parked set."""
        self._handoff_external = max(0, int(n))

    def take_prefilled(self, limit: int | None = None
                       ) -> list[PrefilledHandoff]:
        """Export parked finished prefills as block-granular KV
        handoffs (prefill role). Per slot: gather its blocks dense in
        ONE jitted read (global ids — GSPMD reads the dp-sharded pool
        directly), publish the prompt prefix to the slot's shard trie
        (later same-prefix prompts still hit on the prefill chips),
        then release pins + owned blocks and free the slot. The
        journal row retires here: from the prefill role's point of
        view the work is done once the handoff exists; the decode
        role re-journals it on import (docs/RESILIENCE.md)."""
        out: list[PrefilledHandoff] = []
        for slot in list(self._handoff):
            if limit is not None and len(out) >= limit:
                break
            req, tok, plen, ready_at, prefill_s = \
                self._handoff.pop(slot)
            tbl = list(self._tables[slot])
            nb = self._pool.blocks_for(plen)
            nbp = 1
            while nbp < nb:
                nbp *= 2
            bids = np.full((1, nbp), self._pool.num_blocks,
                           dtype=np.int32)     # OOB pad: clamped, dead
            bids[0, :nb] = tbl[:nb]
            with self._dispatch_boundary("kv_export"):
                kv_k, kv_v = self._export_fn(
                    self._pool.k, self._pool.v, jnp.asarray(bids))
            adopted: frozenset | set = frozenset()
            pc = self._prefixes[self._slot_shard(slot)] \
                if self._prefixes else None
            if pc is not None:
                try:
                    with self._dispatch_boundary("prefix_publish"):
                        adopted = pc.adopt_blocks(
                            req.prompt, tbl, self._owned_from[slot],
                            eligible_tokens=req.cache_eligible_tokens)
                except Exception:
                    self.prefix_publish_failures += 1
                finally:
                    m = self._prefix_pins.pop(req.request_id, None)
                    if m is not None:
                        pc.release(m)
            self._paged_release_slot(slot, keep=adopted)
            self._free.append(slot)
            self.handoff_exported += 1
            if self.telemetry is not None:
                self.telemetry.on_retire(req.request_id, new_tokens=1,
                                         finish_reason="handoff")
            if self.journal is not None:
                self.journal.record_retire(req.request_id)
                self._journal_ckpt.pop(req.request_id, None)
            out.append(PrefilledHandoff(
                request=req, first_token=tok, prompt_len=plen,
                kv_k=kv_k, kv_v=kv_v, blocks=nb, ready_at=ready_at,
                prefill_s=prefill_s))
        return out

    def admit_prefilled(self, handoff: PrefilledHandoff, *,
                        correlation_id: str | None = None
                        ) -> int | None:
        """Decode-role import: accept a handed-off finished prefill.
        Allocates fresh blocks on a dp shard with slot + headroom,
        moves the KV device-to-device onto this engine's mesh,
        scatters it into the new blocks (both pool halves donated),
        and activates the slot at ``positions == prompt_len`` with
        the already-sampled first token — decode continues
        bit-identically (greedy f32) to a co-located engine, because
        the handoff moved the exact KV bytes. Returns the new request
        id, or None when no slot/blocks fit right now — the caller
        re-parks the handoff, which is the backpressure signal toward
        the prefill role."""
        if not self.paged:
            raise ValueError("admit_prefilled requires kv_pool_blocks")
        if self.role == "prefill":
            raise ValueError(
                "admit_prefilled on a prefill-role engine")
        req0 = handoff.request
        plen = handoff.prompt_len
        span = min(plen + req0.max_new_tokens + self._write_margin,
                   self.max_len)
        need = self._pool.blocks_for(span)
        slot = None
        for s in range(self._dp):
            cand = next((x for x in self._free
                         if self._slot_shard(x) == s), None)
            if cand is not None and need <= self._shard_headroom(s) \
                    and self._occupied < self._slot_cap:
                slot = cand
                break
        if slot is None:
            return None
        rid = self._next_id
        self._next_id += 1
        corr = correlation_id if correlation_id is not None \
            else req0.correlation_id
        req = Request(
            rid, list(req0.prompt), req0.max_new_tokens,
            cache_eligible_tokens=req0.cache_eligible_tokens,
            correlation_id=corr, tenant=req0.tenant,
            priority=req0.priority, deadline_at=req0.deadline_at)
        if req.deadline_at != float("inf"):
            # submit() is never called on this path: arm the per-step
            # expiry sweep or a handed-off deadline would never fire
            self._deadlines_in_use = True
        if self.journal is not None:
            self.journal.record_submit(
                rid, req.prompt, req.max_new_tokens,
                cache_eligible_tokens=req.cache_eligible_tokens,
                correlation_id=corr, tenant=req.tenant,
                priority=req.priority)
            self.journal.checkpoint_many(
                [(rid, [handoff.first_token])])
            self._journal_ckpt[rid] = 1
        nb = self._pool.blocks_for(plen)
        tbl = self._alloc_blocks(nb, self._slot_shard(slot))
        width = handoff.kv_k.shape[3]       # NBpad * block
        sbids = np.full((1, width), self._pool.num_blocks,
                        dtype=np.int32)     # GLOBAL ids (plain jit)
        soffs = np.zeros((1, width), dtype=np.int32)
        pos = np.arange(plen)
        sbids[0, :plen] = np.asarray(tbl, dtype=np.int32)[
            pos // self._block]
        soffs[0, :plen] = pos % self._block
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            target = NamedSharding(self.mesh, PartitionSpec())
        else:
            target = jax.devices()[0]
        with self._dispatch_boundary("kv_import"):
            kv_k = jax.device_put(handoff.kv_k, target)
            kv_v = jax.device_put(handoff.kv_v, target)
            self._pool.k, self._pool.v = self._import_fn(
                self._pool.k, self._pool.v, kv_k, kv_v,
                jnp.asarray(sbids), jnp.asarray(soffs))
        self._free.remove(slot)
        self._tables[slot] = tbl
        self._owned_from[slot] = 0
        self.handoff_imported += 1
        now = time.monotonic()
        if self.telemetry is not None:
            self.telemetry.on_submit(rid, len(req.prompt), corr)
            self.telemetry.on_admit(rid, wave_start=now,
                                    admit_kind="handoff")
        tok = int(handoff.first_token)
        self._active[slot] = req
        self._generated[slot] = [tok]
        self._spec_track(slot, req, tok)
        self._positions[slot] = plen
        self._next_tok[slot] = tok
        self._t_prefill[slot] = handoff.prefill_s
        req.decode_started_at = now
        if tok in self._eos_set or req.max_new_tokens <= 1:
            self._retire(slot,
                         "eos" if tok in self._eos_set else "length")
        return rid

    def _gather_bids(self, width_tokens: int) -> "np.ndarray":
        """[num_slots, width/block] block-id view map for a read of
        ``width_tokens`` columns per slot; rows pad OOB past their
        table (clamped garbage, masked by lengths downstream).

        Ids are SHARD-LOCAL (``gid % blocks_per_shard`` — a slot's
        blocks never leave its dp shard, so the modulo IS the base
        subtraction) with the per-shard block count as the OOB
        sentinel: inside the dp shard_map each body indexes only its
        own pool slice. One shard (mesh=None) makes local == global
        and the sentinel == num_blocks, the original map."""
        from copilot_for_consensus_tpu.engine.kv_pool import (
            BLOCK_TABLE_DTYPE,
        )

        bps = self._pool.blocks_per_shard
        nb = -(-width_tokens // self._block)
        arr = np.full((self.num_slots, nb), bps,
                      dtype=BLOCK_TABLE_DTYPE)
        for s in range(self.num_slots):
            tbl = self._tables[s]
            n = min(nb, len(tbl))
            if n:
                arr[s, :n] = np.asarray(
                    tbl[:n], dtype=BLOCK_TABLE_DTYPE) % bps
        return arr

    def _write_maps(self, rows, width: int, n_rows: int):
        """Per-(row, column) pool write maps for one dispatch:
        ``rows`` is ``[(row_idx, table, start_pos, n_valid)]`` — column
        j of row i targets block ``table[(start+j) // block]`` offset
        ``(start+j) % block`` for j < n_valid; everything else carries
        the OOB block id and drops in the scatter. Ids are shard-local
        with the per-shard OOB sentinel (see ``_gather_bids``)."""
        from copilot_for_consensus_tpu.engine.kv_pool import (
            BLOCK_TABLE_DTYPE,
        )

        bps = self._pool.blocks_per_shard
        bids = np.full((n_rows, width), bps, dtype=BLOCK_TABLE_DTYPE)
        offs = np.zeros((n_rows, width), dtype=BLOCK_TABLE_DTYPE)
        for idx, tbl, start, n_valid in rows:
            # columns at/past max_len are dead padding in every
            # dispatch (the contiguous merge drops them OOB); masking
            # them here keeps the map inside the table
            n = min(n_valid, width, self.max_len - start)
            if n <= 0:
                continue
            pos = start + np.arange(n)
            bids[idx, :n] = np.asarray(tbl, dtype=BLOCK_TABLE_DTYPE)[
                pos // self._block] % bps
            offs[idx, :n] = pos % self._block
        return bids, offs

    def _view_width(self, kv_len: int, steps: int) -> int:
        """Gather-view width for a dispatch that reads ``kv_len``
        committed columns and writes up to ``steps`` more: block-
        rounded so the view's reshape stays exact."""
        blk = self._block
        return kv_len + (-(-steps // blk)) * blk

    def _used_tokens(self) -> int:
        """Live cache positions across the pool's owners: committed
        slot timelines (minus their borrowed prefix spans — those live
        in trie blocks and are counted once via node_count, not per
        borrower), chunk fills, and published blocks (always full)."""
        used = sum(int(self._positions[s])
                   - self._owned_from[s] * self._block
                   for s in self._active)
        used += sum(e[1] for e in self._chunking.values())
        # handoff-parked slots hold their prompt KV until exported
        used += sum(h[2] - self._owned_from[s] * self._block
                    for s, h in self._handoff.items())
        used += sum(p.node_count for p in self._prefixes) * self._block
        return used

    def kv_pool_stats(self) -> dict:
        """Paged-KV counters for benches/metrics (mirrors
        ``prefix_stats``). ``fragmentation_ratio`` is internal: the
        reserved-but-dead fraction of allocated blocks;
        ``zero_copy_hit_rate`` is seeded (pointer) admissions over all
        paged admissions. Stats/bench API — the per-step gauges read
        the pool counters directly instead (hot-path economy)."""
        out = {"enabled": self.paged}
        if not self.paged:
            return out
        used_tokens = self._used_tokens()
        out.update({
            "num_blocks": self._pool.num_blocks,
            "block_size": self._block,
            "free_blocks": self._pool.free_blocks,
            "blocks_in_use": self._pool.blocks_in_use,
            "pinned_blocks": self._pool.pinned_blocks,
            "fragmentation_ratio": round(
                self._pool.fragmentation(used_tokens), 4),
            "zero_copy_admits": self.zero_copy_admits,
            "paged_admits": self.paged_admits,
            "zero_copy_hit_rate": (
                self.zero_copy_admits / self.paged_admits
                if self.paged_admits else 0.0),
            "peak_active": self.peak_active,
            "headroom_blocks": self._block_headroom(),
            "dp_shards": self._dp,
            "role": self.role,
            "handoff_parked": len(self._handoff),
            "handoff_exported": self.handoff_exported,
            "handoff_imported": self.handoff_imported,
        })
        return out

    # ------------------------------------------------------------------
    # SLO-aware scheduling (engine/scheduler.py)
    # ------------------------------------------------------------------

    def _sched_cost(self, req: Request) -> int:
        """What this request will actually prefill: its prompt minus
        the prefix-cache match — the DRR charge AND the chunk-vs-wave
        routing size, so cached prompts cost their suffix. Sharded
        engines take the BEST match across the per-dp-shard tries
        (the admission router places the request on that shard)."""
        if not self._prefixes:
            return len(req.prompt)
        digs = self._req_digests(req)
        best = max(p.match_tokens(req.prompt, digests=digs)
                   for p in self._prefixes)
        return max(1, len(req.prompt) - best)

    def _placement_key(self, req: Request):
        """Prefix-cache-aware placement key: the first radix block
        digest. Requests sharing it open with the same block-aligned
        span, so co-scheduling them into one wave makes the whole wave
        ride the seeded path (or publish one shared prefix)."""
        if self._prefix is None:
            return None
        digs = self._req_digests(req)
        return digs[0] if digs else None

    def _sched_pump(self) -> None:
        """One scheduler turn: feed the closed loop, release at most
        one wave's token budget of requests (DRR order), route
        long-prompt cache misses to the chunked-prefill path."""
        sched = self._sched
        backlog = len(self._handoff) + self._handoff_external
        sched.observe(queued=self.queue_depth,
                      active=len(self._active),
                      num_slots=self.num_slots,
                      telemetry=self.telemetry,
                      free_blocks=(self._block_headroom()
                                   if self.paged else None),
                      total_blocks=(self._pool.num_blocks
                                    if self.paged else None),
                      handoff_backlog=(backlog
                                       if self.role == "prefill"
                                       else None))
        if self.role == "prefill" and backlog >= self._handoff_high:
            # Role-aware release hold: finished prefills are piling up
            # faster than the decode role drains them — releasing more
            # waves would only pin pool blocks behind the handoff.
            # Decode ITL on the decode chips stays flat; the shed loop
            # (handoff_backlog signal) handles the door.
            return
        staged = len(self._queue) + len(self._chunk_pending)
        room = len(self._free) - staged
        if room <= 0:
            return
        reqs = sched.select(max_requests=room,
                            token_budget=sched.cfg.prefill_wave_tokens,
                            cost_fn=self._sched_cost,
                            placement_key=self._placement_key)
        ct = sched.cfg.chunk_tokens
        for req in reqs:
            # Prefix-cache hits keep the seeded wave (the pool gather
            # and the chunk continuation cannot share one program);
            # long cache-miss prompts chunk. A hit shows as suffix
            # cost < prompt length — no extra radix walk (the digests
            # are memoized on the Request, but the walk isn't free).
            cost = self._sched_cost(req)
            if self._chunk_ok and cost >= len(req.prompt) \
                    and len(req.prompt) > ct:
                self._chunk_pending.append(req)
            else:
                self._queue.append(req)

    def _chunk_step(self) -> None:
        """One chunked-prefill continuation dispatch: every chunking
        slot advances by at most one chunk-bucket of prompt tokens;
        rows whose prompt completes activate into decode with their
        first token (sampled in-program from the last prompt
        position). Free/active rows park OOB and drop."""
        while self._chunk_pending and self._free \
                and self._occupied < self._slot_cap:
            if self.paged:
                # free-block accounting per dp shard: place the chunk
                # on a shard with a free slot AND headroom for its
                # worst case (first fit, shard order — chunked prompts
                # are cache misses, so there is no prefix to chase)
                need = self._worst_blocks_total(self._chunk_pending[0])
                slot = None
                for s in range(self._dp):
                    cand = next((x for x in self._free
                                 if self._slot_shard(x) == s), None)
                    if cand is not None \
                            and need <= self._shard_headroom(s):
                        slot = cand
                        break
                if slot is None:
                    break   # every shard's pool is full
                self._free.remove(slot)
                req = self._chunk_pending.pop(0)
            else:
                req = self._chunk_pending.pop(0)
                slot = self._free.pop(0)
            self._chunking[slot] = [req, 0, time.monotonic()]
        if not self._chunking:
            return
        t0 = time.monotonic()
        ct = self._chunk_buckets[-1]
        rem_max = max(len(req.prompt) - filled
                      for req, filled, _ in self._chunking.values())
        width = _next_bucket(min(rem_max, ct), self._chunk_buckets)
        tokens = np.zeros((self.num_slots, width), dtype=np.int32)
        qlens = np.ones((self.num_slots,), dtype=np.int32)
        positions = np.full((self.num_slots,), self.max_len,
                            dtype=np.int32)
        fed: dict[int, int] = {}
        hi = 0
        for slot, (req, filled, _started) in self._chunking.items():
            n = min(len(req.prompt) - filled, width)
            tokens[slot, :n] = req.prompt[filled:filled + n]
            qlens[slot] = n
            positions[slot] = filled
            fed[slot] = n
            hi = max(hi, filled)
        self._key, sub = jax.random.split(self._key)
        seq = self.telemetry.next_step() if self.telemetry is not None \
            else None
        # On failure the _chunking entries are untouched (fill offsets
        # only advance after a successful host fetch): an injected
        # fault retries the same chunk next step; a real device failure
        # is evacuated by the supervisor, which restarts chunking
        # requests from token zero (their partial fill is not trusted).
        kv_len = self._kv_extent(hi)
        self._phase(None)
        with step_annotation("prefill_chunk", seq), \
                self._dispatch_boundary("prefill_chunk"):
            with quant.pallas_qmatmul_override(
                    self._decode_pallas_override):
                if self.paged:
                    for slot, n in fed.items():
                        self._ensure_blocks(
                            slot, self._chunking[slot][1] + n)
                    rows = [(slot, self._tables[slot],
                             self._chunking[slot][1], n)
                            for slot, n in fed.items()]
                    sbids, soffs = self._write_maps(rows, width,
                                                    self.num_slots)
                    first_dev, pk, pv = self._chunk_paged_fn(
                        self.params,
                        jnp.asarray(tokens),
                        jnp.asarray(qlens),
                        jnp.asarray(positions),
                        self._pool.k, self._pool.v,
                        jnp.asarray(self._gather_bids(
                            self._view_width(kv_len, width))),
                        jnp.asarray(sbids), jnp.asarray(soffs),
                        sub,
                        kv_len=kv_len,
                    )
                    self._pool.k, self._pool.v = pk, pv
                else:
                    first_dev, self._cache = self._chunk_fn(
                        self.params,
                        jnp.asarray(tokens),
                        jnp.asarray(qlens),
                        jnp.asarray(positions),
                        self._cache,
                        sub,
                        kv_len=kv_len,
                    )
            first = _host_fetch(first_dev)
        step_s = time.monotonic() - t0
        self._phase("commit")
        self.chunk_s += step_s
        self.chunk_dispatches += 1
        for req in self._active.values():
            req.stalled_s += step_s      # sat through this chunk
        now = time.monotonic()
        rows = len(fed)
        first_tokens = 0
        for slot in list(self._chunking):
            entry = self._chunking[slot]
            req, _filled, started = entry
            entry[1] += fed[slot]
            self.prefill_tokens += fed[slot]
            self.chunk_prefill_tokens += fed[slot]
            if entry[1] < len(req.prompt):
                continue
            del self._chunking[slot]
            tok = int(first[slot])
            first_tokens += 1
            if self.telemetry is not None:
                self.telemetry.on_admit(req.request_id,
                                        wave_start=started,
                                        admit_kind="chunked")
            if (self.role == "prefill" and tok not in self._eos_set
                    and req.max_new_tokens > 1):
                # chunked prefills hand off exactly like wave admits
                self._park_handoff(slot, req, tok, len(req.prompt),
                                   now - started)
                continue
            self._active[slot] = req
            self._generated[slot] = [tok]
            self._spec_track(slot, req, tok)
            self._positions[slot] = len(req.prompt)
            self._next_tok[slot] = tok
            self._t_prefill[slot] = now - started
            req.decode_started_at = now
            if tok in self._eos_set or req.max_new_tokens <= 1:
                self._retire(slot,
                             "eos" if tok in self._eos_set else "length")
        if self.telemetry is not None:
            self.telemetry.record_step(
                "prefill_chunk", step_s, seq=seq, rows=rows,
                batch=self.num_slots, tokens=sum(fed.values()),
                padded_tokens=self.num_slots * width,
                route=self._kv_route, t_start=t0,
                new_tokens=first_tokens,
                prompt_tokens=sum(fed.values()),
                first_use=self._first_use("prefill_chunk", kv_len,
                                          width))
            self.telemetry.on_prefill_chunks(rows)

    def _decode_once(self) -> None:
        window = self._dispatch_steps
        # Speculation routes a step to the verify dispatch whenever any
        # active slot's draft index hits (the no-hit slots ride the
        # same program in the k=0 lane). Draft-less steps keep the
        # plain windowed path: a window amortizes the host sync over
        # ``decode_window`` tokens, which beats a 1-token verify
        # dispatch when there is nothing to verify.
        # _spec_allowed consults the supervisor's spec_verify circuit
        # breaker: open → plain decode serves (degraded mode), half-
        # open → exactly this step may probe with a verify dispatch.
        if self.spec_decode and self._active and self._spec_allowed():
            drafts = self._spec_drafts()
            if drafts:
                self._dispatch_verify(drafts)
                return
        self._key, sub = jax.random.split(self._key)
        # a snapshot: the harvest below retires rows out of _active
        active_before = list(self._active.items())
        t0 = time.monotonic()
        seq = self.telemetry.next_step() if self.telemetry is not None \
            else None
        kv_len = self._kv_bucket()
        extra: dict = {}
        if self._reads_live_blocks():
            kv_len = self.max_len
            extra = {"state_tokens_read": self._live_blocks_read(window)}
        elif self._eva:
            win_tokens, sum_tokens = self._eva_live()
            w_sz = self.cfg.window_size
            closing = sum(int(self._positions[s]) % w_sz + window >= w_sz
                          for s in self._active)
            extra = {"window_tokens": win_tokens,
                     "summary_tokens": sum_tokens,
                     "windows_compacted": closing,
                     "state_tokens_read": self._eva_read()}
            # the static key: can some slot's window fill within this
            # dispatch? Only that program holds the compaction; two
            # decode programs in all
            kv_len = closing > 0
        elif self._mla:
            # ONE program whatever the lengths: each slot's latents
            # are scored below its own length
            kv_len = self.max_len
            extra = {"window_tokens": sum(int(self._positions[s])
                                          for s in self._active),
                     "state_tokens_read": self._latent_read(window),
                     **self._selection_counts(window)}
        elif self._mixed:
            # ONE program whatever the lengths, as above
            kv_len = self.max_len
            extra = {"window_tokens": sum(int(self._positions[s])
                                          for s in self._active),
                     **self._mixed_read(window)}
        self._phase(None)
        with step_annotation("decode", seq), \
                self._dispatch_boundary("decode"):
            if self._mla or self._mixed:
                decode = self._decode_mla_fn if self._mla \
                    else self._decode_mixed_fn
                toks, self._cache, counts = decode(
                    self.params, jnp.asarray(self._next_tok),
                    jnp.asarray(self._positions), self._cache, sub)
                toks = _host_fetch(toks)                 # [steps, slots]
                extra.update(_expert_counts(_host_fetch(counts)))
                self.plain_s += time.monotonic() - t0
                self.plain_dispatches += 1
            elif self._eva:
                toks, self._cache = self._decode_eva_fn(
                    self.params, jnp.asarray(self._next_tok),
                    jnp.asarray(self._positions), self._cache, sub,
                    may_close=kv_len)
                toks = _host_fetch(toks)                 # [steps, slots]
                self.plain_s += time.monotonic() - t0
                self.plain_dispatches += 1
            else:
                # the override (if any) is read at TRACE time; holding
                # it around the call bakes the qmatmul route into the
                # decode program without touching other programs/engines
                with quant.pallas_qmatmul_override(
                        self._decode_pallas_override):
                    if self.paged:
                        for slot in self._active:
                            self._ensure_blocks(
                                slot, int(self._positions[slot])
                                + window)
                        rows = [(s, self._tables[s],
                                 int(self._positions[s]), window)
                                for s in self._active]
                        sbids, soffs = self._write_maps(
                            rows, window, self.num_slots)
                        toks, pk, pv = self._decode_paged_fn(
                            self.params,
                            jnp.asarray(self._next_tok),
                            jnp.asarray(self._positions),
                            self._pool.k, self._pool.v,
                            jnp.asarray(self._gather_bids(
                                self._view_width(kv_len, window))),
                            jnp.asarray(sbids), jnp.asarray(soffs),
                            sub,
                            kv_len=kv_len,
                            n_windows=self.windows_per_dispatch,
                        )
                        self._pool.k, self._pool.v = pk, pv
                    else:
                        toks, self._cache = self._decode_fn(
                            self.params,
                            jnp.asarray(self._next_tok),
                            jnp.asarray(self._positions),
                            self._cache,
                            sub,
                            kv_len=kv_len,
                            n_windows=self.windows_per_dispatch,
                        )
                toks = _host_fetch(toks)                 # [steps, slots]
                self.plain_s += time.monotonic() - t0
                self.plain_dispatches += 1
        step_s = time.monotonic() - t0
        self._phase("harvest")
        harvested_total = 0
        for slot, req in active_before:
            req.decode_s_own += step_s
            req.decode_dispatches += 1
            gen = self._generated[slot]
            harvested0 = len(gen)
            finished = None
            for step in range(window):
                tok = int(toks[step, slot])
                gen.append(tok)
                if tok in self._eos_set:
                    finished = "eos"
                    break
                if len(gen) >= req.max_new_tokens:
                    finished = "length"
                    break
            harvested_total += len(gen) - harvested0
            if self.spec_decode:
                # weight-pass ledger + draft index upkeep: a plain
                # window costs one weight pass PER STEP per row
                self._row_tokens += len(gen) - harvested0
                self._row_passes += window
                idx = self._draft_index.get(slot)
                if idx is not None:
                    idx.extend(gen[harvested0:])
            self._positions[slot] += window
            self._next_tok[slot] = int(toks[window - 1, slot])
            # Keep a full window of cache headroom: the next window writes
            # positions [pos, pos+window).
            if (finished is None
                    and self._positions[slot] + window > self.max_len - 1):
                finished = "length"
            if finished:
                self._retire(slot, finished)
        if self.telemetry is not None:
            # the padded grid is window × slots (every row advances
            # every step)
            self.telemetry.record_step(
                "decode", step_s, seq=seq, rows=len(active_before),
                batch=self.num_slots, tokens=harvested_total,
                padded_tokens=window * self.num_slots,
                route=self._kv_route, t_start=t0,
                new_tokens=harvested_total,
                first_use=self._first_use(
                    "decode", kv_len, self.windows_per_dispatch),
                **extra)

    def _spec_allowed(self) -> bool:
        """Spec-decode degraded-mode gate: the supervisor's
        ``spec_verify`` circuit breaker (open after repeated verify
        failures) vetoes the verify dispatch; plain decode serves."""
        sup = self.supervisor
        return sup is None or sup.spec_allowed()

    def _spec_track(self, slot: int, req: Request, first_tok: int
                    ) -> None:
        """Build the stream's draft index at activation (spec engines):
        once over the full context (prompt + first generated token),
        extended per accepted token from then on."""
        if not self.spec_decode:
            return
        idx = NgramDraftIndex(req.prompt, ngram=self.spec_ngram,
                              min_ngram=self.spec_min_ngram)
        idx.extend([first_tok])
        self._draft_index[slot] = idx

    def _spec_bucket(self, n: int) -> int:
        """Largest declared draft length <= n (0 = no draft). Buckets
        are the retrace bound: every verify program's token width is
        some declared length + 1."""
        best = 0
        for k in self.spec_draft_lens:
            if k <= n:
                best = max(best, k)
        return best

    def _spec_drafts(self) -> dict[int, list[int]]:
        """Prompt-lookup drafts for the next verify dispatch: per
        active slot, probe its n-gram index and clamp to the cache
        headroom (the verify writes KV at [pos, pos+k]). The DISPATCH
        width snaps to the declared bucket set (that is the retrace
        bound: the program shape is the width, not the per-row
        lengths), and only fires when some slot's draft reaches a
        nonzero bucket — but once it fires, shorter drafts ride the
        same program for free via the per-row qlens masking, so a
        3-token draft still earns its tokens on an 8-wide wave.
        Empty dict = the step falls through to the plain windowed
        path (a window amortizes the host sync; a 1-token verify
        doesn't)."""
        cands: dict[int, list[int]] = {}
        k_max = 0
        for slot in self._active:
            idx = self._draft_index.get(slot)
            if idx is None:
                continue
            self.spec_lookups += 1
            d = idx.draft(self._spec_max_draft)
            room = self.max_len - 1 - int(self._positions[slot])
            d = d[:max(0, room)]
            if d:
                cands[slot] = d
                k_max = max(k_max, self._spec_bucket(len(d)))
        if k_max == 0:
            return {}
        drafts = {}
        for slot, d in cands.items():
            drafts[slot] = d[:k_max]
            self.spec_hits += 1
            self.spec_drafted_tokens += len(drafts[slot])
        return drafts

    def _dispatch_verify(self, drafts: dict[int, list[int]]) -> None:
        """One verify dispatch: every active slot's committed next
        token plus its (possibly empty) draft, one weight pass,
        exact accept/rewind on the host side."""
        k_max = max(len(d) for d in drafts.values())
        s = k_max + 1
        active_before = list(self._active.items())
        tokens = np.zeros((self.num_slots, s), dtype=np.int32)
        tokens[:, 0] = self._next_tok
        qlens = np.ones((self.num_slots,), dtype=np.int32)
        for slot, d in drafts.items():
            tokens[slot, 1:1 + len(d)] = d
            qlens[slot] = len(d) + 1
        self._key, sub = jax.random.split(self._key)
        t0 = time.monotonic()
        seq = self.telemetry.next_step() if self.telemetry is not None \
            else None
        kv_len = self._kv_bucket()
        self._phase(None)
        with step_annotation("verify", seq), \
                self._dispatch_boundary("verify"):
            with quant.pallas_qmatmul_override(
                    self._decode_pallas_override):
                if self.paged:
                    # The dispatch width s is global; near-cap rows'
                    # columns past max_len are dead padding (the
                    # contiguous merge drops them OOB) — cap the table
                    # growth at max_len so no slot ever allocates past
                    # its admission-time worst-case reservation.
                    for slot in self._active:
                        self._ensure_blocks(
                            slot, min(int(self._positions[slot]) + s,
                                      self.max_len))
                    rows = [(sl, self._tables[sl],
                             int(self._positions[sl]), s)
                            for sl in self._active]
                    sbids, soffs = self._write_maps(rows, s,
                                                    self.num_slots)
                    out_dev, acc_dev, pk, pv = self._verify_paged_fn(
                        self.params,
                        jnp.asarray(tokens),
                        jnp.asarray(qlens),
                        jnp.asarray(self._positions),
                        self._pool.k, self._pool.v,
                        jnp.asarray(self._gather_bids(
                            self._view_width(kv_len, s))),
                        jnp.asarray(sbids), jnp.asarray(soffs),
                        sub,
                        kv_len=kv_len,
                    )
                    self._pool.k, self._pool.v = pk, pv
                else:
                    out_dev, acc_dev, self._cache = self._verify_fn(
                        self.params,
                        jnp.asarray(tokens),
                        jnp.asarray(qlens),
                        jnp.asarray(self._positions),
                        self._cache,
                        sub,
                        kv_len=kv_len,
                    )
            out = _host_fetch(out_dev)                     # [slots, S]
            acc = _host_fetch(acc_dev)                     # [slots]
        step_s = time.monotonic() - t0
        self._phase("harvest")
        self.spec_s += step_s
        self.spec_dispatches += 1
        accepted0 = self.spec_accepted_tokens
        emitted0 = self.spec_emitted_tokens
        for slot, req in active_before:
            req.decode_s_own += step_s
            req.decode_dispatches += 1
            m = int(acc[slot]) + 1        # emitted: accepts + 1 sample
            self.spec_accepted_tokens += m - 1
            self.spec_rows += 1
            self._row_passes += 1
            gen = self._generated[slot]
            emitted = [int(t) for t in out[slot, :m]]
            finished = None
            kept = 0
            for tok in emitted:
                gen.append(tok)
                kept += 1
                if tok in self._eos_set:
                    finished = "eos"
                    break
                if len(gen) >= req.max_new_tokens:
                    finished = "length"
                    break
            self.spec_emitted_tokens += kept
            self._row_tokens += kept
            self._draft_index[slot].extend(emitted[:kept])
            # Rewind/advance the committed length to the accept point:
            # cache columns [pos+m, pos+k_max] hold rejected-draft KV,
            # dead by the prefix-length masking until the next write
            # lands on them (see _verify).
            self._positions[slot] += m
            self._next_tok[slot] = emitted[m - 1]
            if (finished is None
                    and self._positions[slot] + self._dispatch_steps
                    > self.max_len - 1):
                finished = "length"
            if finished:
                self._retire(slot, finished)
        if self.telemetry is not None:
            self.telemetry.record_step(
                "verify", step_s, seq=seq, rows=len(active_before),
                batch=self.num_slots,
                tokens=self.spec_emitted_tokens - emitted0,
                padded_tokens=s * self.num_slots,
                draft_tokens=sum(len(d) for d in drafts.values()),
                accepted_tokens=self.spec_accepted_tokens - accepted0,
                route=self._kv_route, t_start=t0,
                new_tokens=self.spec_emitted_tokens - emitted0,
                first_use=self._first_use("verify", kv_len, s))

    def _retire(self, slot: int, reason: str) -> None:
        self._positions[slot] = self.max_len   # park OOB (see __init__)
        self._draft_index.pop(slot, None)
        req = self._active.pop(slot)
        adopted: frozenset | set = frozenset()
        pc = self._prefixes[self._slot_shard(slot)] \
            if self._prefixes else None
        if pc is not None:
            # Publish BEFORE the slot returns to the free list: the
            # cache still holds this prompt's KV at [0, plen). Prompt
            # KV is temperature-independent (it never saw a sampled
            # token), so it is safe to share across sampling configs.
            # A publish failure is CONTAINED here (counted, pin still
            # released): it loses only this prompt's cache
            # contribution, and must never take the completion — or
            # the whole step — down with it.
            try:
                with self._dispatch_boundary("prefix_publish"):
                    if self.paged:
                        # Refcount handoff, zero device work: the
                        # slot's own shard's trie adopts its
                        # prompt-prefix blocks by id
                        # (docs/ENGINE_PREFIX_CACHE.md).
                        adopted = pc.adopt_blocks(
                            req.prompt, self._tables[slot],
                            self._owned_from[slot],
                            eligible_tokens=req.cache_eligible_tokens)
                    else:
                        pc.publish(
                            req.prompt, self._cache, slot,
                            eligible_tokens=req.cache_eligible_tokens)
            except Exception:
                self.prefix_publish_failures += 1
            finally:
                m = self._prefix_pins.pop(req.request_id, None)
                if m is not None:
                    pc.release(m)
        if self.paged:
            # tail blocks (generated-token KV + unpublished prompt
            # tail) go straight back to the allocator
            self._paged_release_slot(slot, keep=adopted)
        gen = self._generated.pop(slot)
        if gen and gen[-1] in self._eos_set:
            gen = gen[:-1]
        self._done[req.request_id] = Completion(
            request_id=req.request_id,
            prompt_len=len(req.prompt),
            tokens=gen,
            finish_reason=reason,
            prefill_s=self._t_prefill.pop(slot, 0.0),
            decode_s=time.monotonic() - req.decode_started_at,
        )
        if self.telemetry is not None:
            self.telemetry.on_retire(
                req.request_id, new_tokens=len(gen),
                finish_reason=reason, decode_s_own=req.decode_s_own,
                decode_dispatches=req.decode_dispatches,
                stalled_s=req.stalled_s)
            # ledger gauges at retire cadence: the stats are cumulative
            # engine-wide counters, so per-step export buys nothing
            self.telemetry.update_ledgers(
                self.prefix_stats() if self._prefix is not None
                else None,
                self.spec_stats() if self.spec_decode else None)
        self._free.append(slot)

    def _journal_tick(self) -> None:
        """Incremental token checkpoints (engine/journal.py): every
        ``checkpoint_every`` decode steps, and on any step that retired
        a request (``per-retire``: the surviving slots' progress is
        durable before the completed work's rows delete). Also exports
        the journal gauges."""
        j = self.journal
        self._journal_steps += 1
        if self._active and (self._done
                             or self._journal_steps
                             >= j.checkpoint_every):
            self._journal_steps = 0
            pairs = []
            for slot, req in self._active.items():
                gen = self._generated.get(slot)
                if gen:
                    pairs.append((req.request_id, gen))
                    self._journal_ckpt[req.request_id] = len(gen)
            if pairs:
                j.checkpoint_many(pairs)
        if self.telemetry is not None:
            lag = 0
            for slot, req in self._active.items():
                gen = self._generated.get(slot)
                if gen:
                    lag = max(lag, len(gen) - self._journal_ckpt.get(
                        req.request_id, 0))
            self.telemetry.gauge_journal(j.depth(), lag)

    def _drain_done(self) -> list[Completion]:
        out = []
        for c in self._done.values():
            st = self._journal_stitch.pop(c.request_id, None)
            if st is not None:
                # Stitch the continuation back onto the ORIGINAL
                # identity (the runner's _ReplayState move, one level
                # down): the harvester sees one completion with the
                # original prompt length and the full token stream.
                plen, prefix = st
                c = Completion(
                    request_id=c.request_id, prompt_len=plen,
                    tokens=prefix + c.tokens,
                    finish_reason=c.finish_reason,
                    prefill_s=c.prefill_s, decode_s=c.decode_s)
            out.append(c)
        if self.journal is not None and out:
            # Retire at harvest: the row leaves the journal in the same
            # step() call that returns the completion. A SIGKILL inside
            # this window replays the request — at-least-once, absorbed
            # by the pipeline supersede contract (docs/RESILIENCE.md).
            for c in out:
                self.journal.record_retire(c.request_id)
                self._journal_ckpt.pop(c.request_id, None)
        self._done.clear()
        return out

    def _recover_from_journal(self) -> int:
        """Warm restart (construction time, single-owner thread):
        resubmit every unfinished journaled request as a
        prompt+generated continuation through the normal submit path —
        scheduler ledgers and telemetry spans rebuild as a side effect
        — and re-key each row onto its continuation id. Requests whose
        wall-clock deadline expired during the outage complete as
        honest ``finish_reason="deadline"`` drops; continuations that
        no longer fit ``prompt_limit`` are abandoned (counted), never
        silently head-truncated into divergence."""
        from copilot_for_consensus_tpu.obs import trace as _trace

        entries = self.journal.unfinished()
        if not entries:
            return 0
        # Continuation ids must never collide with journaled ids: a
        # fresh engine counts from 0, and a reused id would make the
        # supersede re-key and the retire delete hit the WRONG row.
        self._next_id = max(self._next_id,
                            max(e.request_id for e in entries) + 1)
        now_wall = time.time()
        self._journal_recovering = True
        self._journal_suppress = True
        try:
            for e in entries:
                done = min(len(e.tokens), e.max_new_tokens)
                remaining = e.max_new_tokens - done
                if e.deadline_wall and e.deadline_wall <= now_wall:
                    self.deadline_expired += 1
                    self._done[e.request_id] = Completion(
                        request_id=e.request_id,
                        prompt_len=len(e.prompt),
                        tokens=list(e.tokens)[:done],
                        finish_reason="deadline")
                    continue
                if remaining <= 0:
                    # Fully generated before the crash (which landed
                    # between the final checkpoint and the retire):
                    # emit, don't recompute.
                    self._done[e.request_id] = Completion(
                        request_id=e.request_id,
                        prompt_len=len(e.prompt),
                        tokens=list(e.tokens)[:e.max_new_tokens],
                        finish_reason="length")
                    continue
                prompt = list(e.prompt) + list(e.tokens)
                if len(prompt) > self.prompt_limit:
                    # submit() would head-truncate and the continuation
                    # would diverge from the fault-free stream — honest
                    # abandonment over silent divergence.
                    self.journal.record_abandon(e.request_id)
                    self.journal_abandoned += 1
                    continue
                kw: dict = {}
                if e.deadline_wall:
                    kw["deadline_s"] = e.deadline_wall - now_wall
                rid = self.submit(
                    prompt, remaining,
                    cache_eligible_tokens=e.cache_eligible_tokens,
                    correlation_id=e.correlation_id, tenant=e.tenant,
                    priority=e.priority or "interactive", **kw)
                self.journal.supersede(e.request_id, rid, e.tokens)
                self._journal_stitch[rid] = (len(e.prompt),
                                             list(e.tokens))
                self._journal_ckpt[rid] = 0
                self.journal_recovered.append((rid, e.correlation_id))
                self.journal_replayed += 1
                if self.telemetry is not None:
                    self.telemetry.on_journal_replayed()
                if e.trace_id and e.span_id:
                    # attempt-numbered replay annotation in the
                    # ORIGINATING pipeline trace (never a fresh orphan
                    # root — parentless recoveries skip the span)
                    with _trace.span(
                            "engine_replay", kind="engine_replay",
                            service="engine",
                            correlation_id=e.correlation_id,
                            attempt=e.attempt + 1,
                            parent=(e.trace_id, e.span_id),
                            request_id=rid, restart=True):
                        pass
        finally:
            self._journal_recovering = False
            self._journal_suppress = False
        return self.journal_replayed


# ---------------------------------------------------------------------------
# shardcheck contracts (analysis/shardcheck.py)
# ---------------------------------------------------------------------------


@checkable("generation-engine")
def _shardcheck_generation_engine():
    """Declare the engine's jitted programs on a tiny config (CPU-built
    in well under a second) and verify, by tracing:

    * every ``donate_argnums`` entry aliases a shape/dtype-matching
      output (an undonated slot cache double-allocates per dispatch);
    * admit / seeded admit / decode / verify / prefix-pool publish all
      agree on ONE KV-cache layout (L, Hkv, Dh, dtype) — the cache is
      handed between these five programs every serving step;
    * the prefill bucket table covers the longest admissible prompt
      (``prompt_limit``), and the verify dispatch's token-width table
      covers every declared speculative draft length, both bounding
      compile count.

    The tiny shapes don't weaken the checks: layout agreement, alias
    feasibility, and bucket coverage are shape-RELATION properties, and
    the relations here are the same ones the serving-size engine
    builds.

    Cases carrying an ``hlo=HloSpec(...)`` are ADDITIONALLY lowered and
    compiled by the post-lowering pass (analysis/hlocheck.py): donated
    args must survive as compiled input_output_alias entries, the
    kernel route must lower with no pool-working-set gather, sharded
    dispatches must keep their declared collective counts, and every
    dispatch's compiled memory peak is gated (budgets carry ~2×
    headroom over the measured tiny-config peak — see
    docs/artifacts/HLO_BUDGETS.json for the measured numbers)."""
    import functools

    from copilot_for_consensus_tpu.models.configs import DecoderConfig

    cfg = DecoderConfig(name="shardcheck-tiny", vocab_size=64,
                        d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                        d_ff=64, max_seq_len=128)
    eng = GenerationEngine(cfg, num_slots=4, max_len=64,
                           prefill_buckets=(16, 32), decode_window=4,
                           windows_per_dispatch=1, prefill_chunk=8,
                           prefix_cache_blocks=4,
                           spec_decode=True, spec_draft_lens=(0, 2, 4))

    def aval(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    cache = aval(eng._cache)
    pool = aval(eng._prefix.pool)
    key = jax.random.PRNGKey(0)
    n, bucket, chunk = 4, 16, eng.prefill_chunk
    group = "engine.generation-kv"
    return [
        ContractCase(
            label="admit", fn=eng._admit_fn,
            args=(eng.params, S((n, bucket), i32), S((n,), i32), cache,
                  S((n,), i32), key),
            donate_argnums=(3,), kv_group=group,
            kv_caches=(("slot-cache", cache),),
            buckets=eng.buckets, bucket_covers=(eng.prompt_limit,),
            hlo=HloSpec(peak_bytes=470_000)),
        ContractCase(
            label="admit-seeded", fn=eng._admit_seeded_fn,
            args=(eng.params, S((n, bucket), i32), S((n,), i32),
                  pool["k"], pool["v"], S((n * 2,), i32), S((n,), i32),
                  cache, S((n,), i32), key),
            donate_argnums=(7,), kv_group=group,
            kv_caches=(("slot-cache", cache), ("prefix-pool", pool))),
        ContractCase(
            label="decode",
            fn=functools.partial(eng._decode_fn, kv_len=eng.max_len,
                                 n_windows=1),
            args=(eng.params, S((eng.num_slots,), i32),
                  S((eng.num_slots,), i32), cache, key),
            donate_argnums=(3,), kv_group=group,
            kv_caches=(("slot-cache", cache),),
            hlo=HloSpec(peak_bytes=470_000)),
        ContractCase(
            label="verify",
            # token width = largest declared draft length + 1 (the
            # committed next token); the bucket table is the declared
            # draft-length set so a new spec_draft_lens entry must be
            # covered here or the lane goes red
            fn=functools.partial(eng._verify_fn, kv_len=eng.max_len),
            args=(eng.params,
                  S((eng.num_slots, max(eng.spec_draft_lens) + 1), i32),
                  S((eng.num_slots,), i32), S((eng.num_slots,), i32),
                  cache, key),
            donate_argnums=(4,), kv_group=group,
            kv_caches=(("slot-cache", cache),),
            buckets=tuple(k + 1 for k in eng.spec_draft_lens),
            bucket_covers=(max(eng.spec_draft_lens) + 1,),
            hlo=HloSpec(peak_bytes=510_000)),
        ContractCase(
            label="prefix-publish", fn=eng._prefix._publish_fn,
            args=(pool, cache["k"], cache["v"], S((2,), i32),
                  S((2, chunk), i32), S((2, chunk), i32)),
            donate_argnums=(0,), kv_group=group,
            kv_caches=(("prefix-pool", pool),)),
    ] + _paged_contract_cases(cfg, group) \
        + _paged_mesh_contract_cases(cfg, group)


def _paged_contract_cases(cfg, group):
    """The paged engine's dispatch contracts (kv_pool_blocks > 0):

    * every paged dispatch donates BOTH pool halves (the one long-lived
      KV allocation — a dropped alias double-buffers the whole pool);
    * the pool rides the same ``engine.generation-kv`` layout group as
      the contiguous slot cache (one (L, Hkv, Dh, dtype) convention
      under both layouts — the bit-identity gate depends on it);
    * block tables form their own ``engine.generation-kv-table`` layout
      group: the anchor case declares the canonical
      ``kv_pool.BLOCK_TABLE_DTYPE`` and every dispatch's table must
      match it — flipping the dispatch-side table dtype (the tripwire
      in tests/test_shardcheck.py) is a ``shard-kv-layout`` finding;
    * the KERNEL route's dispatches (``kv_kernel="pallas"``) declare
      into the SAME ``engine.generation-kv`` group with the same
      donations and the same table dtype — the two routes must agree
      on one pool layout, or the ``kv_kernel`` knob would silently
      change serving semantics;
    * block packing forms the ``engine.generation-kv-pack`` layout
      group: the anchor declares the kernel's
      ``ops.paged_attention.KERNEL_BLOCK_PACK``, the pool layout
      declares ``kv_pool.POOL_BLOCK_PACK``, and the dispatch side
      declares its own literal — flipping any one of the three (the
      block-pack tripwire) is a ``shard-kv-layout`` finding;
    * the KERNEL route's dispatches additionally declare an
      ``hlo-materialize`` fingerprint (no gather at/above the pool
      working-set size in the lowered StableHLO) — the gather
      elimination PR 16 shipped is a CONTRACT here, not a test detail,
      and re-introducing a ``paged_gather_kv`` call turns the hlo lane
      red; the reference route declares the same budget family WITHOUT
      the fingerprint (its gather is the design being replaced) so the
      two routes' compiled peaks stay individually gated;
    * the ``program-cache`` case lowers one variant per declared
      bucket (prefill buckets × verify draft widths × the chunk
      program) and pins the distinct-program count to the literal
      cross-product — widening any bucket table without updating the
      declaration is an ``hlo-program-cache`` finding.
    """
    import functools

    from copilot_for_consensus_tpu.engine.kv_pool import (
        BLOCK_TABLE_DTYPE,
        POOL_BLOCK_PACK,
    )
    from copilot_for_consensus_tpu.ops.paged_attention import (
        KERNEL_BLOCK_PACK,
    )

    eng = GenerationEngine(cfg, num_slots=4, max_len=64,
                           prefill_buckets=(16, 32), decode_window=4,
                           windows_per_dispatch=1, prefill_chunk=8,
                           prefix_cache_blocks=4,
                           kv_pool_blocks=16, spec_decode=True,
                           spec_draft_lens=(0, 2, 4))
    eng_k = GenerationEngine(cfg, num_slots=4, max_len=64,
                             prefill_buckets=(16, 32), decode_window=4,
                             windows_per_dispatch=1, prefill_chunk=8,
                             prefix_cache_blocks=4,
                             kv_pool_blocks=16, kv_kernel="pallas",
                             spec_decode=True, spec_draft_lens=(0, 2, 4))
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    table_dtype = jnp.int32       # dispatch-side block-table dtype
    block_pack = 128              # dispatch-side kernel lane packing
    pool = {"k": S(eng._pool.k.shape, eng._pool.k.dtype),
            "v": S(eng._pool.v.shape, eng._pool.v.dtype)}
    key = jax.random.PRNGKey(0)
    n, bucket = 4, 16
    b = eng.num_slots
    w = eng._dispatch_steps
    s_v = max(eng.spec_draft_lens) + 1
    kv_len = 64
    nb_view = eng._view_width(kv_len, w) // eng._block
    tgroup = "engine.generation-kv-table"
    pgroup = "engine.generation-kv-pack"
    # hlo-materialize fingerprint: one gather materializing the pool
    # working set (L × B × Hkv × kv_len × Dh result elements) is the
    # paged_gather_kv pattern the kernel route exists to eliminate;
    # legitimate small gathers (embedding lookup: B × bucket × d_model
    # = 2048 elements here) sit well below the threshold
    ws_elems = cfg.n_layers * b * cfg.n_kv_heads * kv_len * cfg.head_dim
    no_gather = (("gather", ws_elems),)

    def tbl(rows, width):
        return S((rows, width), table_dtype)

    return [
        # the canonical table layout, declared FIRST so it is the
        # group's reference signature (kv_pool.BLOCK_TABLE_DTYPE)
        ContractCase(
            label="paged-table-layout", kv_group=tgroup,
            kv_caches=(("block-table",
                        {"table": S((b, nb_view),
                                    jnp.dtype(BLOCK_TABLE_DTYPE))}),)),
        ContractCase(
            label="admit-paged", fn=eng._admit_paged_fn,
            args=(eng.params, S((n, bucket), i32), S((n,), i32),
                  pool["k"], pool["v"], tbl(n, bucket), tbl(n, bucket),
                  key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool", pool),),
            buckets=eng.buckets, bucket_covers=(eng.prompt_limit,),
            # admission scatters into the pool; it must never gather
            # the working set back out on EITHER route
            hlo=HloSpec(forbid_ops=no_gather, peak_bytes=440_000)),
        ContractCase(
            label="admit-seeded-paged", fn=eng._admit_seeded_paged_fn,
            args=(eng.params, S((n, bucket), i32), S((n,), i32),
                  pool["k"], pool["v"], S((n, 2), i32), S((n,), i32),
                  tbl(n, bucket), tbl(n, bucket), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool", pool),)),
        ContractCase(
            label="decode-paged",
            fn=functools.partial(eng._decode_paged_fn, kv_len=kv_len,
                                 n_windows=1),
            args=(eng.params, S((b,), i32), S((b,), i32),
                  pool["k"], pool["v"],
                  S((b, nb_view), jnp.dtype(BLOCK_TABLE_DTYPE)),
                  tbl(b, w), tbl(b, w), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool", pool),),
            # the REFERENCE route gathers its working set by design —
            # no forbid_ops; the peak budget documents (and caps) the
            # materialization cost the kernel route removes (measured
            # 327K vs the kernel decode's 189K)
            hlo=HloSpec(peak_bytes=650_000)),
        ContractCase(
            label="decode-paged-table", kv_group=tgroup,
            kv_caches=(("block-table",
                        {"table": S((b, nb_view), table_dtype)}),)),
        ContractCase(
            label="verify-paged",
            fn=functools.partial(eng._verify_paged_fn, kv_len=kv_len),
            args=(eng.params, S((b, s_v), i32), S((b,), i32),
                  S((b,), i32), pool["k"], pool["v"],
                  S((b, eng._view_width(kv_len, s_v) // eng._block),
                    jnp.dtype(BLOCK_TABLE_DTYPE)),
                  tbl(b, s_v), tbl(b, s_v), key),
            donate_argnums=(4, 5), kv_group=group,
            kv_caches=(("kv-pool", pool),),
            buckets=tuple(k + 1 for k in eng.spec_draft_lens),
            bucket_covers=(max(eng.spec_draft_lens) + 1,),
            hlo=HloSpec(peak_bytes=700_000)),
        ContractCase(
            label="chunk-paged",
            fn=functools.partial(eng._chunk_paged_fn, kv_len=kv_len),
            args=(eng.params, S((b, eng._block), i32), S((b,), i32),
                  S((b,), i32), pool["k"], pool["v"],
                  S((b, eng._view_width(kv_len, eng._block)
                     // eng._block), jnp.dtype(BLOCK_TABLE_DTYPE)),
                  tbl(b, eng._block), tbl(b, eng._block), key),
            donate_argnums=(4, 5), kv_group=group,
            kv_caches=(("kv-pool", pool),)),
        # ---- Pallas kernel route (kv_kernel="pallas"): the same four
        # gathering dispatches rebound over the in-place kernel, same
        # signatures, same donations, same pool layout group — route
        # selection must never change the serving contract ----------
        ContractCase(
            label="admit-seeded-paged-kernel",
            fn=eng_k._admit_seeded_paged_fn,
            args=(eng_k.params, S((n, bucket), i32), S((n,), i32),
                  pool["k"], pool["v"], S((n, 2), i32), S((n,), i32),
                  tbl(n, bucket), tbl(n, bucket), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool", pool),),
            hlo=HloSpec(forbid_ops=no_gather, peak_bytes=460_000)),
        ContractCase(
            label="decode-paged-kernel",
            fn=functools.partial(eng_k._decode_paged_fn, kv_len=kv_len,
                                 n_windows=1),
            args=(eng_k.params, S((b,), i32), S((b,), i32),
                  pool["k"], pool["v"],
                  S((b, nb_view), jnp.dtype(BLOCK_TABLE_DTYPE)),
                  tbl(b, w), tbl(b, w), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool", pool),),
            # PR 16's gather-elimination guarantee, as a contract: the
            # kernel decode lowers with NO working-set gather
            hlo=HloSpec(forbid_ops=no_gather, peak_bytes=380_000)),
        ContractCase(
            label="decode-paged-kernel-table", kv_group=tgroup,
            kv_caches=(("block-table",
                        {"table": S((b, nb_view), table_dtype)}),)),
        ContractCase(
            label="verify-paged-kernel",
            fn=functools.partial(eng_k._verify_paged_fn,
                                 kv_len=kv_len),
            args=(eng_k.params, S((b, s_v), i32), S((b,), i32),
                  S((b,), i32), pool["k"], pool["v"],
                  S((b, eng_k._view_width(kv_len, s_v) // eng_k._block),
                    jnp.dtype(BLOCK_TABLE_DTYPE)),
                  tbl(b, s_v), tbl(b, s_v), key),
            donate_argnums=(4, 5), kv_group=group,
            kv_caches=(("kv-pool", pool),),
            buckets=tuple(k + 1 for k in eng_k.spec_draft_lens),
            bucket_covers=(max(eng_k.spec_draft_lens) + 1,),
            hlo=HloSpec(forbid_ops=no_gather, peak_bytes=360_000)),
        ContractCase(
            label="chunk-paged-kernel",
            fn=functools.partial(eng_k._chunk_paged_fn, kv_len=kv_len),
            args=(eng_k.params, S((b, eng_k._block), i32),
                  S((b,), i32), S((b,), i32), pool["k"], pool["v"],
                  S((b, eng_k._view_width(kv_len, eng_k._block)
                     // eng_k._block), jnp.dtype(BLOCK_TABLE_DTYPE)),
                  tbl(b, eng_k._block), tbl(b, eng_k._block), key),
            donate_argnums=(4, 5), kv_group=group,
            kv_caches=(("kv-pool", pool),),
            hlo=HloSpec(forbid_ops=no_gather, peak_bytes=380_000)),
        # ---- block packing (engine.generation-kv-pack): kernel-side
        # KERNEL_BLOCK_PACK (anchor), pool-side POOL_BLOCK_PACK, and
        # the dispatch-side literal must all name the same lane width
        # — the pool layout, the kernel BlockSpecs, and the engine's
        # bucket alignment are compiled against it independently ----
        ContractCase(
            label="kernel-block-pack-layout", kv_group=pgroup,
            kv_caches=(("block-pack",
                        {"pack": S((KERNEL_BLOCK_PACK,), i32)}),)),
        ContractCase(
            label="pool-block-pack", kv_group=pgroup,
            kv_caches=(("block-pack",
                        {"pack": S((POOL_BLOCK_PACK,), i32)}),)),
        ContractCase(
            label="dispatch-block-pack", kv_group=pgroup,
            kv_caches=(("block-pack",
                        {"pack": S((block_pack,), i32)}),)),
        # ---- program-cache cardinality: one variant per declared
        # bucket; the distinct compiled-program count must equal the
        # LITERAL cross-product below. Widening prefill_buckets or
        # spec_draft_lens (or chunking by a new width) without
        # updating this declaration is an hlo-program-cache finding —
        # the silent version of that drift is a retrace explosion ----
        ContractCase(
            label="program-cache",
            hlo=HloSpec(
                variants=tuple(
                    (f"admit@{bk}", eng._admit_paged_fn,
                     (eng.params, S((n, bk), i32), S((n,), i32),
                      pool["k"], pool["v"], tbl(n, bk), tbl(n, bk),
                      key))
                    for bk in eng.buckets
                ) + tuple(
                    (f"verify@{k + 1}",
                     functools.partial(eng._verify_paged_fn,
                                       kv_len=kv_len),
                     (eng.params, S((b, k + 1), i32), S((b,), i32),
                      S((b,), i32), pool["k"], pool["v"],
                      S((b, eng._view_width(kv_len, k + 1)
                         // eng._block),
                        jnp.dtype(BLOCK_TABLE_DTYPE)),
                      tbl(b, k + 1), tbl(b, k + 1), key))
                    for k in eng.spec_draft_lens
                ) + (
                    ("chunk@block",
                     functools.partial(eng._chunk_paged_fn,
                                       kv_len=kv_len),
                     (eng.params, S((b, eng._block), i32),
                      S((b,), i32), S((b,), i32), pool["k"],
                      pool["v"],
                      S((b, eng._view_width(kv_len, eng._block)
                         // eng._block),
                        jnp.dtype(BLOCK_TABLE_DTYPE)),
                      tbl(b, eng._block), tbl(b, eng._block), key)),
                ),
                # 2 prefill buckets + 3 verify draft widths + 1 chunk
                expected_programs=6)),
    ]


def _paged_mesh_contract_cases(cfg, group):
    """The MESH-sharded paged dispatch contracts (kv_pool_blocks > 0 on
    a dp×tp mesh — ISSUE 15):

    * every sharded dispatch still donates BOTH pool halves through
      the outer jit (the shard_map indirection must not cost the pool
      a double-buffer);
    * the sharded pool rides the same ``engine.generation-kv`` layout
      group as the single-device pool and the contiguous slot cache —
      dp/tp sharding must never change the (L, Hkv, Dh, dtype)
      convention the bit-identity gate depends on;
    * the pool's PartitionSpec is declared as a divisibility contract:
      the BLOCK axis must divide dp (per-shard allocators own equal
      slices); kv-heads replicate here (tiny config: tp ∤ Hkv — the
      same fallback rule the engine applies);
    * the dispatch-side block tables keep the canonical
      ``kv_pool.BLOCK_TABLE_DTYPE`` under dp sharding
      (``engine.generation-kv-table`` group membership);
    * the KV handoff import (disaggregated roles) donates both pool
      halves like every other pool writer;
    * the two decode dispatches (reference and kernel route) declare
      exact ``hlo-collective-budget`` counts: GSPMD reshard insertion
      — the RoPE-miscompile class — shows up as a changed collective
      count in the compiled program long before a TPU run shows it as
      a wrong answer or a step-time cliff. The budgets are the
      compiled ground truth of this mesh/config; a legitimate
      partitioning change updates them HERE, next to the declaration,
      never in the baseline file.
    """
    import functools

    from copilot_for_consensus_tpu.analysis.contracts import (
        require_devices,
    )
    from copilot_for_consensus_tpu.engine.kv_pool import (
        BLOCK_TABLE_DTYPE,
    )
    from copilot_for_consensus_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    require_devices(8)
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    eng = GenerationEngine(cfg, num_slots=4, max_len=64,
                           prefill_buckets=(16, 32), decode_window=4,
                           windows_per_dispatch=1, prefill_chunk=8,
                           prefix_cache_blocks=4, kv_pool_blocks=32,
                           spec_decode=True, spec_draft_lens=(0, 2, 4),
                           mesh=mesh)
    eng_k = GenerationEngine(cfg, num_slots=4, max_len=64,
                             prefill_buckets=(16, 32), decode_window=4,
                             windows_per_dispatch=1, prefill_chunk=8,
                             prefix_cache_blocks=4, kv_pool_blocks=32,
                             kv_kernel="pallas", spec_decode=True,
                             spec_draft_lens=(0, 2, 4), mesh=mesh)
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    pool = {"k": S(eng._pool.k.shape, eng._pool.k.dtype),
            "v": S(eng._pool.v.shape, eng._pool.v.dtype)}
    key = jax.random.PRNGKey(0)
    n, bucket = 4, 16
    b = eng.num_slots
    w = eng._dispatch_steps
    s_v = max(eng.spec_draft_lens) + 1
    kv_len = 64
    nb_view = eng._view_width(kv_len, w) // eng._block
    tgroup = "engine.generation-kv-table"
    # the pool's PartitionSpec as a divisibility contract: blocks/dp
    pool_logical = {"k": (None, "kv_blocks", "kv_heads", None, None),
                    "v": (None, "kv_blocks", "kv_heads", None, None)}
    pool_rules = {"kv_blocks": "dp",
                  # tiny config: tp ∤ Hkv → replicated, the engine's
                  # own fallback (BlockPool.spec does the same)
                  "kv_heads": None}

    def tbl(rows, width):
        return S((rows, width), jnp.dtype(BLOCK_TABLE_DTYPE))

    return [
        ContractCase(
            label="pool-partition-spec", mesh=mesh, rules=pool_rules,
            logical=(("kv-pool-mesh", pool, pool_logical),)),
        ContractCase(
            label="admit-paged-mesh", fn=eng._admit_paged_fn,
            args=(eng.params, S((n, bucket), i32), S((n,), i32),
                  pool["k"], pool["v"], tbl(n, bucket),
                  tbl(n, bucket), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),),
            buckets=eng.buckets, bucket_covers=(eng.prompt_limit,)),
        ContractCase(
            label="admit-seeded-paged-mesh",
            fn=eng._admit_seeded_paged_fn,
            args=(eng.params, S((n, bucket), i32), S((n,), i32),
                  pool["k"], pool["v"], tbl(n, 2), S((n,), i32),
                  tbl(n, bucket), tbl(n, bucket), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),)),
        ContractCase(
            label="decode-paged-mesh",
            fn=functools.partial(eng._decode_paged_fn, kv_len=kv_len,
                                 n_windows=1),
            args=(eng.params, S((b,), i32), S((b,), i32),
                  pool["k"], pool["v"], tbl(b, nb_view),
                  tbl(b, w), tbl(b, w), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),),
            # the view is dp-sharded over slots; merge_window's slab
            # scatter batches over that axis, so it partitions with no
            # collective (the per-column scatter it replaced all-gathered
            # updates and indices: 6 all-gathers, 8 permutes then)
            hlo=HloSpec(
                collectives={"all-reduce": 4, "all-gather": 2,
                             "collective-permute": 4},
                peak_bytes=240_000)),
        ContractCase(
            label="decode-paged-mesh-table", kv_group=tgroup,
            kv_caches=(("block-table",
                        {"table": tbl(b, nb_view)}),)),
        ContractCase(
            label="verify-paged-mesh",
            fn=functools.partial(eng._verify_paged_fn, kv_len=kv_len),
            args=(eng.params, S((b, s_v), i32), S((b,), i32),
                  S((b,), i32), pool["k"], pool["v"],
                  tbl(b, eng._view_width(kv_len, s_v) // eng._block),
                  tbl(b, s_v), tbl(b, s_v), key),
            donate_argnums=(4, 5), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),),
            buckets=tuple(k + 1 for k in eng.spec_draft_lens),
            bucket_covers=(max(eng.spec_draft_lens) + 1,)),
        ContractCase(
            label="chunk-paged-mesh",
            fn=functools.partial(eng._chunk_paged_fn, kv_len=kv_len),
            args=(eng.params, S((b, eng._block), i32), S((b,), i32),
                  S((b,), i32), pool["k"], pool["v"],
                  tbl(b, eng._view_width(kv_len, eng._block)
                      // eng._block),
                  tbl(b, eng._block), tbl(b, eng._block), key),
            donate_argnums=(4, 5), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),)),
        ContractCase(
            label="kv-handoff-import", fn=eng._import_fn,
            args=(pool["k"], pool["v"],
                  S((cfg.n_layers, 1, cfg.n_kv_heads, 16,
                     cfg.head_dim), eng.kv_dtype),
                  S((cfg.n_layers, 1, cfg.n_kv_heads, 16,
                     cfg.head_dim), eng.kv_dtype),
                  S((1, 16), i32), S((1, 16), i32)),
            donate_argnums=(0, 1), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),),
            hlo=HloSpec(peak_bytes=140_000)),
        # ---- kernel route under the mesh: the shard-mapped partial
        # keeps the dp-sharded pool donated and the shard-local block
        # tables on the canonical dtype (same layout groups — the
        # route knob changes how blocks are read, never the sharded
        # pool contract) -------------------------------------------
        ContractCase(
            label="decode-paged-mesh-kernel",
            fn=functools.partial(eng_k._decode_paged_fn,
                                 kv_len=kv_len, n_windows=1),
            args=(eng_k.params, S((b,), i32), S((b,), i32),
                  pool["k"], pool["v"], tbl(b, nb_view),
                  tbl(b, w), tbl(b, w), key),
            donate_argnums=(3, 4), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),),
            # the kernel route reads pool blocks in place (no view
            # gather); counts are the compiled ground truth under the
            # installed jax, like the reference route's above
            hlo=HloSpec(
                collectives={"all-reduce": 3, "all-gather": 8},
                peak_bytes=175_000)),
        ContractCase(
            label="decode-paged-mesh-kernel-table", kv_group=tgroup,
            kv_caches=(("block-table",
                        {"table": tbl(b, nb_view)}),)),
        ContractCase(
            label="verify-paged-mesh-kernel",
            fn=functools.partial(eng_k._verify_paged_fn,
                                 kv_len=kv_len),
            args=(eng_k.params, S((b, s_v), i32), S((b,), i32),
                  S((b,), i32), pool["k"], pool["v"],
                  tbl(b, eng_k._view_width(kv_len, s_v)
                      // eng_k._block),
                  tbl(b, s_v), tbl(b, s_v), key),
            donate_argnums=(4, 5), kv_group=group,
            kv_caches=(("kv-pool-mesh", pool),),
            buckets=tuple(k + 1 for k in eng_k.spec_draft_lens),
            bucket_covers=(max(eng_k.spec_draft_lens) + 1,)),
    ]
