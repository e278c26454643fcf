"""jax.profiler integration: what the chip and the host are doing, by name.

SURVEY.md §5 assigns the tracing/profiling subsystem to the TPU build
(the reference's per-event correlation_id covers the host side; device
time needs the XLA profiler). A trace is captured with

    with maybe_profile("var/traces"):            # or None → no-op
        engine.generate(...)

(config ``llm.profile_dir`` → ``GenerationEngine(profile_dir=...)``;
off by default) and is Perfetto/TensorBoard-compatible. Three kinds of
name go into it, each declared here and nowhere else:

* **Dispatch steps** — ``step_annotation(kind, seq)``: a
  ``StepTraceAnnotation`` around each engine dispatch whose
  ``step_num`` is the flight recorder's step id
  (``engine/telemetry.py:StepRecord.seq``), so a device-trace row and
  a host-side ``StepRecord`` name the SAME step. Read by
  ``benchmark/harness/trace_reduce.py`` (device time per step, idle
  gaps inside a dispatch).
* **Device scopes** — ``SCOPES``, ``EVA_SCOPES``, ``XING_SCOPES``,
  ``GLM_SCOPES``, ``MIXED_SCOPES`` / ``scope(name)``:
  ``jax.named_scope``
  around the code that does each thing inside the jitted programs. The
  name lands in every HLO instruction's ``op_name`` metadata
  (``jit(_decode)/while/body/ffn/dot_general``) and from there in the
  trace's ``tf_op`` event-metadata stat; it costs nothing at run time
  and changes no HLO but its metadata. Read by
  ``benchmark/harness/scope_reduce.py`` (device self time by scope:
  the ``decode_*_share`` / ``prefill_*_share`` metrics), and by an
  operator in Perfetto/XProf as the op's "tf_op" / framework name.
* **Host phases** — ``HOST_PHASES`` / ``host_span(name, step_num)``: a
  ``TraceAnnotation`` around each stretch of host work between
  dispatches, on the profiler's clock, whose duration also goes to
  ``EngineTelemetry.phase_seconds`` and the
  ``engine_host_phase_seconds_total{phase}`` counter. Phases never
  nest and never overlap a dispatch step: the trace reducer gives an
  idle gap of the device to the one span that holds its midpoint
  (``breakdown.idle_gaps``, the ``idle_*_share`` metrics); the exact
  split among phases is the counter's.

All three are TraceMe-based and near-free while no profiler session is
active, so the engines keep them on unconditionally.
"""

from __future__ import annotations

import contextlib
import pathlib
import time

#: device scopes, innermost wins (``unembed`` holds a ``norm_rope``)
SCOPES = (
    "embed",      # token embedding lookup
    "kv_prefix",  # static prefix slice / paged gather of the KV cache
    "qkv",        # q, k, v projections
    "attn",       # attention proper (XLA decode pieces, flash, paged)
    "attn_out",   # the wo projection
    "ffn",        # swiglu / gelu MLP / MoE
    "norm_rope",  # rms_norm, apply_rope
    "unembed",    # final norm + lm_head
    "sample",     # engine/sampling.py:sample
    "kv_write",   # window-buffer updates, merge_window, the admit
    #               program's cache scatter
)

#: scopes that only the attention="eva" programs hold (models/eva.py).
#: Apart from ``SCOPES``: the benchmark's accepted scope table and its
#: tests hold every name of ``SCOPES`` to the dense programs' trace;
#: the readers of these name this tuple themselves
#: (benchmark/readers/scope_share_of.py).
EVA_SCOPES = (
    "kv_compact",  # pooling a filled window of exact keys and values
    #                into its chunk summaries
)

#: scopes that only the attention="mla" programs hold (models/xing.py),
#: in a tuple of their own for the same reason
XING_SCOPES = (
    "moe_route",      # router scores, top-k, grouping tokens by expert
    "moe_experts",    # the grouped matmuls over the experts held
    "mhc",            # the residual streams' maps, their Sinkhorn
    #                   projection and the two mixes
    "latent_expand",  # prefill: every head's keys and values from the
    #                   cached latents of earlier pieces
)

#: scopes of the attention="mla" programs of a config that SELECTS
#: what attention reads (``cfg.index_topk``; GLM-5 class): the four it
#: shares with ``XING_SCOPES`` (it has one residual stream: no ``mhc``)
#: and its own two. A tuple of its own, as those: a reader reduces a
#: program's trace over ``SCOPES`` plus the ONE tuple it names
GLM_SCOPES = (
    "moe_route",
    "moe_experts",
    "latent_expand",
    "indexer",        # index queries, keys and head weights; the index
    #                   scores over the cached index keys
    "select",         # the exact top-k of a query's index scores as a
    #                   threshold and the mask over the columns
    #                   attention walks. The threshold's counting: in
    #                   XLA for a decode step and off a TPU
    #                   (ops/sparse_select.py); for an admission piece
    #                   on a TPU over keys held in VMEM
    #                   (ops/select_threshold.py), its ties' cut in XLA
)

#: scopes of the attention="mixed" programs (models/mixed.py): the two
#: it shares with ``XING_SCOPES`` (the experts are that module's) and
#: its own four. A tuple of its own, as those. Innermost wins: the
#: dispatch's own columns and the fold of the partials stay under
#: ``attn`` (``ops/attention.py``'s helpers), so ``attn_window`` /
#: ``attn_full`` are a layer's read of its CACHE (decode) and the whole
#: of a piece's attention (admission)
MIXED_SCOPES = (
    "moe_route",
    "moe_experts",
    "attn_window",     # a window layer's attention over its ring
    "attn_full",       # a full layer's attention over its extent
    "ring_write",      # a piece's, and a dispatch's, columns into the
    #                    rings (``kv_write``: into the full extents)
    "shared_experts",  # the shared experts' SwiGLU
)

#: host phases of the serving loop (with the dispatch kinds ``decode``
#: and ``prefill`` and ``_no_annotation_`` they fit the trace
#: reducer's ten gap owners)
HOST_PHASES = (
    "wait_work",  # runner blocked with nothing pending, engine idle
    "enqueue",    # runner handing fresh arrivals to eng.submit
    "plan",       # step() host work leading up to a dispatch
    "commit",     # after an admission's host fetch: records, slots
    "harvest",    # after a decode's host fetch: tokens, retire
    "upkeep",     # journal tick, gauges, draining completions
    "resolve",    # runner resolving handles and done-callbacks
)


@contextlib.contextmanager
def maybe_profile(trace_dir: str | None, *, create_perfetto_link=False):
    """Capture a jax.profiler trace into ``trace_dir`` when set; plain
    no-op when None/empty — callers never branch."""
    if not trace_dir:
        yield None
        return
    import jax

    path = pathlib.Path(trace_dir)
    path.mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(path),
                            create_perfetto_link=create_perfetto_link):
        yield str(path)


def step_annotation(name: str, step_num: int | None = None):
    """``StepTraceAnnotation`` context for one engine dispatch.

    ``name`` is the wave kind (prefill/decode/verify/...), ``step_num``
    the flight-recorder step id. Returns a no-op context when the
    profiler API is unavailable (stripped-down jax builds) — callers
    never branch."""
    import jax

    try:
        if step_num is None:
            return jax.profiler.StepTraceAnnotation(name)
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
    except Exception:  # pragma: no cover - profiler API missing
        return contextlib.nullcontext()


def scope(name: str):
    """``jax.named_scope`` for one of ``SCOPES``, ``EVA_SCOPES``,
    ``XING_SCOPES``, ``GLM_SCOPES`` or ``MIXED_SCOPES`` (trace time
    only)."""
    import jax

    if name not in SCOPES + EVA_SCOPES + XING_SCOPES + GLM_SCOPES \
            + MIXED_SCOPES:
        raise ValueError(f"unknown device scope {name!r}; obs/profile.py "
                         f"SCOPES has {SCOPES}, EVA_SCOPES {EVA_SCOPES}, "
                         f"XING_SCOPES {XING_SCOPES}, GLM_SCOPES "
                         f"{GLM_SCOPES}, MIXED_SCOPES {MIXED_SCOPES}")
    return jax.named_scope(name)


class host_span:
    """One host phase: a ``TraceAnnotation(name, step_num=...)`` on the
    profiler's clock (a phase is not a step, so not a
    ``StepTraceAnnotation``) whose ``time.monotonic()`` duration is
    handed to ``sink(name, seconds)`` on exit. ``step_num`` is the id
    of the dispatch the phase follows or leads to — the trace reducer
    keeps only host events that carry one."""

    __slots__ = ("name", "_note", "_sink", "_t0")

    def __init__(self, name: str, step_num: int | None = None,
                 sink=None):
        import jax

        if name not in HOST_PHASES:
            raise ValueError(f"unknown host phase {name!r}; "
                             f"obs/profile.py HOST_PHASES has "
                             f"{HOST_PHASES}")
        self.name, self._sink = name, sink
        kw = {} if step_num is None else {"step_num": step_num}
        self._note = jax.profiler.TraceAnnotation(name, **kw)

    def __enter__(self):
        self._t0 = time.monotonic()
        self._note.__enter__()
        return self

    def __exit__(self, *exc):
        self._note.__exit__(*exc)
        if self._sink is not None:
            self._sink(self.name, time.monotonic() - self._t0)
        return False
