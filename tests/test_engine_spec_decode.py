# Speculative decode path: the prompt-lookup draft index (host-side,
# fast) and the engine's multi-token verify dispatch (CPU e2e, slow
# lane) — greedy speculation must be bit-identical to the vanilla
# decode path, and the copy-heavy fixture must clear >= 2 tokens per
# weight pass.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from copilot_for_consensus_tpu.engine.tokenizer import NgramDraftIndex


# ---------------------------------------------------------------------------
# draft index (pure host state, no jax)
# ---------------------------------------------------------------------------


def test_draft_returns_continuation_of_matched_ngram():
    idx = NgramDraftIndex([1, 2, 3, 4, 5, 1, 2, 3])
    assert idx.draft(4) == [4, 5, 1, 2]


def test_draft_prefers_longest_ngram():
    # tail (8, 2, 3): the 3-gram occurred once (followed by 9); the
    # 2-gram (2, 3) also occurred earlier followed by 4 — the 3-gram
    # match must win.
    idx = NgramDraftIndex([1, 2, 3, 4, 8, 2, 3, 9, 7, 8, 2, 3])
    assert idx.draft(1) == [9]


def test_draft_falls_back_to_min_ngram():
    idx = NgramDraftIndex([1, 2, 3, 4, 9, 9, 2, 3])
    assert idx.draft(2) == [4, 9]      # only the 2-gram (2, 3) matches


def test_draft_earliest_occurrence_wins_for_longest_span():
    # (1, 2) occurs at the start and at the tail; the earliest
    # continuation remembers the longer copyable span.
    idx = NgramDraftIndex([1, 2, 7, 8, 9, 1, 2], min_ngram=2, ngram=2)
    assert idx.draft(3) == [7, 8, 9]


def test_tail_never_matches_itself():
    # the context's own final n-gram has no continuation and must not
    # be indexed (a self-match would return an empty draft forever)
    idx = NgramDraftIndex([5, 6, 7])
    assert idx.draft(4) == []
    idx.extend([8])
    assert idx.draft(4) == []          # still no repeated n-gram


def test_incremental_extend_equals_bulk_build():
    toks = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 1, 4, 1, 9, 2, 6]
    bulk = NgramDraftIndex(toks)
    inc = NgramDraftIndex(toks[:5])
    for t in toks[5:]:
        inc.extend([t])
    assert bulk.draft(8) == inc.draft(8)
    assert len(bulk) == len(inc)


def test_draft_truncates_to_max_tokens():
    idx = NgramDraftIndex([1, 2, 3, 4, 5, 6, 7, 1, 2])
    assert idx.draft(2) == [3, 4]
    assert idx.draft(0) == []


def test_rejects_bad_ngram_bounds():
    with pytest.raises(ValueError):
        NgramDraftIndex([], ngram=1, min_ngram=2)


# ---------------------------------------------------------------------------
# engine end-to-end (CPU, slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestSpecDecodeEndToEnd:
    """The verify dispatch against the real engine on CPU.

    Two fixtures: random tiny weights (mixed accept/reject traffic —
    exercises rewind) and a crafted copy-cycle model whose greedy
    continuation is exactly periodic, so prompt-lookup drafts are
    always right and the weight-pass amortization is measurable
    deterministically (no reliance on what random weights happen to
    generate)."""

    def _engines(self, params, cfg, **spec_kw):
        from copilot_for_consensus_tpu.engine.generation import (
            GenerationEngine,
        )

        kw = dict(num_slots=4, max_len=256, prefill_buckets=(32, 64),
                  dtype=jnp.float32, attn_impl="xla", decode_window=4)
        kw.update(spec_kw.pop("engine_kw", {}))
        return (GenerationEngine(cfg, params, **kw),
                GenerationEngine(cfg, params, spec_decode=True,
                                 spec_draft_lens=(0, 4, 8), **kw,
                                 **spec_kw))

    def _random_setup(self):
        from copilot_for_consensus_tpu.models import decoder
        from copilot_for_consensus_tpu.models.configs import decoder_config

        cfg = decoder_config("tiny")
        params = decoder.init_params(jax.random.PRNGKey(7), cfg,
                                     dtype=jnp.float32)
        return cfg, params

    def test_greedy_bit_identical_on_random_weights(self):
        cfg, params = self._random_setup()
        base, spec = self._engines(params, cfg)
        prompts = [[5, 9, 13, 5, 9, 13, 5, 9],
                   [40, 41, 42, 43, 44, 45, 46],
                   list(np.arange(20) % 7 + 3)]
        want = base.generate(prompts, max_new_tokens=24)
        got = spec.generate(prompts, max_new_tokens=24)
        for w, g in zip(want, got):
            assert g.tokens == w.tokens
            assert g.finish_reason == w.finish_reason

    def test_copy_heavy_fixture_bit_identical_and_amortized(self, copy_cycle):
        """The acceptance fixture: greedy speculation-on output equals
        speculation-off bit for bit, AND the measured per-stream
        tokens_per_weight_pass clears 2.0 — the decode bandwidth wall
        actually moved."""
        cfg, params, prompt = copy_cycle
        base, spec = self._engines(params, cfg)
        want = base.generate([prompt], max_new_tokens=64)[0]
        got = spec.generate([prompt], max_new_tokens=64)[0]
        assert got.tokens == want.tokens
        assert len(got.tokens) == 64
        st = spec.spec_stats()
        assert st["enabled"]
        assert st["draft_hit_rate"] > 0.9
        assert st["verify_dispatches"] > 0
        assert st["mean_accepted_per_step"] >= 2.0
        assert st["tokens_per_weight_pass"] >= 2.0, st

    def test_mixed_wave_hit_and_miss_slots_stay_exact(self, copy_cycle):
        """Streams with and without draft hits share verify dispatches
        (the k=0 lane); nobody's tokens may change."""
        cfg, params, prompt = copy_cycle
        base, spec = self._engines(params, cfg)
        prompts = [prompt, [200, 201, 202, 203]]   # cycle + no-repeat
        want = base.generate(prompts, max_new_tokens=32)
        got = spec.generate(prompts, max_new_tokens=32)
        for w, g in zip(want, got):
            assert g.tokens == w.tokens

    def test_sampled_speculation_reproducible_and_in_vocab(self, copy_cycle):
        """The sampled verify path (rejection rule) is seed-stable and
        emits valid tokens; distribution-exactness itself is proven at
        the verify_draft level (test_engine_sampling.py)."""
        from copilot_for_consensus_tpu.engine.sampling import (
            SamplingConfig,
        )

        cfg, params, prompt = copy_cycle
        outs = []
        for _ in range(2):
            _, spec = self._engines(
                params, cfg,
                engine_kw=dict(
                    num_slots=4, max_len=256, prefill_buckets=(32, 64),
                    dtype=jnp.float32, attn_impl="xla", decode_window=4,
                    sampling=SamplingConfig(temperature=0.8, top_k=20),
                    seed=3))
            outs.append(spec.generate([prompt],
                                      max_new_tokens=24)[0].tokens)
        assert outs[0] == outs[1]
        assert all(0 <= t < cfg.vocab_size for t in outs[0])
        assert len(outs[0]) == 24

    def test_rewind_after_rejection_keeps_later_steps_exact(self):
        """Force heavy rejection: prompts whose repeated n-grams draft
        the WRONG continuation for a random-weights model. Every
        rejected draft rewinds the slot length pointer; subsequent
        tokens must still match the vanilla engine exactly."""
        cfg, params = self._random_setup()
        base, spec = self._engines(params, cfg)
        rng = np.random.default_rng(5)
        span = rng.integers(3, cfg.vocab_size, size=6).tolist()
        prompts = [span * 4, (span + [7]) * 3]
        want = base.generate(prompts, max_new_tokens=32)
        got = spec.generate(prompts, max_new_tokens=32)
        for w, g in zip(want, got):
            assert g.tokens == w.tokens
        st = spec.spec_stats()
        assert st["hits"] > 0                 # drafts were attempted
