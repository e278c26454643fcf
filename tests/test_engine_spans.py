# What the chip and the host are doing, by name (obs/profile.py): host
# phases of the serving loop, the step records' token and time fields,
# per-request stall accounting, and the device scopes in the lowered
# programs. Tiny engine on the CPU; no profiler session needed.
import time

import pytest

from copilot_for_consensus_tpu.engine.scheduler import SchedulerConfig
from copilot_for_consensus_tpu.engine.telemetry import (
    FlightRecorder,
    StepRecord,
    live,
)
from copilot_for_consensus_tpu.obs.profile import (
    HOST_PHASES,
    SCOPES,
    host_span,
    scope,
)

ADMISSION = ("prefill", "prefill_seeded", "prefill_chunk")


@pytest.fixture(scope="module")
def tiny_parts():
    import jax
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.models import decoder
    from copilot_for_consensus_tpu.models.configs import decoder_config

    cfg = decoder_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(11), cfg,
                                 dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine,
    )

    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("eos_id", -1)        # every request ends at its limit
    return GenerationEngine(cfg, params, **kw)


def _prompt(n, base=3):
    return [base + (i % 50) for i in range(n)]


@pytest.fixture(scope="module")
def driven(tiny_parts):
    """One engine driven through admissions of two shapes and decode
    over several cache extents; the tests below read its records."""
    eng = _engine(*tiny_parts, max_len=512)
    comps = eng.generate([_prompt(5), _prompt(9), _prompt(20)], 12)
    comps += eng.generate([_prompt(30)], 110)    # past a 128 extent
    comps += eng.generate([_prompt(7, base=9)], 3)
    return eng, comps


def test_unknown_names_are_refused():
    with pytest.raises(ValueError, match="HOST_PHASES"):
        host_span("thinking")
    with pytest.raises(ValueError, match="SCOPES"):
        scope("misc")


def test_phases_cover_the_loop_outside_dispatches(tiny_parts):
    """Runner + engine under a trickle of arrivals: every phase fires,
    nothing fires that HOST_PHASES does not list, and the phases add up
    to the loop's wall time outside the dispatches."""
    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )

    eng = _engine(*tiny_parts)
    eng.generate([_prompt(6)], 10)          # compile outside the loop
    tele = eng.telemetry
    assert tele in live()
    seen = []
    sink = tele._on_phase
    tele._on_phase = lambda name, s: (seen.append(name), sink(name, s))
    before = dict(tele.phase_seconds)
    seq0 = tele.recorder.last_seq
    t0 = time.monotonic()
    runner = AsyncEngineRunner(eng).start()
    handles = []
    for i in range(6):
        handles.append(runner.submit(_prompt(5 + i), 10))
        time.sleep(0.05)
    for h in handles:
        assert len(h.result(timeout=120).tokens) == 10
    time.sleep(0.25)                         # idle: wait_work
    assert runner.stop(timeout=30)
    wall = time.monotonic() - t0
    assert set(seen) == set(HOST_PHASES)
    assert set(tele.phase_seconds) == set(HOST_PHASES)
    phases = sum(tele.phase_seconds[p] - before[p] for p in HOST_PHASES)
    dispatched = sum(r.duration_s for r in tele.recorder.records()
                     if r.seq > seq0)
    assert phases >= 0.95 * (wall - dispatched), (phases, wall,
                                                  dispatched)
    assert tele.phase_seconds["wait_work"] - before["wait_work"] >= 0.2
    exported = tele.metrics.counter_value(
        "engine_host_phase_seconds_total",
        {"engine": "generation", "phase": "harvest"})
    assert exported == pytest.approx(tele.phase_seconds["harvest"])


def _shared(n):
    """The same 16 tokens open every prompt; the tail is its own."""
    return _prompt(16) + [60 + n] * max(4, n - 16)


def _cycle(n):
    """A prompt that repeats itself: prompt-lookup drafts hit."""
    return [5, 9, 13, 17] * (n // 4)


#: every dispatch kind out_tok_s would count the day a cell turns it
#: on: the options that bring it out, the prompts, and the kind of
#: record that must appear
_KINDS = {
    "waves": (dict(), _prompt, "prefill"),
    "seeded": (dict(prefix_cache_blocks=8, prefill_chunk=8), _shared,
               "prefill_seeded"),
    "verify": (dict(spec_decode=True), _cycle, "verify"),
    "chunked": (dict(scheduler=SchedulerConfig(chunk_tokens=8)), _prompt,
                "prefill_chunk"),
    "paged": (dict(kv_pool_blocks=40, prefill_chunk=8), _prompt,
              "prefill"),
}


@pytest.mark.parametrize("case", list(_KINDS))
def test_new_tokens_sum_to_tokens_delivered(tiny_parts, case):
    kw, make, kind = _KINDS[case]
    eng = _engine(*tiny_parts, prefill_buckets=(16, 32, 64), **kw)
    prompts = [make(n) for n in (5, 24, 40, 11, 30, 26)]
    comps = eng.generate(prompts[:2], 9)
    # arrivals while others decode
    rids = [eng.submit(p, 13) for p in prompts[2:]]
    done = {}
    for _ in range(200):
        for c in eng.step():
            done[c.request_id] = c
        if len(done) == len(rids):
            break
    comps += [done[r] for r in rids]
    recs = eng.telemetry.recorder.records()
    assert any(r.kind == kind for r in recs), {r.kind for r in recs}
    if case == "paged":
        assert {r.route for r in recs} == {"reference"}
    assert sum(r.new_tokens for r in recs) == \
        sum(len(c.tokens) for c in comps)
    # prompt_tokens are the tokens PREFILLED: what a cached prefix
    # seeded was not
    assert (eng.prefill_tokens_saved > 0) == (case == "seeded")
    assert sum(r.prompt_tokens for r in recs) == \
        sum(len(p) for p in prompts) - eng.prefill_tokens_saved


def test_step_times_run_forward_and_add_up(driven):
    eng, _comps = driven
    recs = eng.telemetry.recorder.records()
    assert len(recs) > 10
    ends = [r.t_end for r in recs]
    assert ends == sorted(ends)
    for r in recs:
        assert r.t_end - r.t_start == pytest.approx(r.duration_s,
                                                    abs=1e-9)
        assert r.t_start > 0


def test_one_first_use_per_program(driven):
    eng, _comps = driven
    recs = eng.telemetry.recorder.records()
    firsts = [r for r in recs if r.first_use]
    assert len(firsts) == len(eng.programs_seen)
    assert {k[0] for k in eng.programs_seen} == {"prefill", "decode"}
    # an admission's static key is its padded grid: rows x bucket
    by_key = {}
    for r in recs:
        if r.kind in ADMISSION:
            by_key.setdefault((r.kind, r.batch, r.padded_tokens),
                              []).append(r)
    assert len(by_key) >= 3
    for group in by_key.values():
        assert [r.first_use for r in group] == \
            [True] + [False] * (len(group) - 1)
    # decode programs differ by cache extent: more than one was used
    assert sum(1 for r in firsts if r.kind == "decode") >= 2


def test_recorder_keeps_an_hour_of_steps():
    fr = FlightRecorder()
    for _ in range(600):
        fr.record(StepRecord(seq=fr.next_seq(), kind="decode",
                             t_wall=0.0, duration_s=0.001))
    assert len(fr.records()) == 600 and fr.last_seq == 600
    assert fr.capacity >= 8 * 3600


def test_stall_accounting_when_a_wave_lands_mid_decode(tiny_parts):
    eng = _engine(*tiny_parts)
    eng.generate([_prompt(6)], 2)            # compile the first shapes
    first = eng.submit(_prompt(6), 40, correlation_id="first")
    for _ in range(2):
        eng.step()
    assert eng._active, "the first request is decoding"
    second = eng.submit(_prompt(12), 4, correlation_id="second")
    done = {}
    for _ in range(60):
        for c in eng.step():
            done[c.request_id] = c
        if len(done) == 2:
            break
    traces = {t.correlation_id: t for t in eng.telemetry.completed}
    a, b = traces["first"], traces["second"]
    waves = [r for r in eng.telemetry.recorder.records()
             if r.kind in ADMISSION
             and a.first_token_at < r.t_end < a.finished_at]
    assert len(waves) == 1                   # the second's prefill
    assert a.stalled_s == pytest.approx(waves[0].duration_s)
    assert b.stalled_s == 0.0
    decodes = [r for r in eng.telemetry.recorder.records()
               if r.kind == "decode"
               and a.first_token_at < r.t_end < a.finished_at]
    assert a.decode_dispatches == len(decodes) == 5   # 39 tokens / 8
    assert a.decode_s_own == pytest.approx(
        sum(r.duration_s for r in decodes))
    for t in (a, b):
        assert t.host_s >= 0
        assert t.decode_s_own + t.stalled_s + t.host_s == \
            pytest.approx(t.finished_at - t.first_token_at, abs=1e-9)
    assert len(done[first].tokens) == 40 and len(done[second].tokens) == 4


def _op_names(lowered) -> set[str]:
    """The op_name metadata of the compiled program's instructions:
    the full scope paths, as a device trace carries them."""
    import re

    return set(re.findall(r'op_name="([^"]*)"',
                          lowered.compile().as_text()))


def test_scopes_are_in_the_lowered_programs(tiny_parts):
    """A refactor that drops a scope fails here, on a CPU: the names
    the scope metrics sum by must be in the programs' op_name
    metadata."""
    import jax
    import jax.numpy as jnp

    eng = _engine(*tiny_parts)
    key = jax.random.PRNGKey(0)
    decode = _op_names(eng._decode_fn.lower(
        eng.params, jnp.asarray(eng._next_tok),
        jnp.asarray(eng._positions), eng._cache, key,
        kv_len=64, n_windows=1))
    admit = _op_names(eng._admit_fn.lower(
        eng.params, jnp.zeros((2, 16), jnp.int32),
        jnp.ones((2,), jnp.int32), eng._cache,
        jnp.zeros((2,), jnp.int32), key))
    for name in SCOPES:
        assert any(f"/{name}/" in op and op.startswith("jit(_decode)/")
                   for op in decode), f"{name} missing from _decode"
        if name != "kv_prefix":      # an admission reads no prefix
            assert any(f"/{name}/" in op
                       and op.startswith("jit(_admit_fused)/")
                       for op in admit), \
                f"{name} missing from _admit_fused"
