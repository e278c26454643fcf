"""The metric arithmetic on hand-made records."""
import pytest

from benchmark.harness import stats


def old_generated_tokens(step, first_tokens=None) -> int:
    """How ``out_tok_s`` counted a step until PR 28, by its kind; kept
    here as the reference the program's own count is held to. A
    piggyback dispatch's ``tokens`` holds its prompt tokens too and
    leaves out the first token of each prompt it finished, which the
    tap then read from the engine's counters. Any other kind: 0."""
    if step["kind"] in ("decode", "verify"):
        return int(step["tokens"])
    if step["kind"] == "piggyback":
        return (int(step["tokens"]) - int(step["prompt_tokens"])
                + int(first_tokens))
    if step["kind"].startswith("prefill"):
        return int(step["rows"])
    return 0


def step(t_end, kind="decode", tokens=8, rows=1, **more):
    s = {"t_end": t_end, "kind": kind, "tokens": tokens, "rows": rows,
         **more}
    s.setdefault("new_tokens", old_generated_tokens(s, 0))
    return s


def test_out_tok_s_is_continuous_in_every_timestamp():
    steps = [step(10.0), step(10.2), step(10.4), step(10.6)]
    base = stats.out_tok_s(steps, (9.0, 11.0))
    assert base == pytest.approx(24 / 0.6)
    steps[-1] = step(10.61)
    moved = stats.out_tok_s(steps, (9.0, 11.0))
    assert moved == pytest.approx(24 / 0.61)
    assert 0 < base - moved < 0.02 * base        # no step of a request


def test_out_tok_s_counts_first_tokens_of_waves_not_prompt_tokens():
    steps = [step(1.0), step(2.0, "prefill", tokens=4096, rows=3),
             step(3.0)]
    assert stats.out_tok_s(steps, (0.0, 5.0)) == pytest.approx(11 / 2.0)
    assert stats.out_tok_s(steps[:1], (0.0, 5.0)) is None
    # steps outside the interval are not counted
    assert stats.out_tok_s(steps + [step(9.0)], (0.0, 5.0)) == \
        pytest.approx(11 / 2.0)


def test_the_count_is_the_records_own_whatever_the_kind():
    """A piggyback dispatch hands over decoded tokens and first tokens
    and no prompt token; a kind this benchmark has never heard of (a
    step that yields several tokens a sequence) is counted for what
    its record says, where the count by kind gave it 0; and a record
    that says nothing is an error, not a larger or a smaller rate."""
    piggy = step(2.0, "piggyback", tokens=8 + 640, rows=1,
                 prompt_tokens=640, new_tokens=10)
    assert old_generated_tokens(piggy, first_tokens=2) == 10
    many = step(2.5, "multi_token_decode", tokens=40, rows=8,
                new_tokens=5)
    assert old_generated_tokens(many) == 0
    steps = [step(1.0), piggy, many, step(3.0)]
    assert stats.out_tok_s(steps, (0.0, 5.0)) == \
        pytest.approx((10 + 5 + 8) / 2.0)
    silent = dict(step(2.0))
    del silent["new_tokens"]
    with pytest.raises(KeyError):
        stats.out_tok_s([step(1.0), silent, step(3.0)], (0.0, 5.0))


def req(due, first=None, finish=None, new=10, ok=True, phase="counted"):
    return {"phase": phase, "due": due, "first_token_at": first,
            "finished_at": finish, "new_tokens": new, "ok": ok}


def test_due_in_interval_finished_after_it_is_counted():
    window = (0.0, 10.0)
    reqs = [req(1.0, 1.5, 3.0), req(9.9, 14.0, 20.0),
            req(10.5, 10.6, 11.0), req(-1.0, 0.5, 1.0, phase="lead_in")]
    assert len(stats.counted(reqs, window)) == 2
    assert sorted(stats.ttft_values(reqs, window)) == \
        pytest.approx([0.5, 4.1])


def test_failed_request_takes_the_largest_ttft_and_tpot():
    window = (0.0, 10.0)
    reqs = [req(1.0, 1.2, 2.1, new=10), req(2.0, 2.9, 4.7, new=10),
            req(3.0, None, None, ok=False)]
    assert sorted(stats.ttft_values(reqs, window)) == \
        pytest.approx([0.2, 0.9, 0.9])
    assert sorted(stats.tpot_values_ms(reqs, window)) == \
        pytest.approx([100.0, 200.0, 200.0])


def test_e2e_is_from_due_time_and_a_failed_request_takes_the_largest():
    window = (0.0, 10.0)
    reqs = [req(1.0, 1.2, 2.1), req(2.0, 2.9, 4.7),
            req(3.0, None, None, ok=False), req(9.5, 11.0, 15.0)]
    assert sorted(stats.e2e_values(reqs, window)) == \
        pytest.approx([1.1, 2.7, 5.5, 5.5])


def test_p90_rule_on_100_and_on_113_values():
    v100 = list(range(1, 101))
    assert stats.percentile(v100, 0.9) == pytest.approx(90.1)
    v113 = list(range(1, 114))
    assert stats.percentile(v113, 0.9) == pytest.approx(1 + 0.9 * 112)
    assert stats.percentile([5.0], 0.9) == 5.0
    assert stats.percentile(v100, 0.5) == pytest.approx(50.5)


def test_spread_is_the_drivers_rule():
    import statistics
    vals = [10.0, 10.2, 10.1, 9.9, 10.4, 10.0]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q[2] - q[0]) / statistics.median(vals))


def test_every_metric_of_the_manifest_has_its_reader_file():
    """BENCHMARK.json holds name, unit and cells; metrics/<name>.json
    only the reader and its arguments; a metric without ``workloads``
    goes to every cell that reports the metric it moves."""
    import json

    from benchmark.harness import spec

    bench = json.loads((spec.REPO / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        e2e = spec.metric_files(cell["name"], "end_to_end")
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        layer = spec.metric_files(cell["name"], "per_layer")
        assert layer
        for m in e2e + layer:
            assert hasattr(spec.module("readers", m["reader"]), "read")
        moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
        assert all(moved[m["name"]] in names for m in layer)
    files = {p.stem for p in (spec.BENCH / "metrics").glob("*.json")}
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert files == listed


def _tiny_engine(**more):
    import json

    import jax.numpy as jnp

    from benchmark.builders._decoder import decoder_config, dims_of
    from benchmark.harness import spec, weights
    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine)

    cfg = json.loads((spec.BENCH / "configs"
                      / "mistral-7b-int8.json").read_text())
    dims = dims_of(cfg, True)
    args = dict(cfg["engine"], **cfg["rehearsal"]["engine"])
    args["prefill_buckets"] = tuple(args["prefill_buckets"])
    return dims, GenerationEngine(
        decoder_config(dims, "tiny"), weights.decoder_weights(dims, 5),
        dtype=jnp.bfloat16, seed=5, **args, **more)


@pytest.mark.parametrize("piggyback", [False, True],
                         ids=["waves", "piggyback-on"])
def test_recorded_steps_count_every_delivered_token(piggyback):
    """Step lists recorded from a tiny engine through the builder's
    tap, admission waves only and with chunked-prefill piggybacking
    switched on: the records' own count is the count by kind wherever
    that was defined from a record alone, a piggyback step's is the old
    arithmetic with the first tokens the engine counted, their sum is
    exactly the tokens the requests received, and so the rate by
    either count is the same number."""
    import numpy as np

    from benchmark.builders._decoder import token_ids
    from benchmark.builders._tap import EngineTap

    dims, engine = _tiny_engine(
        **({"piggyback_min_prompt": 40} if piggyback else {}))
    tap = EngineTap(engine)
    rng = np.random.default_rng(0)
    for n in (20, 100, 60, 24, 90):
        engine.submit(token_ids(rng, n, dims["vocab_size"]), 12)
    done = []
    while len(done) < 5:
        done += engine.step()
    tap.poll()
    steps = tap.step_list()
    piggy = [s for s in steps if s["kind"] == "piggyback"]
    assert bool(piggy) == piggyback
    assert any(s["kind"].startswith("prefill") for s in steps)
    for s in steps:
        assert s["t_end"] - s["t_start"] == pytest.approx(s["duration_s"])
        if s["kind"] != "piggyback":
            assert s["new_tokens"] == old_generated_tokens(s)
    if piggyback:
        assert any(s["prompt_tokens"] > 0 for s in piggy)
        assert sum(s["new_tokens"] - s["tokens"] + s["prompt_tokens"]
                   for s in piggy) == engine.piggy_rows
    assert sum(s["new_tokens"] for s in steps) == \
        sum(len(c.tokens) for c in done) == 60
    window = (steps[0]["t_end"] - 1.0, steps[-1]["t_end"] + 1.0)
    span = steps[-1]["t_end"] - steps[0]["t_end"]
    assert stats.out_tok_s(steps, window) == pytest.approx(
        (60 - steps[0]["new_tokens"]) / span)


def test_model_keys_that_are_not_numbers_reach_the_builder():
    """``dims_of`` keeps the model's keys as the file has them and
    drops the file's own sections; for ``mistral-7b-int8`` the numbers
    are the ones the old rule (numbers only) kept."""
    import json

    from benchmark.builders._decoder import FILE_KEYS, dims_of
    from benchmark.harness import spec

    cfg = json.loads((spec.BENCH / "configs"
                      / "mistral-7b-int8.json").read_text())
    numbers = {k: v for k, v in cfg.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    dims = dims_of(cfg, False)
    assert {k: v for k, v in dims.items() if k in numbers} == numbers
    assert set(dims) - set(numbers) == {"hidden_act", "model_type",
                                        "tie_word_embeddings"}
    assert not set(dims) & FILE_KEYS
    more = dict(cfg, layer_types=["a", "b"], rope_scaling={"factor": 2.0})
    more["rehearsal"] = dict(cfg["rehearsal"], model={
        **cfg["rehearsal"]["model"], "layer_types": ["a"]})
    assert dims_of(more, False)["layer_types"] == ["a", "b"]
    assert dims_of(more, False)["rope_scaling"] == {"factor": 2.0}
    assert dims_of(more, True)["layer_types"] == ["a"]
    assert dims_of(more, True)["hidden_size"] == 128
