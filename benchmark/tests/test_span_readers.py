"""The readers of the program's own accounting, on hand-built runs:
idle gaps by host phase, a request's time after its first token, and
set-up's program loads; and the rehearsal, which lists the new
metrics' names and still gives no result."""
import pytest

from benchmark import run as bench_run
from benchmark.readers import first_use_steps, gap_share, request_share


def test_gap_share_by_owner_and_by_exception():
    run = {"trace": {"window_s": 10.0, "idle_gaps": [
        ["wait_work", 0.6], ["harvest", 0.2], ["decode", 0.1],
        ["_no_annotation_", 0.05], ["plan", 0.05]]}}
    assert gap_share.read(run, {"owners": ["wait_work"]}) == \
        pytest.approx(6.0)
    assert gap_share.read(run, {"except": ["wait_work"]}) == \
        pytest.approx(4.0)
    assert gap_share.read(run, {"owners": ["resolve"]}) == 0.0
    # a program without host phases (the parent commit), a CPU
    # rehearsal, an untraced run: nothing to read
    old = {"trace": {"window_s": 10.0, "idle_gaps": [
        ["_no_annotation_", 0.8], ["decode", 0.1]]}}
    assert gap_share.read(old, {"except": ["wait_work"]}) is None
    assert gap_share.read({"trace": {"window_s": 0.0, "idle_gaps": []}},
                          {"owners": ["wait_work"]}) is None
    assert gap_share.read({"trace": None}, {"owners": ["x"]}) is None


def _run(parts):
    """Counted requests 2 s each from first token to finish, with the
    program's (stalled_s, host_s) or None where the trace is gone."""
    reqs = []
    for i, part in enumerate(parts):
        stalled, host = part or (None, None)
        reqs.append({"phase": "counted", "due": 9.5 + i,
                     "first_token_at": 10.0 + i, "finished_at": 12.0 + i,
                     "stalled_s": stalled, "host_s": host})
    # a lead-in request: offered, never counted
    reqs.append({"phase": "lead_in", "due": 1.0, "first_token_at": 2.0,
                 "finished_at": 3.0, "stalled_s": 0.9, "host_s": 0.9})
    return {"window": (9.0, 20.0), "records": {"requests": reqs}}


def test_request_share_sums_over_counted_requests():
    run = _run([(0.8, 0.02), (0.4, 0.04), (0.0, 0.06)])
    assert request_share.read(run, {"part": "stalled"}) == \
        pytest.approx(100 * 1.2 / 6.0)
    assert request_share.read(run, {"part": "host"}) == \
        pytest.approx(100 * 0.12 / 6.0)
    # one counted request's trace is gone: no value, not a smaller sum
    gone = _run([None, (0.4, 0.04), (0.0, 0.06)])
    assert request_share.read(gone, {"part": "stalled"}) is None
    assert request_share.read(_run([]), {"part": "stalled"}) is None


def test_first_use_steps_counts_set_up_only():
    def rec(t_end, dur, first):
        return {"t_end": t_end, "duration_s": dur, "first_use": first}

    steps = [rec(1.0, 0.9, True), rec(2.0, 0.1, False),
             rec(3.0, 1.5, True), rec(9.5, 0.2, True)]  # in the window
    run = {"window": (9.0, 20.0), "records": {"steps": steps}}
    assert first_use_steps.read(run, {"value": "count"}) == 2.0
    assert first_use_steps.read(run, {"value": "seconds"}) == \
        pytest.approx(2.4)
    with pytest.raises(ValueError):
        first_use_steps.read(run, {"value": "mean"})
    # no program was loaded in set-up: nothing to read
    run["records"]["steps"] = steps[1:2]
    assert first_use_steps.read(run, {"value": "count"}) is None
    # a record without the field is an error, as in the tap
    run["records"]["steps"] = [{"t_end": 1.0, "duration_s": 0.9}]
    with pytest.raises(KeyError):
        first_use_steps.read(run, {"value": "count"})


NEW_ON_ANY_PLATFORM = {
    "mistral-7b-int8.qa-steady": {
        "tpot_stall_share.qa", "tpot_host_share.qa",
        "setup_program_loads.qa", "setup_program_load_s.qa"},
    "mistral-7b-int8.summarize-backlog": {
        "setup_program_loads.backlog", "setup_program_load_s.backlog"},
}


@pytest.mark.parametrize("workload", sorted(NEW_ON_ANY_PLATFORM))
def test_traced_rehearsal_lists_the_new_metrics_and_exits_3(
        workload, capsys):
    rc = bench_run.main(["--workload", workload, "--seed", "3000000019",
                         "--seconds", "4", "--trace", "1",
                         "--rehearse"])
    assert rc == bench_run.REHEARSAL_EXIT == 3
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert last.startswith("[bench] rehearsal on platform: cpu")
    for name in NEW_ON_ANY_PLATFORM[workload]:
        assert f'"{name}"' in last
    # device metrics are never read from a CPU run
    assert "decode_kv_prefix_share" not in last
    assert "idle_host_share" not in last
