"""Share of the HBM roofline a decode step reaches: the bytes it has to
read (every matrix once, and each live sequence's own cache prefix as
long as it was when the dispatch began) over the chip's bandwidth and
the device time of the decode program, in percent."""
from benchmark.harness import roofline
from benchmark.readers import _select


def _live_lens(reqs, t0):
    out = []
    for r in reqs:
        if not (r["first_token_at"] and r["finished_at"]):
            continue
        if r["first_token_at"] <= t0 < r["finished_at"]:
            span = r["finished_at"] - r["first_token_at"]
            done = (t0 - r["first_token_at"]) / span if span > 0 else 0.0
            out.append(r["prompt_len"] + done * r["new_tokens"])
    return out


def read(run, args):
    rows = _select.traced_steps(run, args["step"], args["module"])
    if not rows:
        return None
    per = run["records"]["engine"]["steps_per_dispatch"]
    reqs = run["records"]["engine_requests"]
    need = 0.0
    for s, _d in rows:
        lens = _live_lens(reqs, s["t_end"] - s["duration_s"])
        need += per * roofline.decode_bytes(
            run["dims"], lens, args["weight_bytes"], args["kv_bytes"])
    peak = roofline.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / sum(d for _s, d in rows)
