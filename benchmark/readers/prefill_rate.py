"""Prefill on the device: real prompt tokens per device second of the
admission program, or (``share``) the FLOPs those tokens need over the
chip's bf16 peak and that time, in percent. Prompt lengths of a wave
come from the requests admitted in it."""
from benchmark.harness import roofline
from benchmark.readers import _select


def read(run, args):
    rows = _select.traced_steps(run, args["step"], args["module"])
    if not rows:
        return None
    dev = sum(d for _s, d in rows)
    if not args.get("share"):
        return sum(s["tokens"] for s, _d in rows) / dev
    lens = []
    reqs = run["records"]["engine_requests"]
    for s, _d in rows:
        mine = [r["prompt_len"] for r in reqs
                if r["admitted_at"] and r["first_token_at"]
                and abs(r["first_token_at"] - s["t_end"]) < 0.05
                and r["admitted_at"] <= s["t_end"]]
        if sum(mine) != s["tokens"]:
            # fall back to equal lengths: fewer attention FLOPs than
            # any uneven split, so the share is never overstated
            mine = [s["tokens"] / s["rows"]] * s["rows"]
        lens += mine
    peak = roofline.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * roofline.prefill_flops(run["dims"], lens) / peak / dev
