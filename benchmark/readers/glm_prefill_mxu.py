"""Share of the chip's bf16 peak that the admission program of a
latent-attention engine with a learned selection reaches, in percent:
the FLOPs its real prompt tokens need (``harness/glm_roofline.py``: 2
per parameter a token passes, the held experts by the pairs the program
counted, index scores over every query-position pair of the wave,
``live_tokens``, expanded attention over the pairs the selection keeps,
``selected_tokens``, the head on the rows that yielded a token) over
the peak and the program's device time in the traced waves. Step
records without the counts (a program that admits otherwise) or no
trace: no value."""
from benchmark.harness import glm_roofline, roofline
from benchmark.readers import _select


def read(run, args):
    rows = [(s, d) for s, d in _select.traced_steps(
        run, args["step"], args["module"]) if s.get("live_tokens")]
    if not rows:
        return None
    need = sum(glm_roofline.prefill_flops(
        run["dims"], s["tokens"], s["live_tokens"], s["selected_tokens"],
        s["expert_rows"], s["new_tokens"]) for s, _d in rows)
    peak = roofline.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / peak / sum(d for _s, d in rows)
