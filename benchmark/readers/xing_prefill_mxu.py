"""Share of the chip's bf16 peak that the admission program of a
latent-attention engine with sparse experts reaches, in percent: the
FLOPs its real prompt tokens need (``harness/xing_roofline.py``: 2 per
active parameter and token, expanded attention over the query-key
pairs the program counted on each wave's record, the output head on
the rows that yielded a token) over the peak and the program's device
time in the traced waves. Step records without ``attn_pairs`` (a
program that admits otherwise) or no trace: no value."""
from benchmark.harness import roofline, xing_roofline
from benchmark.readers import _select


def read(run, args):
    rows = [(s, d) for s, d in _select.traced_steps(
        run, args["step"], args["module"]) if "attn_pairs" in s]
    if not rows:
        return None
    need = sum(xing_roofline.prefill_flops(
        run["dims"], s["tokens"], s["attn_pairs"], s["new_tokens"])
        for s, _d in rows)
    peak = roofline.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / peak / sum(d for _s, d in rows)
