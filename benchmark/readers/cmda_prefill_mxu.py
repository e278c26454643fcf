"""Share of the chip's bf16 peak that the admission program of a
mixed-layer engine reaches, in percent: the FLOPs its real prompt
tokens need (``harness/cmda_roofline.py``: 2 per parameter a token
passes, the held experts by the pairs the program counted, attention
over the causal pairs inside the window, ``window_attn_pairs``, in the
window layers and over all causal pairs, ``attn_pairs``, in the full
ones, the head on the rows that yielded a token) over the peak and the
program's device time in the traced waves. Step records without the
counts (a program that admits otherwise) or no trace: no value."""
from benchmark.harness import cmda_roofline, roofline
from benchmark.readers import _select


def read(run, args):
    rows = [(s, d) for s, d in _select.traced_steps(
        run, args["step"], args["module"]) if s.get("window_attn_pairs")]
    if not rows:
        return None
    need = sum(cmda_roofline.prefill_flops(
        run["dims"], s["tokens"], s["attn_pairs"], s["window_attn_pairs"],
        s["expert_rows"], s["new_tokens"]) for s, _d in rows)
    peak = roofline.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * need / peak / sum(d for _s, d in rows)
