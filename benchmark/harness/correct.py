"""Decides ``correct`` for a served decoder: a seeded sample of the
requests the window finished, the longest among them, is replayed once
through the plain reference (prompt plus served tokens), and at every
served position the served token's logit is held against the
reference's best. Greedy serving only.

The reference is the module ``benchmark/reference/<name>.py`` that the
cell's configuration file names under ``"reference"``; this file
imports none. What such a module offers:

``logits_at(weights, dims, tokens, positions, lower=None, pad_to=0)``
    float32 ``[len(positions), vocab]``: the logits that follow
    ``tokens[: p + 1]`` for each p, from a forward pass in float32 at
    ``highest`` matmul precision with no cache, batching or kernels,
    over the weights and dims that the configuration's builder made
    (``System.weights``, ``System.dims``). It imports nothing of the
    program. ``pad_to`` lets a run compile one length for all its
    passes.
``padded_len(n)``
    the length a sequence of ``n`` tokens is padded to.
``LOWERS``
    the lower-precision controls it can compute, as values of
    ``lower``: each the same pass in a precision below the one the
    configuration states. The tests hold every limit of ``correct``
    against them.
"""

from __future__ import annotations

import random

import numpy as np


def sample_requests(engine_requests, since: float, seed: int, k: int):
    done = [r for r in engine_requests
            if r["tokens"] and r["prompt"] is not None
            and r["enqueued_at"] >= since
            and r["finish_reason"] in ("length", "eos")]
    if not done:
        return []
    done.sort(key=lambda r: (r["enqueued_at"], r["rid"]))
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:max(0, k - 1)]


def logit_gaps(ref, weights, dims, sample, lowers=()) -> dict:
    """gap = reference's best logit minus the reference's logit of the
    served token, per served position; ``ref`` is the reference module
    the configuration names. For each lower precision in
    ``lowers`` (the control): the same gap for the token that the
    reference computed in that precision puts first. ``rows`` keeps
    the gaps request by request, for the tools that set the limits."""
    import time

    rows = {"served": [], **{m: [] for m in lowers}}
    took = []
    pad = ref.padded_len(max(
        (len(r["prompt"]) + len(r["tokens"]) for r in sample), default=0))
    for r in sample:
        t0 = time.monotonic()
        prompt, served = list(r["prompt"]), list(r["tokens"])
        seq = prompt + served
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        logits = ref.logits_at(weights, dims, seq, at, pad_to=pad)
        best = logits.max(-1)
        pos = np.arange(len(at))
        rows["served"].append(best - logits[pos, np.asarray(served)])
        for m in lowers:
            low = ref.logits_at(weights, dims, seq, at, lower=m,
                                pad_to=pad)
            rows[m].append(best - logits[pos, low.argmax(-1)])
        took.append(round(time.monotonic() - t0, 2))
    if not sample:
        return {"tokens": 0, "requests": 0}
    out = {"requests": len(sample), "seconds_each": took, "rows": rows,
           **summary(rows["served"])}
    for m in lowers:
        out[f"control_{m}"] = summary(rows[m])
    return out


def summary(rows) -> dict:
    """The numbers compared, over per-request gap arrays."""
    flat = np.concatenate(rows)
    return {"tokens": int(flat.size),
            "logit_gap_max": float(flat.max()),
            "logit_gap_mean": float(flat.mean()),
            "not_best_share": float((flat > 0).mean())}


def free_device_memory(keep) -> int:
    """Delete every live device array but ``keep``'s leaves, so the
    reference runs after the program's state is freed."""
    import jax

    mine = {id(x) for x in jax.tree.leaves(keep)}
    n = 0
    for arr in jax.live_arrays():
        if id(arr) not in mine:
            try:
                arr.delete()
                n += 1
            except RuntimeError:
                pass              # already deleted or donated
    return n
