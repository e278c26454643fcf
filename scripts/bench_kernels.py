"""Decode-shaped weight-streaming kernel shootout (real TPU).

Measures the matmul path of one decode step in isolation — x [B, D]
chained through all layers' projections via ``lax.scan`` exactly like
``models/decoder.py`` — so candidates can be compared in minutes instead
of full-engine runs. Honesty guards (see memory: microbenchmarks lie):

* every layer has DISTINCT weights (a reused matrix becomes VMEM-resident
  and fakes a 2 TB/s "stream");
* the chain's output feeds the next layer and is returned (nothing is
  dead code);
* effective GB/s is computed from the total quantized weight bytes the
  step must read, so modes are comparable by wall time alone.

The end-to-end authority remains ``python bench.py``.

Usage: python scripts/bench_kernels.py [mode ...]
Modes: bw xla_int8 pallas_int8 w8a8 int4 w4a8 (default: all)
"""

from __future__ import annotations

import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

B = 128          # decode batch (slots)
D = 4096         # d_model
DKV = 1024       # kv proj width (8 kv heads x 128)
F = 14336        # d_ff
L = 32           # layers
GROUP = 256      # int4 scale group


def make_params(mode: str):
    """All layers' quantized projections, generated ON DEVICE by one
    jitted program instead of shipping GBs of host-built weights."""
    int4 = mode in ("int4", "w4a8", "w4a8f", "int4f")

    def build(key):
        if mode.endswith("f"):      # fused qkv + gate/up projections
            shapes = {"wqkv": (D, D + 2 * DKV), "wo": (D, D),
                      "w_gu": (D, 2 * F), "w_down": (F, D)}
        else:
            shapes = {"wq": (D, D), "wk": (D, DKV), "wv": (D, DKV),
                      "wo": (D, D), "w_gate": (D, F), "w_up": (D, F),
                      "w_down": (F, D)}
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (d, f)) in zip(keys, shapes.items()):
            if int4:
                out[name] = {
                    "q4": jax.random.randint(k, (L, d // 2, f), -128, 128,
                                             jnp.int32).astype(jnp.int8),
                    "scale": jnp.full((L, d // GROUP, f),
                                      d ** -0.5 / 4.61, jnp.float32)}
            else:
                out[name] = {
                    "q": jax.random.randint(k, (L, d, f), -127, 128,
                                            jnp.int32).astype(jnp.int8),
                    "scale": jnp.full((L, 1, f), d ** -0.5 / 73.3,
                                      jnp.float32)}
        return out

    return jax.jit(build)(jax.random.PRNGKey(0))


def weight_bytes(mode: str) -> int:
    per_layer = D * D * 2 + D * DKV * 2 + 3 * D * F
    if mode in ("int4", "w4a8"):
        per_layer //= 2
    return per_layer * L





def build_step(mode: str):
    from copilot_for_consensus_tpu.ops import quant_matmul as qm

    if mode == "xla_int8":
        def mm(x, w):
            return (x @ w["q"].astype(x.dtype)) * w["scale"].astype(x.dtype)
    elif mode == "pallas_int8":
        def mm(x, w):
            return qm.int8_matmul(x, w["q"], w["scale"])
    elif mode == "w8a8":
        def mm(x, w):
            return qm.w8a8_matmul(x, w["q"], w["scale"])
    elif mode == "int4":
        def mm(x, w):
            return qm.int4_matmul(x, w["q4"], w["scale"])
    elif mode == "w4a8":
        def mm(x, w):
            return qm.w4a8_matmul(x, w["q4"], w["scale"])
    elif mode == "w4a8f":
        def mm(x, w):
            return qm.w4a8_matmul(x, w["q4"], w["scale"])
    elif mode == "int4f":
        def mm(x, w):
            return qm.int4_matmul(x, w["q4"], w["scale"])
    else:
        raise ValueError(mode)

    if mode.endswith("f"):
        # Fused projections: 4 kernel calls per layer instead of 7 —
        # isolates per-pallas_call overhead from bandwidth.
        def step(params, x):
            def body(x, layer):
                qkv = mm(x, layer["wqkv"])
                h = qkv[:, :D] + jnp.pad(
                    qkv[:, D:D + DKV] + qkv[:, D + DKV:],
                    ((0, 0), (0, D - DKV)))
                x = x + mm(h, layer["wo"]) * 0.01
                gu = mm(x, layer["w_gu"]).astype(jnp.float32)
                gate = jax.nn.silu(gu[:, :F])
                x = x + mm((gate * gu[:, F:]).astype(x.dtype),
                           layer["w_down"]) * 0.01
                return x, None

            x, _ = jax.lax.scan(body, x, params)
            return x

        return jax.jit(step)

    def step(params, x):
        def body(x, layer):
            xq = mm(x, layer["wq"])
            xk = mm(x, layer["wk"])
            xv = mm(x, layer["wv"])
            # fold k/v back so they're not dead (decode feeds them to
            # attention; here a cheap mix keeps shape [B, D])
            h = xq + jnp.pad(xk + xv, ((0, 0), (0, D - DKV)))
            x = x + mm(h, layer["wo"]) * 0.01
            gate = jax.nn.silu(mm(x, layer["w_gate"]).astype(jnp.float32))
            up = mm(x, layer["w_up"]).astype(jnp.float32)
            x = x + mm((gate * up).astype(x.dtype),
                       layer["w_down"]) * 0.01
            return x, None

        x, _ = jax.lax.scan(body, x, params)
        return x

    return jax.jit(step)


def run_mode(mode: str) -> None:
    rng = np.random.default_rng(0)
    gb = weight_bytes(mode) / 1e9

    if mode == "bw":
        # Pure DMA roofline: in-place int8 increment over 7.5 GB —
        # reads + writes every byte (report counts both directions).
        # The buffer is donated and chained call-to-call, so no result
        # can be cached and nothing is dead.
        chunks = jax.jit(lambda k: jax.random.randint(
            k, (L, 1792, 131072), -127, 128, jnp.int32).astype(jnp.int8)
        )(jax.random.PRNGKey(1))
        gbb = chunks.nbytes / 1e9

        @jax.jit
        def bump(c):
            return c + jnp.int8(1)

        bump_d = jax.jit(bump, donate_argnums=0)
        probe = jax.jit(lambda c: c[0, 0, :8].astype(jnp.int32).sum())
        chunks = bump_d(chunks)
        jax.device_get(probe(chunks))  # timed work ends in a host fetch
        n, t0 = 5, time.monotonic()
        for _ in range(n):
            chunks = bump_d(chunks)
        jax.device_get(probe(chunks))
        dt = (time.monotonic() - t0) / n
        print(f"{mode:12s}  {dt * 1e3:8.2f} ms   {2 * gbb / dt:7.1f} GB/s "
              f"(int8 read+write stream, {gbb:.1f} GB buffer)")
        return

    params = make_params(mode)
    jax.block_until_ready(params)
    x = jnp.asarray(rng.standard_normal((B, D)), jnp.bfloat16)
    step = build_step(mode)
    t0 = time.monotonic()
    jax.device_get(step(params, x))
    compile_s = time.monotonic() - t0
    # Chain the output back in: each call's input depends on the last
    # call's output, so the backend can neither cache identical calls
    # nor elide them; ONE host fetch at the end forces the whole chain.
    n, t0 = 10, time.monotonic()
    out = x
    for _ in range(n):
        out = step(params, out)
    mean = float(np.abs(jax.device_get(out)).mean())
    dt = (time.monotonic() - t0) / n
    print(f"{mode:12s}  {dt * 1e3:8.2f} ms   {gb / dt:7.1f} GB/s "
          f"({gb:.1f} GB wts, compile {compile_s:.0f}s, "
          f"|out|={mean:.3g})")


def main() -> None:
    modes = sys.argv[1:] or ["bw", "xla_int8", "pallas_int8", "w8a8",
                             "int4", "w4a8"]
    from copilot_for_consensus_tpu.parallel.mesh import (
        enable_compile_cache,
        require_accelerator,
    )

    dev = require_accelerator("scripts/bench_kernels.py")
    enable_compile_cache()
    print(f"device: {dev.device_kind} ({dev.platform}), "
          f"B={B} D={D} F={F} L={L}")
    for mode in modes:
        run_mode(mode)


if __name__ == "__main__":
    main()
