"""Isolate the decode kernel's cost components on the real chip.

Times one pallas_call per (mode, tensor) over L distinct [D, F] int8/int4
weight tensors at decode batch B, chaining outputs and ending timed
work in a host fetch. Modes:

  dma       grid streams the weight; body does a trivial reduce of one
            sublane — pure DMA-pipeline ceiling for weight-shaped reads
  convdot   int8 tile -> bf16 convert -> bf16 MXU dot (pallas_int8 body)
  i8dot     native int8 MXU dot, scales at finalize (w8a8 body)
  unpack8   packed int4 tile -> int8-domain nibble unpack -> int8 dots
            per scale group (w4a8 body, no int32 widening)
  unpack32  same but widening through int32 (the r2 kernel's unpack)

Usage: python scripts/probe_stream.py [mode ...]
"""

from __future__ import annotations

import functools
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

B, D, F, L = 128, 4096, 14336, 20
GROUP = 256


def k_dma(x_ref, w_ref, o_ref, acc_ref):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
    # touch the tile cheaply: one sublane row into the accumulator
    acc_ref[:] += w_ref[0, :].astype(jnp.float32)[None, :]

    @pl.when(di == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = (acc_ref[:]
                    + x_ref[:, :1].astype(jnp.float32)).astype(o_ref.dtype)


def k_convdot(x_ref, w_ref, o_ref, acc_ref):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
    acc_ref[:] += jax.lax.dot(x_ref[:], w_ref[:].astype(x_ref.dtype),
                              preferred_element_type=jnp.float32)

    @pl.when(di == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def k_i8dot(x_ref, w_ref, o_ref, acc_ref):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
    acc_ref[:] += jax.lax.dot(x_ref[:], w_ref[:],
                              preferred_element_type=jnp.int32)

    @pl.when(di == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = (acc_ref[:].astype(jnp.float32) * 1e-4).astype(
            o_ref.dtype)


def k_unpack(xe_ref, xo_ref, w_ref, o_ref, acc_ref, *, widen: bool,
             groups: int, gdp: int):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
    if widen:
        p = w_ref[:].astype(jnp.int32)
        lo = (((p & 0xF) ^ 8) - 8).astype(jnp.int8)
        hi = (p >> 4).astype(jnp.int8)
    else:
        p = w_ref[:]
        lo = ((p & jnp.int8(0xF)) ^ jnp.int8(8)) - jnp.int8(8)
        hi = p >> 4              # arithmetic shift keeps the sign
    part = jnp.zeros_like(acc_ref)
    for g in range(groups):
        sl = slice(g * gdp, (g + 1) * gdp)
        pg = jax.lax.dot(xe_ref[:, sl], lo[sl],
                         preferred_element_type=jnp.int32)
        pg += jax.lax.dot(xo_ref[:, sl], hi[sl],
                          preferred_element_type=jnp.int32)
        part += pg.astype(jnp.float32) * (1e-4 * (g + 1))
    acc_ref[:] += part

    @pl.when(di == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def build(mode: str):
    bf, bd = 512, 2048
    if mode in ("dma", "convdot", "i8dot"):
        w = jax.jit(lambda k: jax.random.randint(
            k, (L, D, F), -127, 128, jnp.int32).astype(jnp.int8)
        )(jax.random.PRNGKey(0))
        kern = {"dma": k_dma, "convdot": k_convdot, "i8dot": k_i8dot}[mode]
        xdt = jnp.int8 if mode == "i8dot" else jnp.bfloat16

        def one(x, wl):
            return pl.pallas_call(
                kern,
                grid=(F // bf, D // bd),
                in_specs=[pl.BlockSpec((B, bd), lambda j, k: (0, k)),
                          pl.BlockSpec((bd, bf), lambda j, k: (k, j))],
                out_specs=pl.BlockSpec((B, bf), lambda j, k: (0, j)),
                out_shape=jax.ShapeDtypeStruct((B, F), jnp.bfloat16),
                scratch_shapes=[pltpu.VMEM((B, bf), jnp.float32
                                           if mode != "i8dot"
                                           else jnp.int32)],
            )(x.astype(xdt) if xdt == jnp.int8 else x, wl)
    else:
        w = jax.jit(lambda k: jax.random.randint(
            k, (L, D // 2, F), -128, 128, jnp.int32).astype(jnp.int8)
        )(jax.random.PRNGKey(0))
        widen = mode == "unpack32"
        gdp = GROUP // 2
        bdp = bd // 2
        groups = bdp // gdp
        kern = functools.partial(k_unpack, widen=widen, groups=groups,
                                 gdp=gdp)

        def one(x, wl):
            xq = jnp.clip(jnp.round(x.astype(jnp.float32) * 16), -127,
                          127).astype(jnp.int8)
            return pl.pallas_call(
                kern,
                grid=(F // bf, (D // 2) // bdp),
                in_specs=[pl.BlockSpec((B, bdp), lambda j, k: (0, k)),
                          pl.BlockSpec((B, bdp), lambda j, k: (0, k)),
                          pl.BlockSpec((bdp, bf), lambda j, k: (k, j))],
                out_specs=pl.BlockSpec((B, bf), lambda j, k: (0, j)),
                out_shape=jax.ShapeDtypeStruct((B, F), jnp.bfloat16),
                scratch_shapes=[pltpu.VMEM((B, bf), jnp.float32)],
            )(xq[:, 0::2], xq[:, 1::2], wl)

    def step(w, x):
        def body(x, wl):
            y = one(x, wl)
            # fold [B, F] back to [B, D] cheaply so layers chain
            return jnp.tanh(y[:, :D] * 1e-2) , None

        x, _ = jax.lax.scan(body, x, w)
        return x

    def step_n(w, x, n):
        # n chained passes INSIDE one program, so per-dispatch host
        # cost (not measured on the current chip) cannot dwarf a few
        # ms of kernel difference.
        def body(x, _):
            return step(w, x), None

        x, _ = jax.lax.scan(body, x, None, length=n)
        return x

    return w, jax.jit(step_n, static_argnames="n")


def run(mode: str) -> None:
    w, step_n = build(mode)
    gb = w.nbytes / 1e9
    x = jnp.asarray(np.random.default_rng(0).standard_normal((B, D)),
                    jnp.bfloat16)
    n = 10
    t0 = time.monotonic()
    jax.device_get(step_n(w, x, n))
    compile_s = time.monotonic() - t0
    t0 = time.monotonic()
    mean = float(np.abs(jax.device_get(step_n(w, x, n))).mean())
    dt = (time.monotonic() - t0) / n
    print(f"{mode:10s}  {dt * 1e3:8.2f} ms   {gb / dt:7.1f} GB/s "
          f"({gb:.1f} GB, compile {compile_s:.0f}s, |out|={mean:.3g})")


def main() -> None:
    modes = sys.argv[1:] or ["dma", "convdot", "i8dot", "unpack8",
                             "unpack32"]
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}, B={B} D={D} F={F} L={L} "
          f"(per-pass bytes = one [D,F] tensor x L)")
    for m in modes:
        run(m)


if __name__ == "__main__":
    main()
