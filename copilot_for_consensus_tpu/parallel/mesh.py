"""Mesh construction for single-host, multi-chip, and multi-slice runs."""

from __future__ import annotations

import pathlib
import re
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

from copilot_for_consensus_tpu.analysis.contracts import checkable

MESH_AXES = ("dp", "pp", "sp", "ep", "tp")

#: Where compiled programs persist when nothing outside the process
#: placed the cache: one fixed directory at the root of the checkout.
#: The path is part of JAX's cache key, so it must never vary by run
#: (no temp name, pid or timestamp).
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_COMPILE_CACHE_DIR = str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points (``serve``, ``bench.py``, ``chip_smoke.py``, the chip
    benches under ``scripts/``) call this before their first compile.
    A directory placed from outside — ``JAX_COMPILATION_CACHE_DIR``,
    which JAX reads into ``jax_compilation_cache_dir`` itself — is left
    alone and nothing else is set: whoever placed it owns its policy
    (the persistence thresholds ride the same env). Otherwise the cache
    goes to :data:`DEFAULT_COMPILE_CACHE_DIR` and keeps EVERY program:
    under JAX's default a compile is only persisted when it took over a
    second, so a sub-second program would recompile on every start and
    one that straddles the second would be written on some runs only.

    Either way the cache key takes in the programs' metadata (``op_name``
    with its ``named_scope`` path, source lines), with this checkout's
    root cut off the file names so that a copy elsewhere still hits. By
    JAX's default the key leaves metadata out, and a hit then hands back
    an executable carrying the names of WHATEVER code compiled it first:
    a trace of this code read ``obs/profile.py:SCOPES`` nowhere because
    its decode programs came from a cache that an older checkout had
    filled (PERF.md, PR 26). The price is one compile per program after
    an edit that moves lines under a jitted function."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(str(_CHECKOUT) + "/"))
    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return DEFAULT_COMPILE_CACHE_DIR


def require_accelerator(what: str) -> "jax.Device":
    """Refuse to run ``what`` on a backend nobody asked for.

    When TPU initialisation fails JAX drops to the CPU with a warning,
    and everything downstream — ``attn_impl="auto"``, ``kv_kernel=
    "auto"``, the Pallas kernels' interpret switch — would quietly
    serve a 7B model through XLA:CPU and interpreted kernels. A process
    that builds a ``tpu`` driver or runs a chip bench therefore needs
    ``jax.default_backend() == "tpu"`` unless the caller named another
    platform explicitly (``JAX_PLATFORMS=cpu``, which JAX reads into
    ``jax_platforms`` — the CPU test lanes set it). Returns the first
    device so callers can name it in their output."""
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not jax.config.jax_platforms:
        raise RuntimeError(
            f"{what} needs a TPU, but JAX came up on {dev.platform!r} "
            f"({dev.device_kind}) and no platform was requested: the "
            f"TPU runtime failed to initialise or no chip is attached. "
            f"Set JAX_PLATFORMS=cpu to run on the CPU on purpose.")
    return dev


@dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. Product must equal the device count.

    Leave ``tp`` at 0 to auto-fill it with the remaining devices (serving
    default: shard the model), or set ``tp`` and leave ``dp`` at 0 to
    auto-fill the data axis instead.
    """

    dp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    tp: int = 0

    def resolve(self, n_devices: int) -> "MeshConfig":
        dp, pp, sp, ep, tp = self.dp, self.pp, self.sp, self.ep, self.tp
        if tp == 0:
            fixed = max(1, dp) * max(1, pp) * max(1, sp) * max(1, ep)
            if n_devices % fixed:
                raise ValueError(
                    f"mesh axes dp={dp} pp={pp} sp={sp} ep={ep} do not "
                    f"divide {n_devices} devices"
                )
            tp = n_devices // fixed
        elif dp == 0:
            fixed = max(1, pp) * max(1, sp) * max(1, ep) * tp
            if n_devices % fixed:
                raise ValueError(
                    f"mesh axes pp={pp} sp={sp} ep={ep} tp={tp} do not "
                    f"divide {n_devices} devices"
                )
            dp = n_devices // fixed
        total = max(1, dp) * max(1, pp) * max(1, sp) * max(1, ep) * tp
        if total != n_devices:
            raise ValueError(
                f"mesh {dp}x{pp}x{sp}x{ep}x{tp}={total} != "
                f"{n_devices} devices"
            )
        return MeshConfig(max(1, dp), max(1, pp), max(1, sp), max(1, ep),
                          tp)

    @property
    def shape(self) -> tuple[int, int, int, int, int]:
        return (self.dp, self.pp, self.sp, self.ep, self.tp)


def build_mesh(config: MeshConfig | None = None,
               devices: list | None = None) -> Mesh:
    """Build the 4-axis mesh over all (or the given) devices.

    Axis order is (dp, sp, ep, tp) with tp innermost so tensor-parallel
    collectives ride the fastest ICI links, the standard TPU layout.
    """
    devs = devices if devices is not None else jax.devices()
    cfg = (config or MeshConfig()).resolve(len(devs))
    arr = np.array(devs).reshape(cfg.shape)
    return Mesh(arr, MESH_AXES)


def local_mesh(tp: int | None = None) -> Mesh:
    """Convenience single-axis-of-interest mesh on local devices: all tp."""
    n = len(jax.devices())
    t = tp or n
    if n % t:
        raise ValueError(f"tp={t} does not divide {n} devices")
    return build_mesh(MeshConfig(dp=n // t, tp=t))


def retrieval_mesh(n_devices: int | None = None) -> Mesh:
    """ANN retrieval plane (vectorstore/ivf.py): dp-only mesh — every
    device owns one posting-list shard, no tp axis because the
    candidate rescore is a shard-local matvec with a host top-k merge
    (no collectives in the search dispatch, by contract)."""
    n = n_devices if n_devices is not None else len(jax.devices())
    return build_mesh(MeshConfig(dp=n, tp=1), devices=jax.devices()[:n])


def largest_pow2_leq(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n else 1


def auto_mesh_for_serving(n_devices: int | None = None) -> Mesh:
    """Serving default: tp = largest power of two ≤ device count, dp rest."""
    n = n_devices if n_devices is not None else len(jax.devices())
    tp = largest_pow2_leq(n)
    while n % tp:
        tp //= 2
    return build_mesh(MeshConfig(dp=n // tp, tp=tp),
                      devices=jax.devices()[:n])


# ---------------------------------------------------------------------------
# shardcheck contracts (analysis/shardcheck.py)
# ---------------------------------------------------------------------------


@checkable("serving-meshes")
def _shardcheck_serving_meshes():
    """Every mesh this module can build for serving must carry every
    axis the sharding rules target — MESH_AXES and
    ``sharding.DEFAULT_RULES`` are maintained in different files, and a
    rename on either side must fail CI, not replicate weights."""
    from copilot_for_consensus_tpu.analysis.contracts import (
        ContractCase,
        require_devices,
    )
    from copilot_for_consensus_tpu.parallel.sharding import DEFAULT_RULES

    require_devices(8)
    devs = jax.devices()[:8]
    return [
        ContractCase(label="serving-default",
                     mesh=build_mesh(MeshConfig(), devices=devs),
                     rules=DEFAULT_RULES),
        ContractCase(label="auto-serving",
                     mesh=auto_mesh_for_serving(8),
                     rules=DEFAULT_RULES),
        ContractCase(label="sp4",
                     mesh=build_mesh(MeshConfig(sp=4), devices=devs),
                     rules=DEFAULT_RULES),
    ]
