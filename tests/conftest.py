# Test harness: force an 8-device virtual CPU platform BEFORE jax initialises.
#
# Mirrors the reference's fake-backend strategy (SURVEY.md §4): the full
# multi-chip sharding path is exercised on a virtual device mesh so the suite
# runs anywhere; chip_smoke.py and bench.py (not pytest) are what touch the
# real TPU chip.
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests use the fake mesh, never the chip
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# A pytest plugin may have imported jax before this file ran, in which case
# jax snapshotted JAX_PLATFORMS from the original env. Backends are still
# uninitialized here, so a config update wins either way.
import jax

jax.config.update("jax_platforms", "cpu")

import pathlib
import sys

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


@pytest.fixture
def fixtures_dir() -> pathlib.Path:
    return FIXTURES


@pytest.fixture
def copy_cycle():
    """The spec-decode acceptance fixture (test_engine_spec_decode.py):
    ``(cfg, params, prompt)`` for a tiny f32 decoder whose attention/FFN
    outputs are zeroed and whose one-hot embeddings + lm_head make
    greedy generation the exact cycle t -> 3 + ((t - 3 + 1) % 7). The
    model copies forever, so prompt-lookup drafts ALWAYS hit and the
    verify dispatch is guaranteed to run — random-weight prompts only
    drafted under one jax release's random stream."""
    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.models import decoder
    from copilot_for_consensus_tpu.models.configs import decoder_config

    period = 7
    cfg = decoder_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(7), cfg,
                                 dtype=jnp.float32)
    params["layers"]["wo"] = jnp.zeros_like(params["layers"]["wo"])
    params["layers"]["w_down"] = jnp.zeros_like(
        params["layers"]["w_down"])
    emb = np.zeros((cfg.vocab_size, cfg.d_model), np.float32)
    head = np.zeros((cfg.d_model, cfg.vocab_size), np.float32)
    for i in range(period):
        emb[3 + i, i] = 1.0
        head[i, 3 + (i + 1) % period] = 1.0
    params["tok_emb"] = jnp.asarray(emb)
    params["lm_head"] = jnp.asarray(head)
    return cfg, params, [3 + (i % period) for i in range(2 * period)]


# -- telemetry-bundle CI artifact --------------------------------------
#
# When COPILOT_FLIGHT_RECORD_DIR is set (ci.yml exports it for the test
# lanes), engine telemetry auto-dumps land there on engine errors, and
# the hook below additionally dumps every live recorder when a test
# FAILS — flight records, pipeline trace dumps, AND every live
# telemetry shipper's spool (obs/ship.py) land in ONE directory that
# ci.yml uploads as the telemetry-bundle artifact. A red suite ships
# its whole post-mortem (per-dispatch step records, span DAGs readable
# by tools/tracepath, crash-safe spools readable by the aggregator and
# the slo CLI) instead of a bare traceback. The env read happens here
# in the harness, not in the package (test_no_runtime_env_vars policy).
_FLIGHT_DIR = os.environ.get("COPILOT_FLIGHT_RECORD_DIR", "")
if _FLIGHT_DIR:
    from copilot_for_consensus_tpu.engine import telemetry as _telemetry
    from copilot_for_consensus_tpu.obs import ship as _ship
    from copilot_for_consensus_tpu.obs import trace as _trace

    _telemetry.set_default_dump_dir(_FLIGHT_DIR)
    # Pipeline trace dumps (obs/trace.py) land in the same artifact
    # directory, so a red pipeline test ships its span DAG (stage
    # spans + queue waits + correlation ids, readable by
    # tools/tracepath) alongside the engine flight records.
    _trace.set_default_dump_dir(_FLIGHT_DIR)
    # Shippers built without an explicit path spool here too — the
    # failure hook flushes them so committed rows are in the bundle.
    _ship.set_default_spool_dir(_FLIGHT_DIR)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    if not _FLIGHT_DIR:
        return
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed:
        import re

        from copilot_for_consensus_tpu.engine import (
            telemetry as _telemetry,
        )
        from copilot_for_consensus_tpu.obs import ship as _ship
        from copilot_for_consensus_tpu.obs import trace as _trace

        tag = re.sub(r"[^A-Za-z0-9._-]+", "_", item.nodeid)[-80:]
        _telemetry.dump_all(_FLIGHT_DIR, tag=tag)
        _trace.dump_all(_FLIGHT_DIR, tag=f"pipeline-trace-{tag}")
        _ship.dump_all(_FLIGHT_DIR, tag=f"telemetry-{tag}")
