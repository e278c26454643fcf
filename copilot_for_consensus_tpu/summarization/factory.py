"""LLM-backend driver registry (reference dispatch: ``factory.py:89-94``
of ``copilot_summarization`` — llm_local/llm_llamacpp/llm_openai/... all
collapse into ``tpu`` here, plus ``mock``)."""

from __future__ import annotations

from typing import Any

from copilot_for_consensus_tpu.core.factory import register_driver
from copilot_for_consensus_tpu.core.openai_compat import (
    azure_default_api_version,
)
from copilot_for_consensus_tpu.summarization.base import (
    MockSummarizer,
    Summarizer,
)


def _cfg_get(config: Any, key: str, default=None):
    if config is None:
        return default
    if isinstance(config, dict):
        return config.get(key, default)
    return getattr(config, key, default)


def create_summarizer(config: Any = None, **kwargs: Any) -> Summarizer:
    driver = _cfg_get(config, "driver", "mock")
    if driver == "mock":
        return MockSummarizer(
            max_sentences=int(_cfg_get(config, "max_sentences", 3)))
    if driver == "tpu":
        from copilot_for_consensus_tpu.parallel.mesh import (
            require_accelerator,
        )
        from copilot_for_consensus_tpu.summarization.tpu_summarizer import (
            TPUSummarizer,
        )
        require_accelerator("llm driver 'tpu'")
        return TPUSummarizer(
            model=_cfg_get(config, "model", "mistral-7b"),
            max_new_tokens=int(_cfg_get(config, "max_new_tokens", 256)),
            num_slots=int(_cfg_get(config, "num_slots", 4)),
            max_len=int(_cfg_get(config, "max_len", 4096)),
            checkpoint=_cfg_get(config, "checkpoint"),
            kv_dtype=_cfg_get(config, "kv_dtype"),
            quantize=_cfg_get(config, "quantize", "int8"),
            long_context=bool(_cfg_get(config, "long_context", False)),
            profile_dir=_cfg_get(config, "profile_dir"),
            # resilience (engine/supervisor.py): supervisor=true wires
            # watchdog + request replay + degraded-mode breakers into
            # the engine's dispatcher; deadline_s drops expired work
            supervisor=_cfg_get(config, "supervisor", None),
            deadline_s=_cfg_get(config, "deadline_s", None),
            # durable request journal (engine/journal.py): a config
            # dict {"path": ..., "checkpoint_every": ...} or the
            # "journal_path" string shorthand — either way the engine
            # warm-restarts from it, so a pipeline-process kill costs
            # latency, not work
            journal=(_cfg_get(config, "journal", None)
                     or _cfg_get(config, "journal_path", None)),
            **kwargs,
        )
    if driver in ("openai", "azure_openai"):
        # One client covers the reference's llm_openai AND
        # llm_azure_openai_gpt drivers (openai_summarizer.py:23), plus
        # any OpenAI-compatible server (vLLM/Ollama/llama.cpp).
        from copilot_for_consensus_tpu.summarization.openai_summarizer \
            import OpenAISummarizer

        return OpenAISummarizer(
            base_url=_cfg_get(config, "base_url", ""),
            api_key=_cfg_get(config, "api_key", "") or "",
            model=_cfg_get(config, "model", "gpt-4o-mini"),
            temperature=float(_cfg_get(config, "temperature", 0.2)),
            max_tokens=int(_cfg_get(config, "max_tokens", 512)),
            api_version=azure_default_api_version(
                driver, _cfg_get(config, "api_version", "")),
        )
    raise ValueError(f"unknown llm_backend driver {driver!r}")


register_driver("llm_backend", "mock", create_summarizer)
register_driver("llm_backend", "tpu", create_summarizer)
register_driver("llm_backend", "openai", create_summarizer)
register_driver("llm_backend", "azure_openai", create_summarizer)
