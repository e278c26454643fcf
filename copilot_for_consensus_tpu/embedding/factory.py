"""Embedding driver registry + factory (reference: ``factory.py:26`` of
``copilot_embedding``)."""

from __future__ import annotations

from typing import Any

from copilot_for_consensus_tpu.core.factory import register_driver
from copilot_for_consensus_tpu.core.openai_compat import (
    azure_default_api_version,
)
from copilot_for_consensus_tpu.embedding.base import (
    EmbeddingProvider,
    MockEmbeddingProvider,
    TPUEmbeddingProvider,
)


def _cfg_get(config: Any, key: str, default=None):
    if config is None:
        return default
    if isinstance(config, dict):
        return config.get(key, default)
    return getattr(config, key, default)


def create_embedding_provider(config: Any = None) -> EmbeddingProvider:
    driver = _cfg_get(config, "driver", "mock")
    if driver == "mock":
        return MockEmbeddingProvider(
            dimension=int(_cfg_get(config, "dimension", 32)))
    if driver == "tpu":
        from copilot_for_consensus_tpu.parallel.mesh import (
            require_accelerator,
        )
        require_accelerator("embedding driver 'tpu'")
        return TPUEmbeddingProvider(
            model=_cfg_get(config, "model", "minilm-l6"),
            checkpoint=_cfg_get(config, "checkpoint"),
            batch_size=int(_cfg_get(config, "batch_size", 64)))
    if driver in ("openai", "azure_openai"):
        from copilot_for_consensus_tpu.embedding.openai_provider import (
            OpenAIEmbeddingProvider,
        )

        return OpenAIEmbeddingProvider(
            base_url=_cfg_get(config, "base_url", ""),
            api_key=_cfg_get(config, "api_key", "") or "",
            model=_cfg_get(config, "model", "text-embedding-3-small"),
            dimension=int(_cfg_get(config, "dimension", 1536)),
            api_version=azure_default_api_version(
                driver, _cfg_get(config, "api_version", "")),
            batch_size=int(_cfg_get(config, "batch_size", 256)))
    raise ValueError(f"unknown embedding driver {driver!r}")


register_driver("embedding_backend", "mock", create_embedding_provider)
register_driver("embedding_backend", "tpu", create_embedding_provider)
register_driver("embedding_backend", "openai", create_embedding_provider)
register_driver("embedding_backend", "azure_openai",
                create_embedding_provider)
