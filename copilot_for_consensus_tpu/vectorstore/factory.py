"""Vector store driver registration + create_vector_store."""

from __future__ import annotations

from typing import Any

from copilot_for_consensus_tpu.core.factory import register_driver
from copilot_for_consensus_tpu.vectorstore.memory import InMemoryVectorStore


def create_vector_store(config: Any = None):
    cfg = dict(config or {})
    driver = cfg.get("driver", "memory")
    if driver == "memory":
        return InMemoryVectorStore(cfg)
    if driver == "tpu":
        from copilot_for_consensus_tpu.parallel.mesh import (
            require_accelerator,
        )
        from copilot_for_consensus_tpu.vectorstore.tpu import TPUVectorStore

        require_accelerator("vector_store driver 'tpu'")
        return TPUVectorStore(cfg)
    if driver == "native":
        from copilot_for_consensus_tpu.vectorstore.native import NativeFlatVectorStore

        return NativeFlatVectorStore(cfg)
    if driver == "azure_ai_search":
        from copilot_for_consensus_tpu.vectorstore.azure_ai_search import (
            AzureAISearchVectorStore,
        )

        return AzureAISearchVectorStore(cfg)
    raise ValueError(f"unknown vector_store driver {driver!r}")


for _name in ("memory", "tpu", "native", "azure_ai_search"):
    register_driver("vector_store", _name, create_vector_store)
