"""Distribution layer: layout hints, sharding specs, and collectives.

Three concerns, three modules:

* ``hints``   — thread-local layout state + ``shard_hint`` constraints that
  model code sprinkles on intermediates. Exact identity when no mesh is
  active, so the same model files run unchanged on 1 CPU device.
* ``sharding``— pytree NamedSharding builders consumed by launch/steps.py
  (params / caches / batches for the LM, DLRM and GNN config families).
* ``collectives`` — shard_map-based sharded attention paths (head-sharded
  decode with an all-gather epilogue; sequence-sharded LSE-combined decode).

``collectives`` is imported lazily by callers (it pulls in the model layer,
which itself imports ``hints`` — keeping this __init__ light avoids the
cycle at package-import time).
"""
from . import hints, sharding  # noqa: F401
from .hints import (current_layout, layout, mesh_info, shard_hint,  # noqa: F401
                    suspend_hints)
from .sharding import (batch_sharding, dlrm_param_shardings,  # noqa: F401
                       dp_axes, gnn_batch_shardings, lm_cache_shardings,
                       lm_param_shardings, model_axis_size, replicated)

__all__ = [
    "batch_sharding", "current_layout", "dlrm_param_shardings", "dp_axes",
    "gnn_batch_shardings", "hints", "layout", "lm_cache_shardings",
    "lm_param_shardings", "mesh_info", "model_axis_size", "replicated",
    "shard_hint", "sharding", "suspend_hints",
]
