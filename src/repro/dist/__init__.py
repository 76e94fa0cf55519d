"""Distributed partitioning layer.

``repro.dist.sharding`` holds the SPMD sharding rules (PartitionSpec
legalization + pytree rules for params / optimizer state / batches / GEAR
caches); ``repro.dist.compat`` holds the partially manual ``shard_map``
the train loop runs PowerSGD under.
"""

from repro.dist import compat, sharding
from repro.dist.sharding import (
    batch_pspecs,
    cache_pspecs,
    fit_spec,
    param_pspecs,
    shardings_for,
    zero1_pspecs,
)

__all__ = [
    "compat",
    "sharding",
    "fit_spec",
    "param_pspecs",
    "zero1_pspecs",
    "batch_pspecs",
    "cache_pspecs",
    "shardings_for",
]
