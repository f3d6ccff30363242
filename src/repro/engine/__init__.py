"""repro.engine: sharded, batched query execution over LSM-tree shards.

The layer between the serving runtime and the storage substrate,
organized as **plan -> submit -> collect**: typed columnar ``OpBatch``es
— point lookups, writes, range scans, and range deletes — are compiled
by a ``Planner`` into per-shard ``ShardPlan``s (vectorized routing,
range clipping, same-kind run grouping), launched by ``Engine.submit``
(concurrently across shards when pipelining is on), and merged back in
request order by the returned ``PendingBatch``.  Read batches execute
through the fused Pallas filter stage (Bloom + DR-tree interval kernels,
for point gets and scan validity alike), charge I/O through a
read-through block cache, and roll per-shard ledgers up into
per-op-class engine stats with per-shard wall/stall times.

Public surface: ``Engine`` (the façade), ``OpBatch`` / ``Planner`` /
``Plan`` / ``ShardPlan`` (typed batches + compilation), ``PendingBatch``
(collection), ``EngineConfig`` (execution knobs), ``ShardRouter``
(partitioning), ``ShardExecutor`` (per-shard batched paths),
``BlockCache``, and the stats types.
"""

from .cache import BlockCache
from .engine import Engine
from .executor import EngineConfig, ShardExecutor
from .pending import PendingBatch
from .plan import (KIND_CODES, KIND_NAMES, OP_DELETE, OP_GET, OP_PUT,
                   OP_RANGE_DELETE, OP_RANGE_SCAN, OpBatch, Plan, Planner,
                   PlanStep, ShardPlan)
from .registry import CascadeView, DeviceFilterRegistry
from .router import ShardRouter
from .stats import EngineStats, KernelCounters, merge_io_snapshots

__all__ = ["BlockCache", "Engine", "EngineConfig", "ShardExecutor",
           "ShardRouter", "EngineStats", "KernelCounters",
           "merge_io_snapshots", "OpBatch", "Plan", "Planner", "PlanStep",
           "ShardPlan", "PendingBatch", "CascadeView",
           "DeviceFilterRegistry", "KIND_CODES", "KIND_NAMES",
           "OP_PUT", "OP_DELETE", "OP_GET", "OP_RANGE_DELETE",
           "OP_RANGE_SCAN"]
