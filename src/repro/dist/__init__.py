"""repro.dist — the unified distributed substrate.

One pluggable communication layer behind everything that crosses (or
models crossing) a worker boundary:

* :mod:`repro.dist.meter`        — ``CommMeter`` (scalars/rounds/per-kind
  accounting) and the ``ClusterModel`` / ``TpuV5eModel`` cost models.
* :mod:`repro.dist.tree`         — the paper's Figure-5 tree
  reduce+broadcast: schedules, the canonical tree-order summation, the
  simulated executable spec, and the TPU-native ``psum`` / ppermute
  butterfly mappings.
* :mod:`repro.dist.collectives`  — the ``Collectives`` protocol and the
  ``LocalBackend`` / ``SimBackend`` single-process backends.
* :mod:`repro.dist.costs`        — ``CostModel``, the single home of the
  §4.5 per-outer closed forms (drivers charge them, benchmark schedules
  aggregate them, the drift-guard test pins them together).
* :mod:`repro.dist.shardmap`     — ``ShardMapBackend``, the deployable
  shard_map realization over a mesh axis.
* :mod:`repro.dist.metering`     — ``CommReport``, the per-method
  communication report benchmarks consume.
* :mod:`repro.dist.compat`       — the one place the repo calls
  ``jax.shard_map`` and ``jax.make_mesh`` (Auto mesh axes).
* :mod:`repro.dist.faults`       — seeded fault injection at the
  collective boundary: ``FaultPlan`` / ``RetryPolicy`` /
  ``FaultyBackend``, with retransmissions metered under the ``retry``
  kind so comm accounting stays honest under failure.

Every optimization method in :mod:`repro.core` (FD-SVRG, DSVRG, the
parameter-server baselines) takes a ``Collectives`` backend and routes
all communication accounting and modeled wall-clock through it, so
cross-method comparisons share one meter and one cost model.
"""

from repro.dist.collectives import (
    Collectives,
    LocalBackend,
    SimBackend,
)
from repro.dist.compat import make_mesh, shard_map
from repro.dist.costs import COSTS, CostModel, PhaseCost
from repro.dist.faults import (
    FaultError,
    FaultPlan,
    FaultyBackend,
    RetriesExhaustedError,
    RetryPolicy,
    WorkerCrashError,
)
from repro.dist.meter import (
    ClusterModel,
    CommEvent,
    CommMeter,
    TpuV5eModel,
    tree_rounds,
)
from repro.dist.metering import CommReport
from repro.dist.shardmap import ShardMapBackend
from repro.dist.tree import (
    broadcast_schedule,
    collective_permute_tree,
    psum_tree,
    simulate_tree_sum,
    tree_order_sum,
    tree_schedule,
)

__all__ = [
    "COSTS",
    "ClusterModel",
    "Collectives",
    "CommEvent",
    "CommMeter",
    "CommReport",
    "CostModel",
    "FaultError",
    "FaultPlan",
    "FaultyBackend",
    "PhaseCost",
    "LocalBackend",
    "RetriesExhaustedError",
    "RetryPolicy",
    "ShardMapBackend",
    "SimBackend",
    "TpuV5eModel",
    "WorkerCrashError",
    "broadcast_schedule",
    "collective_permute_tree",
    "make_mesh",
    "psum_tree",
    "shard_map",
    "simulate_tree_sum",
    "tree_order_sum",
    "tree_rounds",
    "tree_schedule",
]
