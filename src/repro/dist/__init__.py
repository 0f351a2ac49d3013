"""Distributed runtime subsystem (DESIGN.md §11).

Two pillars over the serve path:

* ``topology``  — ``MeshPlan``: the frozen DP×TP(×EP) device grid,
  carried on ``ExecutionPolicy.mesh`` and recorded in the artifact
  manifest (``"dp2xtp4"`` shorthand).
* ``loader``    — per-rank artifact loading: each process reads only the
  ``rank_NN.npz`` files its addressable devices' model-axis coordinates
  name, and assembles global arrays from per-device addressable shards
  (``jax.make_array_from_single_device_arrays``) — no host ever
  materializes another rank's slices.
"""

from repro.dist.loader import RankLoadStats, load_per_rank
from repro.dist.topology import MeshPlan, local_model_ranks

__all__ = ["MeshPlan", "RankLoadStats", "load_per_rank",
           "local_model_ranks"]
