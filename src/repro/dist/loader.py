"""Per-rank artifact loading: each process reads only its own shards.

``DeploymentArtifact.load`` reads every ``rank_NN.npz`` and (for mesh
serving) reassembles the global pytree on the host — fine for a single
process that owns the whole mesh, wasteful-to-impossible once the mesh
spans processes: a host would materialize TP-degree times the weights it
can actually place, and at full-model scale wouldn't fit.

``load_per_rank`` is the distributed path.  For a ``("data", "model")``
mesh it:

1. asks ``topology.local_model_ranks`` which model-axis coordinates this
   process's addressable devices sit on,
2. ``checkpoint.load``\\ s exactly those ``rank_NN.npz`` files — the other
   ranks' files are *stat*-ed for the byte ledger but never opened,
3. assembles each leaf as a global ``jax.Array`` from per-device
   addressable shards via ``jax.make_array_from_single_device_arrays``:
   a leaf pre-split along dim ``d`` (the manifest's ``leaf_shards``)
   gets ``NamedSharding(mesh, P(..., "model" @ d, ...))`` with device
   ``(i, j)`` holding rank ``j``'s slice verbatim; an unsplit leaf is
   replicated (``P()``) from the lowest local rank's copy.

Because rank ``j``'s file *is* the ``j``-th slice of every split leaf
(``plan/compiler.stage_shard`` wrote it that way), placement is pure
``device_put`` — no slicing, no concatenation, and crucially no host
copy of any rank this process doesn't own.  The sharding matches
``schemes.pair_pspecs``, so ``shard_map`` consumes the arrays in place.

``RankLoadStats`` is the proof: ``file_bytes_loaded`` (disk bytes this
process read) vs ``file_bytes_total`` (all rank files, sizes via
``os.path.getsize`` only) — a multi-process launch asserts strictly
less-than; the serve banner prints both.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.topology import local_model_ranks

__all__ = ["RankLoadStats", "load_per_rank", "rank_file"]


@dataclasses.dataclass(frozen=True)
class RankLoadStats:
    """What this process actually read off disk (see module doc)."""

    ranks: tuple                 # model-axis ranks whose files were read
    bytes_loaded: int            # sum of leaf nbytes across those files
    file_bytes_loaded: int       # on-disk bytes of the files read
    file_bytes_total: int        # on-disk bytes of ALL rank files

    @property
    def resident_fraction(self) -> float:
        if not self.file_bytes_total:
            return 1.0
        return self.file_bytes_loaded / self.file_bytes_total


def rank_file(dirpath: str, r: int) -> str:
    return os.path.join(dirpath, f"rank_{r:02d}.npz")


def load_per_rank(dirpath: str, manifest: dict,
                  mesh: jax.sharding.Mesh) -> tuple[Any, RankLoadStats]:
    """Load a prepared artifact directory for ``mesh``, reading only this
    process's rank files.  Returns ``(params, stats)`` where ``params`` is
    the planned pytree with every leaf a global ``jax.Array`` sharded (or
    replicated) over ``mesh``.
    """
    from repro.train import checkpoint

    tp = int(manifest["tp"])
    model_dim = mesh.devices.shape[-1]
    if model_dim != tp:
        raise ValueError(
            f"mesh model-axis degree {model_dim} != artifact TP {tp}; "
            "re-run prepare for this mesh")

    ranks = local_model_ranks(mesh)
    if not ranks:
        raise RuntimeError(
            f"process {jax.process_index()} owns no devices on this mesh")
    missing = [r for r in range(tp)
               if not os.path.exists(rank_file(dirpath, r))]
    if missing:
        raise FileNotFoundError(
            f"{dirpath} is missing rank files {missing} (artifact was "
            f"prepared for tp={tp})")

    # staged in host memory and put on each device straight from there: a
    # default-device load would first gather every local rank on one chip
    trees = {r: checkpoint.load(rank_file(dirpath, r), host=True)
             for r in ranks}
    flats = {r: checkpoint.flatten_keys(t) for r, t in trees.items()}
    r0 = ranks[0]
    shards = manifest["leaf_shards"]

    # addressable (device, model-coord) pairs: device grid column j holds
    # rank j's slice of every split leaf (replicated along the data axis)
    pid = jax.process_index()
    grid = np.asarray(mesh.devices, dtype=object)
    addr = [(dev, int(idx[-1])) for idx, dev in np.ndenumerate(grid)
            if dev.process_index == pid]

    leaves = []
    for key, leaf0 in flats[r0].items():
        dim = shards.get(key)
        lshape = tuple(np.shape(leaf0))
        if dim is None:
            gshape = lshape
            sharding = NamedSharding(mesh, P())
            arrs = [jax.device_put(leaf0, dev) for dev, _ in addr]
        else:
            dim = int(dim)
            gshape = lshape[:dim] + (lshape[dim] * tp,) + lshape[dim + 1:]
            spec = [None] * len(lshape)
            spec[dim] = "model"
            sharding = NamedSharding(mesh, P(*spec))
            arrs = [jax.device_put(flats[j][key], dev) for dev, j in addr]
        leaves.append(jax.make_array_from_single_device_arrays(
            gshape, sharding, arrs))

    # flatten_keys iterates in tree_flatten leaf order, so unflattening
    # through the local tree's structure reproduces the planned pytree
    treedef = jax.tree_util.tree_structure(trees[r0])
    params = jax.tree_util.tree_unflatten(treedef, leaves)

    stats = RankLoadStats(
        ranks=ranks,
        bytes_loaded=sum(int(v.nbytes)
                         for f in flats.values() for v in f.values()),
        file_bytes_loaded=sum(os.path.getsize(rank_file(dirpath, r))
                              for r in ranks),
        file_bytes_total=sum(os.path.getsize(rank_file(dirpath, r))
                             for r in range(tp)))
    return params, stats
