"""DeploymentArtifact — the frozen, serialized output of the plan compiler.

One directory per deployment:

* ``manifest.json`` — everything needed to validate a load: format
  version, arch id + config hash, the full ``ExecutionPolicy`` (scheme,
  backend, dtypes, collective shorthand — for a per-layer
  ``CollectivePlan`` the full ``per-layer:`` form, echoed structurally
  under ``collective_plan`` and, when the autotuner chose it, scored
  per site under ``collective_tuner``), the target TP degree, per-pair
  layout metadata from the compiler stages, and the per-leaf shard map
  (which dim of each checkpoint leaf was pre-split).
* ``rank_NN.npz`` — per-rank planned pytrees (packed uint32 weights,
  perms, scales, static scheme fields) via the schema-embedding
  ``train/checkpoint.py`` format.
* ``aux.npz`` — optional beyond-paper extras (attention V->O folds).

Loading NEVER re-runs GPTQ or the layout planner; ``validate`` refuses a
mismatched config, policy, or mesh degree, so an artifact can't silently
serve under a plan it wasn't compiled for.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.comm.spec import CollectivePlan
from repro.core.policy import ExecutionPolicy

FORMAT_VERSION = 1
MANIFEST = "manifest.json"


class PlanMismatchError(ValueError):
    """A deployment artifact was asked to serve under the wrong plan."""


def config_hash(cfg) -> str:
    """Stable content hash of a ``ModelConfig`` (nested dataclasses)."""
    blob = repr(sorted(dataclasses.asdict(cfg).items()))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def policy_fields(policy: ExecutionPolicy) -> dict:
    """The manifest's view of an ``ExecutionPolicy`` (strings only).

    ``backend``, ``kv`` and ``mesh`` are recorded for provenance (so a
    served stats endpoint and the artifact agree on what was prepared)
    but excluded from ``validate``'s comparison: every kernel backend
    computes the same GEMMs over the same planned layout (``auto`` picks
    it on the serving platform — an artifact prepared on a CPU host
    serves with the Pallas kernels on a TPU), the cache layout is a pure
    runtime decision, and the device grid may differ per deployment as
    long as the model-axis degree matches the shards (which
    ``validate``'s ``tp`` check pins) — an artifact prepared dp1xtp2
    serves dp4xtp2.
    """
    return {
        "scheme": policy.scheme,
        "backend": policy.backend,
        "compute_dtype": jnp.dtype(policy.compute_dtype).name,
        "accum_dtype": jnp.dtype(policy.accum_dtype).name,
        "collective": policy.collective.shorthand(),
        "kv": policy.kv.shorthand(),
        "mesh": policy.mesh.shorthand(),
    }


@dataclasses.dataclass(frozen=True)
class DeploymentArtifact:
    """Frozen (manifest, per-rank planned pytrees, aux) triple.

    Two load shapes: ``load`` holds every rank's host pytree in
    ``rank_params`` (single-process serving; ``params`` reassembles);
    ``load_for_mesh`` holds NO host copies — ``global_params`` is the
    already-device-sharded tree assembled from only this process's rank
    files (``dist/loader.py``), and ``load_stats`` is the byte ledger
    proving which files were read."""

    manifest: dict
    rank_params: tuple = ()          # tp per-rank planned pytrees
    aux: Optional[dict] = None       # e.g. {"attn_plans": {path: pairs}}
    global_params: Any = None        # mesh-sharded tree (load_for_mesh)
    load_stats: Any = None           # dist.loader.RankLoadStats

    # ---- construction -----------------------------------------------------

    @classmethod
    def from_state(cls, state, *, seed: Optional[int] = None,
                   extra: Optional[dict] = None) -> "DeploymentArtifact":
        """Freeze a fully-run ``PlanState`` (see ``compiler.run_stages``).

        ``extra``: caller-provenance manifest fields (e.g. the CLI's
        ``smoke`` flag) — merged in, never overriding the plan fields."""
        if state.rank_params is None:
            raise ValueError(
                "PlanState has no rank shards; run stage_shard (tp=...) "
                "before freezing an artifact")
        manifest = {
            "format_version": FORMAT_VERSION,
            "arch_id": state.cfg.arch_id,
            "config_hash": config_hash(state.cfg),
            "quant": dataclasses.asdict(state.cfg.quant),
            "policy": policy_fields(state.policy),
            "tp": state.tp,
            "seed": seed,
            "pairs": list(state.pair_meta),
            "leaf_shards": dict(state.leaf_shards),
        }
        coll = state.policy.collective
        if isinstance(coll, CollectivePlan):
            # structural echo of the per-layer plan (the policy field
            # above already carries the authoritative shorthand)
            manifest["collective_plan"] = {
                "entries": [[pat, spec.shorthand()]
                            for pat, spec in coll.entries],
                "default": coll.default.shorthand(),
            }
        if getattr(state, "tuner_report", ()):
            manifest["collective_tuner"] = list(state.tuner_report)
        if extra:
            manifest = {**extra, **manifest}
        aux = ({"attn_plans": state.attn_plans}
               if state.attn_plans is not None else None)
        return cls(manifest=manifest, rank_params=tuple(state.rank_params),
                   aux=aux)

    # ---- accessors --------------------------------------------------------

    @property
    def tp(self) -> int:
        return int(self.manifest["tp"])

    @property
    def scheme(self) -> str:
        return self.manifest["policy"]["scheme"]

    def policy(self) -> ExecutionPolicy:
        p = self.manifest["policy"]
        backend = p["backend"]
        if self.manifest["quant"]["backend"] == "auto":
            # chosen again for the platform this process serves on
            backend = ExecutionPolicy.auto(p["scheme"]).backend
        return ExecutionPolicy(
            scheme=p["scheme"], backend=backend,
            compute_dtype=p["compute_dtype"], accum_dtype=p["accum_dtype"],
            collective=p["collective"], kv=p.get("kv", "dense"),
            mesh=p.get("mesh"))

    def rank_tree(self, r: int):
        return self.rank_params[r]

    def params(self):
        """Reassemble the global planned pytree (what single-program
        GSPMD/shard_map serving consumes; per-rank serving uses
        ``rank_tree``).  Slicing then concatenating is the identity, so
        this is bit-exact with the in-memory compile."""
        from repro.train import checkpoint

        if self.global_params is not None:
            # load_for_mesh already assembled the device-sharded tree
            return self.global_params
        if not self.rank_params:
            raise ValueError(
                "artifact holds no rank pytrees (loaded per-rank for a "
                "mesh without assembled params?) — use load_for_mesh's "
                "global_params or reload with DeploymentArtifact.load")
        shards = self.manifest["leaf_shards"]
        flats = [checkpoint.flatten_keys(t) for t in self.rank_params]
        keys = list(flats[0])
        leaves = []
        for key in keys:
            dim = shards.get(key)
            # one rank holds the global leaf: no concatenated copy of it
            if dim is None or len(flats) == 1:
                leaves.append(flats[0][key])
            else:
                leaves.append(jnp.concatenate(
                    [f[key] for f in flats], axis=int(dim)))
        treedef = jax.tree_util.tree_structure(self.rank_params[0])
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # ---- validation -------------------------------------------------------

    def validate(self, cfg=None, policy: Optional[ExecutionPolicy] = None,
                 tp: Optional[int] = None) -> "DeploymentArtifact":
        """Refuse to serve under a mismatched plan.  Returns self."""
        if cfg is not None:
            if cfg.arch_id != self.manifest["arch_id"]:
                raise PlanMismatchError(
                    f"artifact was compiled for {self.manifest['arch_id']!r}"
                    f", not {cfg.arch_id!r}")
            if config_hash(cfg) != self.manifest["config_hash"]:
                raise PlanMismatchError(
                    f"config hash {config_hash(cfg)} != artifact's "
                    f"{self.manifest['config_hash']} — the model config "
                    "changed since this plan was compiled")
        if policy is not None:
            want = policy_fields(policy)
            have = dict(self.manifest["policy"])
            # kernel backend, cache layout and device grid are
            # runtime-only (see policy_fields): an artifact prepared
            # dense serves paged, and dp may differ — only the TP degree
            # (checked below against the shards) is load-bearing
            for k in ("backend", "kv", "mesh"):
                want.pop(k, None)
                have.pop(k, None)
            if want != have:
                raise PlanMismatchError(
                    f"policy {want} != artifact's plan {have}")
        if tp is not None and int(tp) != self.tp:
            raise PlanMismatchError(
                f"mesh model-axis degree {tp} != artifact's TP "
                f"{self.tp} — re-run prepare for this mesh")
        return self

    # ---- (de)serialization ------------------------------------------------

    def save(self, dirpath: str) -> str:
        from repro.train import checkpoint

        if not self.rank_params:
            raise ValueError(
                "cannot re-save an artifact loaded per-rank for a mesh: "
                "this process holds only its own ranks' shards")
        os.makedirs(dirpath, exist_ok=True)
        with open(os.path.join(dirpath, MANIFEST), "w") as f:
            json.dump(self.manifest, f, indent=1, sort_keys=True)
        for r, tree in enumerate(self.rank_params):
            checkpoint.save(os.path.join(dirpath, f"rank_{r:02d}"), tree)
        if self.aux is not None:
            checkpoint.save(os.path.join(dirpath, "aux"), self.aux)
        return dirpath

    @classmethod
    def load_manifest(cls, dirpath: str) -> dict:
        """Read and format-check just ``manifest.json`` — the only file a
        distributed process touches before deciding which rank shards it
        owns (``load_for_mesh``)."""
        mpath = os.path.join(dirpath, MANIFEST)
        if not os.path.exists(mpath):
            raise FileNotFoundError(
                f"{dirpath} is not a deployment artifact (no {MANIFEST})")
        with open(mpath) as f:
            manifest = json.load(f)
        if manifest.get("format_version") != FORMAT_VERSION:
            raise PlanMismatchError(
                f"artifact format v{manifest.get('format_version')} != "
                f"supported v{FORMAT_VERSION}")
        return manifest

    @classmethod
    def load(cls, dirpath: str) -> "DeploymentArtifact":
        from repro.train import checkpoint

        manifest = cls.load_manifest(dirpath)
        ranks = tuple(
            checkpoint.load(os.path.join(dirpath, f"rank_{r:02d}.npz"))
            for r in range(int(manifest["tp"])))
        aux_path = os.path.join(dirpath, "aux.npz")
        aux = checkpoint.load(aux_path) if os.path.exists(aux_path) else None
        return cls(manifest=manifest, rank_params=ranks, aux=aux)

    @classmethod
    def load_for_mesh(cls, dirpath: str,
                      mesh: "jax.sharding.Mesh") -> "DeploymentArtifact":
        """Distributed load (DESIGN.md §11): read only the ``rank_NN.npz``
        files whose model-axis coordinates this process's devices own and
        assemble ``global_params`` as mesh-sharded ``jax.Array`` leaves —
        no host ever materializes another rank's slices.  ``rank_params``
        is left empty; ``load_stats`` records the byte ledger."""
        from repro.dist import loader as dist_loader
        from repro.train import checkpoint

        manifest = cls.load_manifest(dirpath)
        params, stats = dist_loader.load_per_rank(dirpath, manifest, mesh)
        aux_path = os.path.join(dirpath, "aux.npz")
        aux = checkpoint.load(aux_path) if os.path.exists(aux_path) else None
        return cls(manifest=manifest, rank_params=(), aux=aux,
                   global_params=params, load_stats=stats)
