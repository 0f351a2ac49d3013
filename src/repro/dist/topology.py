"""MeshPlan — the device-grid half of the deployment plan.

Mirrors ``comm.CollectiveSpec`` / ``cache.PageSpec``: a tiny frozen,
hashable record with a string shorthand, parsed once at config time and
carried on ``ExecutionPolicy.mesh`` so the launcher, the per-rank
artifact loader, and the ``DeploymentArtifact`` manifest all read one
source of truth about *where* the plan runs.

Shorthands (``parse``/``shorthand`` round-trip exactly)::

    dp1xtp1           single device (the default)
    dp2xtp4           2-way data x 4-way model (tensor) parallel
    dp4xtp2xep2       ... plus 2-way expert parallelism for MoE, carved
                      out of the data axis (ep must divide dp)

The mesh axes are always ``("data", "model")`` — the names every
``shard_map`` in ``models/`` and ``core/schemes.py`` binds to.  EP does
not get its own axis: MoE expert dispatch subgroups the data axis (the
plan records the degree so the artifact can refuse a mismatched
deployment; see DESIGN.md §11).

``build_mesh()`` spans **all** processes' devices (``jax.devices()``,
not ``jax.local_devices()``): under ``jax.distributed.initialize`` each
process sees the same global grid and owns only the rows/columns whose
devices are addressable locally — which is exactly what
``dist/loader.py`` uses to decide which ``rank_NN.npz`` files this
process may read.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Union

__all__ = ["MeshPlan", "local_model_ranks"]

_AXIS_RE = re.compile(r"^(dp|tp|ep)(\d+)$")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """One DP×TP (optionally ×EP) device grid, fully specified.

    Frozen + hashable: lives on ``ExecutionPolicy`` (a jit static
    argument) and is recorded in the artifact manifest.  ``dp`` is the
    data-parallel degree (the ``"data"`` mesh axis), ``tp`` the
    model/tensor degree (the ``"model"`` axis the row-TP epilogues
    reduce over), ``ep`` an optional expert-parallel degree that must
    divide ``dp``.
    """

    dp: int = 1
    tp: int = 1
    ep: Optional[int] = None

    def __post_init__(self):
        for field in ("dp", "tp"):
            v = getattr(self, field)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{field} must be a positive int, got {v!r}")
        if self.ep is not None:
            if not isinstance(self.ep, int) or self.ep < 1:
                raise ValueError(f"ep must be a positive int, got {self.ep!r}")
            if self.dp % self.ep != 0:
                raise ValueError(
                    f"ep={self.ep} must divide dp={self.dp} (expert groups "
                    f"are carved out of the data axis)")

    # ---- construction -----------------------------------------------------

    @classmethod
    def parse(cls, value: Union["MeshPlan", str, None]) -> "MeshPlan":
        """Parse a plan, a ``"dp2xtp4[xep2]"`` shorthand, or None (-> the
        single-device default).  Axis terms may appear in any order but
        each at most once; ``shorthand()`` always prints dp, tp, ep."""
        if value is None:
            return cls()
        if isinstance(value, MeshPlan):
            return value
        if not isinstance(value, str):
            raise TypeError(
                f"expected MeshPlan or string shorthand, "
                f"got {type(value).__name__}")
        seen = {}
        for part in value.split("x"):
            m = _AXIS_RE.match(part)
            if m is None:
                raise ValueError(
                    f"unknown mesh spec {value!r}, expected "
                    f"'dp<N>xtp<M>[xep<K>]' (e.g. 'dp2xtp4')")
            axis, deg = m.group(1), int(m.group(2))
            if axis in seen:
                raise ValueError(
                    f"mesh spec {value!r} repeats the {axis!r} axis")
            seen[axis] = deg
        if "dp" not in seen or "tp" not in seen:
            raise ValueError(
                f"mesh spec {value!r} must name both dp and tp degrees")
        return cls(dp=seen["dp"], tp=seen["tp"], ep=seen.get("ep"))

    def shorthand(self) -> str:
        """The string form ``parse`` round-trips (manifests, CLIs, logs)."""
        s = f"dp{self.dp}xtp{self.tp}"
        if self.ep is not None:
            s += f"xep{self.ep}"
        return s

    def with_(self, **kw) -> "MeshPlan":
        return dataclasses.replace(self, **kw)

    # ---- geometry ---------------------------------------------------------

    @property
    def size(self) -> int:
        """Total devices the plan spans."""
        return self.dp * self.tp

    def build_mesh(self, devices=None):
        """Materialize the (dp, tp) ``("data", "model")`` mesh over the
        global device list (all processes' devices — see module doc)."""
        import jax

        from repro.launch.mesh import make_mesh

        devs = list(jax.devices()) if devices is None else list(devices)
        if len(devs) != self.size:
            raise ValueError(
                f"mesh plan {self.shorthand()} spans {self.size} device(s) "
                f"but {len(devs)} are visible; launch with a matching "
                f"device count (or pass an explicit device subset)")
        return make_mesh((self.dp, self.tp), ("data", "model"), devs)

    def local_model_ranks(self, mesh) -> tuple:
        """Model-axis coordinates owned by THIS process's addressable
        devices — the set of ``rank_NN.npz`` files ``dist/loader.py`` is
        allowed to read.  Single-process: every rank."""
        return local_model_ranks(mesh)


def local_model_ranks(mesh) -> tuple:
    """Model-axis ("model", last mesh dim) coordinates of the devices this
    process owns.  Free function so the per-rank loader needs only a mesh,
    not the plan that built it."""
    import jax
    import numpy as np

    pid = jax.process_index()
    ranks = set()
    grid = np.asarray(mesh.devices, dtype=object)
    for idx, dev in np.ndenumerate(grid):
        if dev.process_index == pid:
            ranks.add(int(idx[-1]))
    return tuple(sorted(ranks))
