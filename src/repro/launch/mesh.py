"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS for 512 host devices before any jax
import; smoke tests see 1 device).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

SINGLE_POD = (16, 16)            # 256 chips (one v5e pod slice)
MULTI_POD = (2, 16, 16)          # 2 pods = 512 chips


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """The one mesh constructor: every axis is ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` refuses the ``NamedSharding`` specs the
    models annotate with; GSPMD-style Auto axes are what every
    ``ctx.shard`` and ``shard_map`` in this repo is written for.
    ``devices``: the first ``prod(shape)`` of them form the grid (default:
    all of ``jax.devices()``)."""
    n = 1
    for s in shape:
        n *= s
    devs = list(jax.devices() if devices is None else devices)[:n]
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devs)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — run "
            "under launch/dryrun.py which forces 512 host devices")
    return make_mesh(shape, axes, devs)


def init_distributed(coordinator: str, num_processes: int,
                     process_id: int) -> None:
    """Join a multi-controller JAX job (no-op for a single process).

    MUST run before anything touches devices: the CPU collectives
    implementation is a backend-creation option, so the gloo flag has to
    be set before the backend initializes — which is also why this module
    keeps everything behind functions.  TPU fleets ignore the flag (ICI
    collectives are native); on CPU it is what lets two loopback
    processes run real ppermute/psum rings over sockets.
    """
    if num_processes <= 1:
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def make_plan_mesh(plan) -> jax.sharding.Mesh:
    """Materialize a ``dist.MeshPlan`` over the global device grid."""
    return plan.build_mesh()


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices not divisible by model={model}")
    return make_mesh((n // model, model), ("data", "model"))


def batch_axes_for(mesh: jax.sharding.Mesh, global_batch: int) -> tuple:
    """Batch-sharding axes usable for this mesh and batch size.

    Decode at batch=1 (long_500k) cannot shard its batch dim — returns ()
    so the batch is replicated and only the model axis does real work.
    """
    axes = [a for a in mesh.axis_names if a in ("pod", "data")]
    out = []
    size = 1
    for a in axes:
        s = dict(zip(mesh.axis_names, mesh.devices.shape))[a]
        if global_batch % (size * s) == 0:
            out.append(a)
            size *= s
    return tuple(out)
