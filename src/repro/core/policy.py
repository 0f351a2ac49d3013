"""ExecutionPolicy — the paper's *a-priori deployment plan* as one object.

The paper's contribution is a plan decided before the first token: which
layout scheme the weights were prepared in (Algorithms 1-3), which kernel
executes the dequant-GEMM, what dtypes compute/accumulate in, and which
collective closes the row-TP layer.  The repo used to thread that plan
through the stack as loose kwargs duplicated at every call site; this
module makes it a single frozen, hashable record that flows from config
to kernel unchanged.

Both halves of the plan dispatch through registries:

* ``policy.backend`` — key into ``kernels/dispatch.py``
  (``(layout kind, backend) -> kernel``),
* ``policy.collective`` — a ``CollectiveSpec`` (one collective for every
  row-TP epilogue) or a ``CollectivePlan`` (per-layer selection: ordered
  ``{path glob: spec}`` + default), resolved by ``comm/dispatch.py``
  (``name -> strategy``); string shorthands like ``"psum"``,
  ``"cast:bfloat16"``, ``"quant-int8"`` or
  ``"per-layer:*.mlp=quant-int8,*=psum"`` are accepted and parsed via
  ``comm.parse_collective``.  Epilogues look their spec up with
  ``policy.collective.resolve(pair_path)`` — a bare spec resolves to
  itself for every path.

Construction paths:

* ``ExecutionPolicy.from_config(cfg)`` — the deployment plan recorded in a
  ``ModelConfig``/``QuantConfig`` (``backend="auto"`` resolves via the
  heuristic below).
* ``ExecutionPolicy.auto(scheme)`` — pick the fused Pallas kernel when the
  layout allows it (ordered layouts on a real TPU), fall back to the
  XLA-fused ``jnp`` path otherwise.
* ``ExecutionPolicy()`` — the historical defaults (tp-aware / jnp / f32 /
  psum), bit-identical to the original kwarg defaults.

Consumption: ``PlannedPair.forward(x, policy, mesh=...)`` is the canonical
runtime entry point.  See DESIGN.md §1 for the architecture.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from repro.comm.spec import (CollectivePlan, CollectiveSpec,
                             parse_collective)

__all__ = [
    "KernelTiling", "ExecutionPolicy", "DEFAULT_POLICY", "resolve_policy",
    "platform_is_tpu", "interpret_mode",
]


def platform_is_tpu() -> bool:
    """True when JAX's default backend is a TPU: the one platform test
    behind ``interpret_mode`` and ``ExecutionPolicy.auto``."""
    return jax.default_backend() == "tpu"


def interpret_mode(requested: Optional[bool] = None) -> bool:
    """Whether the Pallas kernels run interpreted.

    ``None`` (the default of ``KernelTiling.interpret``) decides from the
    platform: compiled Mosaic on a TPU, the interpreter elsewhere.
    ``False`` compiles anywhere, which is how a CPU host compiles the
    kernels for a described TPU.  ``True`` on a TPU is refused: there the
    kernels never run interpreted."""
    if requested is None:
        return not platform_is_tpu()
    if requested and platform_is_tpu():
        raise ValueError("interpret=True was requested on a TPU; the Pallas "
                         "kernels run compiled there")
    return bool(requested)


def _canon_dtype(dt):
    """Canonicalize a dtype-like to a hashable np.dtype (None passes)."""
    if dt is None:
        return None
    return jax.dtypes.canonicalize_dtype(dt)


@dataclasses.dataclass(frozen=True)
class KernelTiling:
    """Tile/lowering knobs for the fused Pallas kernels.

    ``block_k=None`` lets ``dequant_matmul.pick_block_k`` choose the
    K tile; ``interpret`` is resolved by ``interpret_mode`` (None: from
    the platform).
    """

    block_m: int = 128
    block_n: int = 128
    block_k: Optional[int] = None
    interpret: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """The entire runtime execution contract for a quantized deployment.

    Frozen + hashable: safe as a jit static argument and inside
    ``shard_map`` closures.  ``scheme`` records the *offline* layout the
    weights were planned with (the runtime always trusts the plan pytree's
    own ``scheme`` field; a policy's copy exists so config-time code can
    carry the full plan in one object).  ``collective`` is the row-TP
    epilogue plan — a ``CollectiveSpec`` applied uniformly, or a
    ``CollectivePlan`` resolving a spec per pair path (string shorthands
    of either accepted); each epilogue dispatches its resolved spec
    through ``comm/dispatch.py``.
    """

    scheme: str = "tp-aware"
    backend: str = "jnp"            # key into kernels.dispatch registry
    compute_dtype: Any = jnp.float32
    accum_dtype: Any = jnp.float32
    collective: Union[CollectiveSpec, CollectivePlan, str] = CollectiveSpec()
    tiling: KernelTiling = KernelTiling()
    # Decode-cache layout ("repro.cache.PageSpec"): dense per-slot rows,
    # or a shared page pool ("paged:16", "paged:16:int8", ...).  String
    # shorthands parse in __post_init__, mirroring ``collective``.
    kv: Any = None
    # Device-grid plan ("repro.dist.MeshPlan"): the DP×TP(×EP) grid the
    # deployment spans, as a frozen record or a "dp2xtp4" shorthand.
    # Recorded in the artifact manifest for provenance (serving on a
    # different grid with the same TP degree is allowed — validate only
    # pins the model-axis degree).
    mesh: Any = None

    def __post_init__(self):
        from repro.cache.spec import PageSpec
        from repro.core.reorder import SCHEMES
        from repro.dist.topology import MeshPlan
        if self.scheme not in SCHEMES:
            raise ValueError(
                f"unknown scheme {self.scheme!r}, expected one of {SCHEMES}")
        object.__setattr__(self, "collective",
                           parse_collective(self.collective))
        object.__setattr__(self, "compute_dtype",
                           _canon_dtype(self.compute_dtype))
        object.__setattr__(self, "accum_dtype",
                           _canon_dtype(self.accum_dtype))
        object.__setattr__(self, "kv", PageSpec.parse(self.kv))
        object.__setattr__(self, "mesh", MeshPlan.parse(self.mesh))

    # ---- builders ---------------------------------------------------------

    def with_(self, **kw) -> "ExecutionPolicy":
        return dataclasses.replace(self, **kw)

    def with_tiling(self, **kw) -> "ExecutionPolicy":
        return dataclasses.replace(
            self, tiling=dataclasses.replace(self.tiling, **kw))

    @classmethod
    def auto(cls, scheme: str = "tp-aware", *, on_tpu: Optional[bool] = None,
             **overrides) -> "ExecutionPolicy":
        """Heuristic plan: fused Pallas kernel when the layout allows.

        Ordered layouts (exllama / tp-aware) have the group-contiguous
        metadata the Pallas kernel's locality depends on; on TPU they get
        ``backend="pallas"``.  The naive g_idx layout and CPU hosts (where
        the kernel would run interpreted) fall back to ``jnp`` — XLA fuses
        the dequant into the GEMM epilogue there.
        """
        if on_tpu is None:
            on_tpu = platform_is_tpu()
        ordered = scheme != "naive-actorder"
        backend = "pallas" if (on_tpu and ordered) else "jnp"
        return cls(scheme=scheme, backend=backend, **overrides)

    @classmethod
    def from_config(cls, cfg) -> "ExecutionPolicy":
        """Build the deployment plan recorded in a ``ModelConfig`` (via its
        ``quant`` field) or a ``QuantConfig`` directly."""
        qc = getattr(cfg, "quant", cfg)
        dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                  "float16": jnp.float16, None: None}

        def lookup(field, name):
            try:
                return dtypes[name]
            except KeyError:
                raise ValueError(
                    f"unknown {field} {name!r}, expected one of "
                    f"{sorted(k for k in dtypes if k)}") from None

        compute = lookup("compute_dtype", qc.compute_dtype)
        collective = parse_collective(qc.collective)
        from repro.cache.spec import PageSpec
        kv = PageSpec(page_size=getattr(qc, "kv_page_size", None),
                      bits=getattr(qc, "kv_bits", None))
        if qc.backend == "auto":
            return cls.auto(qc.scheme, compute_dtype=compute,
                            collective=collective, kv=kv)
        return cls(scheme=qc.scheme, backend=qc.backend,
                   compute_dtype=compute, collective=collective, kv=kv)


DEFAULT_POLICY = ExecutionPolicy()


def resolve_policy(policy: Optional[ExecutionPolicy] = None) -> ExecutionPolicy:
    """``policy`` if given, else the historical defaults."""
    return policy if policy is not None else DEFAULT_POLICY
