"""CollectiveSpec / CollectivePlan — TP epilogue collectives as a plan.

The paper's speedup is a *communication* plan decided a priori: TP-Aware
pays only the trailing AllReduce while the Naive Algorithm's AllGather
grows with rank count.  This module names that trailing collective as a
frozen, hashable spec — strategy name, wire dtype, and quantization
parameters — so the whole comm plan travels on the ``ExecutionPolicy``
exactly like the kernel plan does, and compressed collectives
(Hansen-Palmus et al. 2024; Dong et al. 2024) are one registry entry
away instead of a new string branch at every call site.

``CollectiveSpec.parse`` accepts the string shorthands used by configs
and CLIs:

* ``"psum"`` / ``"psum_scatter"`` / ``"none"`` — bit-exact strategies,
* ``"cast"`` or ``"cast:<dtype>"`` — low-bit wire dtype (default bf16),
* ``"quant-int8"`` or ``"quant-int8:<block>"`` — blockwise int8
  quantized all-reduce (block size default 128),
* ``"quant-int4"`` or ``"quant-int4:<block>"`` — blockwise int4: the
  payload is packed 8-nibbles-per-uint32 with the same
  ``quantization.pack_int4`` layout the weights use (block default 32 —
  15 levels need tighter blocks than int8's 255).

Any other ``:`` token after a quant name is refused with a
``ValueError`` naming it: configs, CLI strings and prepared manifests
come from outside the program, so a token it does not know never runs
as something else.

``CollectivePlan`` lifts the spec to a *per-layer* decision (tolerance
to wire compression varies sharply by layer — Hansen-Palmus et al.
2024; Dong et al. 2024): an ordered ``(path glob, CollectiveSpec)`` map
plus a default, resolved per pair path at the epilogue.  The CLI/config
shorthand is ``"per-layer:<glob>=<spec>[,...][,*=<default>]"``, e.g.
``"per-layer:*.mlp=quant-int8:128,attn*=cast:bf16,*=psum"``; a bare
``CollectiveSpec`` still works everywhere as a one-entry plan
(``parse_collective`` keeps both forms first-class).

Strategy *implementations* live in ``comm/dispatch.py``; the spec only
describes the plan.  ``spec.bytes_on_wire(shape, tp)`` resolves the
strategy's analytic per-device ICI cost so benchmarks and the roofline
can account communication per strategy without compiling anything.
"""

from __future__ import annotations

import dataclasses
import fnmatch
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

__all__ = ["CollectiveSpec", "CollectivePlan", "parse_collective"]

_WIRE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                "float16": jnp.float16,
                # CLI-friendly aliases (the canonical shorthand always
                # prints the full dtype name)
                "f32": jnp.float32, "fp32": jnp.float32,
                "bf16": jnp.bfloat16,
                "f16": jnp.float16, "fp16": jnp.float16}


def _canon_wire_dtype(dt):
    """Canonicalize a wire dtype-like (string names allowed; None passes)."""
    if dt is None:
        return None
    if isinstance(dt, str):
        try:
            dt = _WIRE_DTYPES[dt]
        except KeyError:
            raise ValueError(
                f"unknown wire dtype {dt!r}, expected one of "
                f"{sorted(_WIRE_DTYPES)}") from None
    return jax.dtypes.canonicalize_dtype(dt)


@dataclasses.dataclass(frozen=True)
class CollectiveSpec:
    """One TP epilogue collective, fully specified.

    Frozen + hashable: lives inside ``ExecutionPolicy`` (a jit static
    argument).  ``name`` is a key into the ``comm/dispatch.py`` registry;
    the remaining fields parameterize the strategy:

    * ``wire_dtype`` — the dtype that crosses the ICI (``cast``; also the
      dtype ``bytes_on_wire`` assumes for uncompressed strategies, f32
      when None),
    * ``block_size`` / ``bits`` — blockwise quantization parameters for
      the compressed strategies (``quant-int8``).
    """

    name: str = "psum"
    wire_dtype: Optional[Any] = None
    block_size: int = 128
    bits: Optional[int] = None   # None -> the strategy's payload width

    def __post_init__(self):
        from repro.comm import dispatch  # deferred: dispatch imports spec
        if self.name not in dispatch.strategies():
            raise ValueError(
                f"unknown collective {self.name!r}; registered strategies: "
                f"{list(dispatch.strategies())}")
        if self.name == "cast" and self.wire_dtype is None:
            object.__setattr__(self, "wire_dtype", jnp.bfloat16)
        if self.bits is None:
            object.__setattr__(self, "bits",
                               4 if self.name == "quant-int4" else 8)
        object.__setattr__(self, "wire_dtype",
                           _canon_wire_dtype(self.wire_dtype))
        if self.block_size <= 0:
            raise ValueError(f"block_size must be positive, "
                             f"got {self.block_size}")
        if self.bits not in (4, 8):
            raise ValueError(
                f"only 4/8-bit payloads are implemented, got "
                f"bits={self.bits}")
        want_bits = {"quant-int8": 8, "quant-int4": 4}.get(self.name)
        if want_bits is not None and self.bits != want_bits:
            raise ValueError(
                f"{self.name} carries {want_bits}-bit payloads, got "
                f"bits={self.bits}")

    # ---- construction -----------------------------------------------------

    @classmethod
    def parse(cls, value) -> "CollectiveSpec":
        """Parse a spec, a string shorthand, or None (-> default psum)."""
        if value is None:
            return cls()
        if isinstance(value, CollectiveSpec):
            return value
        if not isinstance(value, str):
            raise TypeError(
                f"expected CollectiveSpec or string shorthand, "
                f"got {type(value).__name__}")
        name, _, arg = value.partition(":")
        if name == "cast":
            return cls(name="cast", wire_dtype=arg or "bfloat16")
        if name in ("quant-int8", "quant-int4"):
            # quant shorthands: "<name>[:<block>]", nothing else
            tokens = arg.split(":") if arg else []
            for i, token in enumerate(tokens):
                if i > 0 or not token.isdigit():
                    raise ValueError(
                        f"collective shorthand {value!r}: unexpected token "
                        f"{token!r} (expected '{name}[:<block>]')")
            default_block = 128 if name == "quant-int8" else 32
            return cls(name=name, bits=4 if name == "quant-int4" else None,
                       block_size=int(tokens[0]) if tokens else default_block)
        if arg:
            raise ValueError(
                f"collective {name!r} takes no ':' argument (got {value!r})")
        return cls(name=name)

    def shorthand(self) -> str:
        """The string form ``parse`` round-trips (for CLIs / logs)."""
        if self.name == "cast":
            return f"cast:{jnp.dtype(self.wire_dtype).name}"
        if self.name in ("quant-int8", "quant-int4"):
            return f"{self.name}:{self.block_size}"
        return self.name

    def with_(self, **kw) -> "CollectiveSpec":
        return dataclasses.replace(self, **kw)

    # ---- plan interface ---------------------------------------------------

    def resolve(self, pair_path: Optional[str] = None) -> "CollectiveSpec":
        """A bare spec is a one-entry plan: every pair path resolves to it
        (the uniform lookup call sites use — see ``CollectivePlan``)."""
        return self

    def specs(self) -> tuple["CollectiveSpec", ...]:
        """Distinct specs this plan can resolve to (just itself)."""
        return (self,)

    # ---- analytic cost ----------------------------------------------------

    def bytes_on_wire(self, shape, tp: int) -> float:
        """Analytic per-device ICI bytes to close a row-TP layer whose
        per-rank partial output has ``shape``, over ``tp`` ranks (ring
        cost model, matching ``launch/roofline.py``)."""
        from repro.comm import dispatch
        return dispatch.resolve(self.name).bytes_on_wire(
            tuple(shape), int(tp), self)

    def site_predictions(self, paths, shape, tp: int) -> dict:
        """Per-site analytic prediction table ``{path: {"spec", "bytes"}}``
        for the given pair paths — what ``repro.analysis``'s HLO linter
        checks measured modules against (a bare spec predicts the same
        cost at every site; see ``CollectivePlan.site_predictions``)."""
        return {path: {"spec": self.resolve(path).shorthand(),
                       "bytes": self.resolve(path).bytes_on_wire(shape, tp)}
                for path in paths}


# ---------------------------------------------------------------------------
# per-layer plans
# ---------------------------------------------------------------------------

_PLAN_PREFIX = "per-layer:"


def _normalize_path(path: str) -> str:
    return path.replace("/", ".")


def _match(path: str, pattern: str) -> bool:
    """Glob-match ``pattern`` against a dotted pair path.

    The pattern is tried against the full path AND every dot-suffix, so
    ``"mlp"`` / ``"*.mlp"`` / ``"attn*"`` all hit ``"layers.mlp"`` /
    ``"super.attn.mlp"`` the way a CLI user expects, while a fully
    qualified path (what the autotuner writes) still matches exactly.
    """
    segs = _normalize_path(path).split(".")
    return any(
        fnmatch.fnmatchcase(".".join(segs[i:]), pattern)
        for i in range(len(segs)))


@dataclasses.dataclass(frozen=True)
class CollectivePlan:
    """Per-layer collective selection, fully specified and frozen.

    An ordered ``(path glob, CollectiveSpec)`` map plus a default:
    ``resolve(pair_path)`` returns the first entry whose glob matches
    the pair's dotted path (e.g. ``"layers.mlp"``,
    ``"layers.moe.experts"``), else ``default``.  Hashable, so it lives
    on ``ExecutionPolicy.collective`` (a jit static argument) exactly
    like a bare ``CollectiveSpec`` — which is the degenerate
    zero-entry plan (see ``CollectiveSpec.resolve``).

    Shorthand (``parse``/``shorthand`` round-trip exactly)::

        per-layer:*.mlp=quant-int8:128,attn*=cast:bfloat16,*=psum

    Entries apply in order; ``*=<spec>`` names the default and must come
    last (anything after a catch-all would be unreachable).
    """

    entries: tuple = ()                       # ((glob, CollectiveSpec), ...)
    default: CollectiveSpec = CollectiveSpec()

    def __post_init__(self):
        ent = []
        for item in self.entries:
            pat, spec = item
            if not isinstance(pat, str) or not pat:
                raise ValueError(
                    f"plan entry pattern must be a non-empty string, "
                    f"got {pat!r}")
            ent.append((pat, CollectiveSpec.parse(spec)))
        object.__setattr__(self, "entries", tuple(ent))
        object.__setattr__(self, "default",
                           CollectiveSpec.parse(self.default))

    # ---- construction -----------------------------------------------------

    @classmethod
    def parse(cls, value) -> "CollectivePlan":
        """Parse a plan, a ``per-layer:`` shorthand, or anything
        ``CollectiveSpec.parse`` accepts (-> one-entry plan)."""
        if isinstance(value, CollectivePlan):
            return value
        if not (isinstance(value, str) and value.startswith(_PLAN_PREFIX)):
            return cls(default=CollectiveSpec.parse(value))
        body = value[len(_PLAN_PREFIX):]
        entries, default, saw_default = [], CollectiveSpec(), False
        for item in body.split(","):
            item = item.strip()
            if not item:
                continue
            if saw_default:
                raise ValueError(
                    f"plan entry {item!r} comes after the catch-all "
                    f"'*=...' and would never match (in {value!r})")
            pat, sep, short = item.partition("=")
            if not sep or not pat:
                raise ValueError(
                    f"plan entry {item!r} is not '<glob>=<spec>' "
                    f"(in {value!r})")
            if pat == "*":
                default, saw_default = CollectiveSpec.parse(short), True
            else:
                entries.append((pat, CollectiveSpec.parse(short)))
        return cls(entries=tuple(entries), default=default)

    def shorthand(self) -> str:
        """The string form ``parse`` round-trips (manifests, CLIs, logs)."""
        parts = [f"{pat}={spec.shorthand()}" for pat, spec in self.entries]
        parts.append(f"*={self.default.shorthand()}")
        return _PLAN_PREFIX + ",".join(parts)

    def with_(self, **kw) -> "CollectivePlan":
        return dataclasses.replace(self, **kw)

    # ---- lookup -----------------------------------------------------------

    def resolve(self, pair_path: Optional[str] = None) -> CollectiveSpec:
        """The spec closing the row-TP epilogue at ``pair_path`` (first
        matching entry, else the default; ``None`` — an anonymous call
        site — always gets the default)."""
        if pair_path is not None:
            for pat, spec in self.entries:
                if _match(pair_path, pat):
                    return spec
        return self.default

    def specs(self) -> tuple[CollectiveSpec, ...]:
        """Distinct specs this plan can resolve to (entry order, default
        last) — what the serve banner and manifest checks enumerate."""
        out = []
        for _, spec in self.entries:
            if spec not in out:
                out.append(spec)
        if self.default not in out:
            out.append(self.default)
        return tuple(out)

    def site_predictions(self, paths, shape, tp: int) -> dict:
        """Per-site analytic prediction table ``{path: {"spec", "bytes"}}``
        — each path resolves its own spec, so this is the plan-level
        ground truth ``repro.analysis`` checks measured HLO and artifact
        manifests against (uniform for a bare ``CollectiveSpec``)."""
        return {path: {"spec": self.resolve(path).shorthand(),
                       "bytes": self.resolve(path).bytes_on_wire(shape, tp)}
                for path in paths}


def parse_collective(value) -> Union[CollectiveSpec, CollectivePlan]:
    """Parse ``ExecutionPolicy.collective``-likes: a spec, a plan, or any
    string shorthand of either (``None`` -> the default psum spec).
    Bare specs stay specs so existing call sites (and policy equality)
    are untouched; only ``per-layer:`` shorthands and explicit plans
    produce a ``CollectivePlan``."""
    if isinstance(value, CollectivePlan) or (
            isinstance(value, str) and value.startswith(_PLAN_PREFIX)):
        return CollectivePlan.parse(value)
    return CollectiveSpec.parse(value)
