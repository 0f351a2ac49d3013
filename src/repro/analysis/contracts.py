"""Contract lint — abstract interpretation of the deployment plan.

Everything here runs under ``jax.eval_shape``: the programs are traced
with shape/dtype avals only, so the whole pass spends **zero FLOPs** and
never allocates a model — Hansen-Palmus et al. 2024's observation that
dtype/wire-bit contracts are exactly where compressed-TP deployments
silently lose quality, made checkable before a single token is served.

* CT001 — for every collective spec × TP degree, tracing the strategy's
  ``apply`` inside ``shard_map`` must return the residual stream's input
  dtype (f32 AND bf16 streams) and the contracted shape (full for
  all-reduce strategies, last-dim sharded for scatter strategies).
* CT002 — at TP=1 every spec is the identity (shape AND dtype) and its
  analytic ``bytes_on_wire`` is exactly zero.
* CT003 — per registered family with a paged cache: the dense and paged
  KV trees agree on per-token geometry (kv-heads × head_dim trailing
  dims) and payload dtype.
* CT004 — per registered family: forward and decode_step emit f32
  logits from fully abstract params (``Model.init`` under eval_shape —
  the GPTQ/reorder/fold pipeline traces abstractly too).

With ``specs=None`` the collective checks sweep every registered
strategy; a caller holding a prepared artifact passes that plan's
resolved ``specs()`` instead so the exact deployed sites are what gets
verified.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence

from repro.analysis.findings import Finding

#: the residual-stream dtypes the collective contract must preserve
STREAM_DTYPES = ("float32", "bfloat16")

#: (rows, cols) of the abstract partial sum the collectives close;
#: cols is divisible by every swept tp (and tp*8 for packed int4)
PROBE_SHAPE = (8, 256)


def _default_specs():
    from repro.comm import dispatch as comm_dispatch
    from repro.comm.spec import CollectiveSpec

    return [CollectiveSpec.parse(n) for n in comm_dispatch.strategies()]


def _tp_mesh(tp: int):
    from repro.launch.mesh import make_mesh

    return make_mesh((tp,), ("model",))


def _abstract_apply(spec, tp: int, dtype):
    """eval_shape of the strategy closing a replicated partial sum over a
    ``tp``-way model axis; returns the output ShapeDtypeStruct."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comm import dispatch as comm_dispatch
    from repro.core.policy import ExecutionPolicy

    mesh = _tp_mesh(tp)
    policy = ExecutionPolicy(collective=spec)
    scatters = comm_dispatch.scatters_output(spec)
    out_spec = P(None, "model") if scatters else P(None, None)
    fn = jax.shard_map(
        lambda y: comm_dispatch.apply(y, "model", spec, policy),
        mesh=mesh, in_specs=P(None, None), out_specs=out_spec,
        check_vma=False)
    y = jax.ShapeDtypeStruct(PROBE_SHAPE, jnp.dtype(dtype))
    return jax.eval_shape(fn, y)


def lint_collectives(specs: Optional[Sequence] = None,
                     tps: Iterable[int] = (1, 2, 4, 8)) -> list[Finding]:
    """CT001 + CT002 over every (spec × tp × stream dtype) site."""
    import jax
    import jax.numpy as jnp

    from repro.comm.spec import CollectiveSpec

    if specs is None:
        specs = _default_specs()
    else:
        specs = [CollectiveSpec.parse(s) for s in specs]

    out: list[Finding] = []
    for spec in specs:
        short = spec.shorthand()
        # CT002: TP=1 — zero wire bytes, identity shape/dtype
        b1 = spec.bytes_on_wire(PROBE_SHAPE, 1)
        if b1 != 0.0:
            out.append(Finding(
                "CT002",
                f"bytes_on_wire at tp=1 is {b1}, not 0 — a single-rank "
                f"deployment would be billed for wire traffic",
                location=short, detail={"bytes": b1}))
        for dtype in STREAM_DTYPES:
            try:
                o1 = _abstract_apply(spec, 1, dtype)
            except Exception as e:     # tracing itself must succeed
                out.append(Finding(
                    "CT002", f"abstract apply failed at tp=1: {e}",
                    location=f"{short}[{dtype}]"))
                continue
            if (o1.shape, str(o1.dtype)) != (
                    PROBE_SHAPE, str(jnp.dtype(dtype))):
                out.append(Finding(
                    "CT002",
                    f"tp=1 is not the identity: {dtype}{PROBE_SHAPE} -> "
                    f"{o1.dtype}{o1.shape}",
                    location=f"{short}[{dtype}]"))
        # CT001: dtype stability at every TP degree with enough devices
        for tp in tps:
            if tp == 1 or tp > len(jax.devices()):
                continue
            # scatter strategies return a (8, n/tp) local shard; the
            # out_specs concatenation makes the GLOBAL aval (8, n) for
            # every strategy — a strategy returning the wrong local
            # shape therefore shows up as a wrong global shape here
            want_shape = PROBE_SHAPE
            for dtype in STREAM_DTYPES:
                loc = f"{short}[{dtype}]@tp={tp}"
                try:
                    o = _abstract_apply(spec, tp, dtype)
                except Exception as e:
                    out.append(Finding(
                        "CT001", f"abstract apply failed: {e}",
                        location=loc))
                    continue
                if str(o.dtype) != str(jnp.dtype(dtype)):
                    out.append(Finding(
                        "CT001",
                        f"collective returns {o.dtype}, not the residual "
                        f"stream's {dtype} — a wire dtype leaks into the "
                        f"caller",
                        location=loc,
                        detail={"got": str(o.dtype), "want": dtype}))
                if o.shape != want_shape:
                    out.append(Finding(
                        "CT001",
                        f"collective returns shape {o.shape}, contract "
                        f"says {want_shape}",
                        location=loc,
                        detail={"got": list(o.shape),
                                "want": list(want_shape)}))
    return out


# ---------------------------------------------------------------------------
# model-family contracts
# ---------------------------------------------------------------------------

def _family_smoke_cfgs():
    """One smoke config per registered family (first matching arch)."""
    from repro.configs import ARCH_IDS, get_smoke_config

    seen = {}
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        seen.setdefault(cfg.family, cfg)
    return seen


def _kv_geometry_leaves(tree, kvh: int, hd: int):
    """(path, aval) of float KV payload leaves (ndim >= 4), and whether
    each ends with the family's (kv_heads, head_dim) token geometry."""
    import jax
    import jax.numpy as jnp

    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if leaf.ndim < 4 or not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        name = jax.tree_util.keystr(path)
        out.append((name, leaf, leaf.shape[-2:] == (kvh, hd)))
    return out


def lint_families(batch: int = 2, seq: int = 16) -> list[Finding]:
    """CT003 + CT004 over every registered model family (smoke shapes)."""
    import jax
    import jax.numpy as jnp

    from repro.models import common as cm
    from repro.models.common import REPLICATED
    from repro.models.registry import build_model

    out: list[Finding] = []
    for family, cfg in sorted(_family_smoke_cfgs().items()):
        model = build_model(cfg)
        loc = f"{family}/{cfg.arch_id}"
        # CT004: abstract init -> forward -> f32 logits, no FLOPs
        try:
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            batch_st = model.batch_shape_structs(batch, seq)
            logits = jax.eval_shape(
                lambda p, b: model.forward(p, b, REPLICATED),
                params, batch_st)
        except Exception as e:
            out.append(Finding(
                "CT004", f"abstract forward failed: {e}", location=loc))
            continue
        if str(logits.dtype) != "float32":
            out.append(Finding(
                "CT004",
                f"forward logits are {logits.dtype}, not float32",
                location=loc, detail={"got": str(logits.dtype)}))
        if logits.shape != (batch, seq, cfg.vocab_size):
            out.append(Finding(
                "CT004",
                f"forward logits shape {logits.shape} != "
                f"{(batch, seq, cfg.vocab_size)}",
                location=loc))
        try:
            cache = jax.eval_shape(
                lambda: model.init_cache(batch, seq))
            tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            dec, _ = jax.eval_shape(
                lambda p, c, t, q: model.decode_step(p, c, t, q,
                                                     REPLICATED),
                params, cache, tok, pos)
        except Exception as e:
            out.append(Finding(
                "CT004", f"abstract decode_step failed: {e}",
                location=loc))
            continue
        if str(dec.dtype) != "float32":
            out.append(Finding(
                "CT004",
                f"decode logits are {dec.dtype}, not float32",
                location=loc, detail={"got": str(dec.dtype)}))
        # CT003: dense vs paged cache geometry agreement
        if not model.supports_paged:
            continue
        kvh, _, _ = cm.head_grid(cfg)
        hd = cfg.head_dim
        try:
            paged = jax.eval_shape(
                lambda: model.init_paged_cache(batch, 8, 8))
        except Exception as e:
            out.append(Finding(
                "CT003", f"abstract paged cache failed: {e}",
                location=loc))
            continue
        dense_kv = _kv_geometry_leaves(cache, kvh, hd)
        paged_kv = _kv_geometry_leaves(paged, kvh, hd)
        for which, leaves in (("dense", dense_kv), ("paged", paged_kv)):
            for name, leaf, ok in leaves:
                if not ok:
                    out.append(Finding(
                        "CT003",
                        f"{which} cache leaf {name} has trailing dims "
                        f"{leaf.shape[-2:]}, family geometry is "
                        f"({kvh}, {hd})",
                        location=loc))
        d_dtypes = {str(leaf.dtype) for _, leaf, _ in dense_kv}
        p_dtypes = {str(leaf.dtype) for _, leaf, _ in paged_kv}
        if d_dtypes != p_dtypes:
            out.append(Finding(
                "CT003",
                f"dense cache payload dtypes {sorted(d_dtypes)} != "
                f"paged {sorted(p_dtypes)}",
                location=loc))
    return out


def run(specs: Optional[Sequence] = None,
        tps: Iterable[int] = (1, 2, 4, 8)) -> list[Finding]:
    """Entry point the CLI calls: collective + family contracts."""
    return lint_collectives(specs=specs, tps=tps) + lint_families()
