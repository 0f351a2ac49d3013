"""Collective autotuner — per-layer ``CollectivePlan`` as a compiler stage.

The repo used to apply ONE global ``ExecutionPolicy.collective`` to every
row-TP epilogue, but tolerance to wire compression varies sharply by
layer (down_proj vs attention O-proj vs MoE within-expert — Hansen-Palmus
et al. 2024; Dong et al. 2024 both mix bit-widths per layer to hold
quality while cutting wire bytes).  ``autotune_collectives`` makes that
decision offline, where the paper says the whole deployment plan lives:

for every pair site the quantize/layout stages planned (``pair_meta``),
it scores each candidate strategy with

* the strategy's analytic ``bytes_on_wire`` (ring cost model — the wire
  cost is shape-determined, no compilation needed), and
* a measured activation-error probe: the site's layer-0 pair is split
  into per-rank shards (``reorder.shard_pair``), calibration batches run
  through each rank's local forward (``pair_forward_reference`` computes
  exactly the partial sums a TP rank produces), and the wire is
  *simulated* with the same blockwise quantize/dequantize helpers the
  runtime strategies use — so the probe needs no mesh and runs on the
  prepare host,

then picks the CHEAPEST strategy whose relative error stays within
``budget`` and writes the resulting ``CollectivePlan`` (one fully
qualified path entry per site + a psum default) into
``PlanState.policy``.  The per-site scores land in
``PlanState.tuner_report`` and are serialized into the artifact manifest
so a served deployment can show why each layer got its collective.

Sites the tuner cannot shard for the target TP degree (non-divisible N1,
group-misaligned shards) keep the default — recorded as ``untunable`` in
the report, never silently dropped.

Aux attention V->O folds (``cfg.quant.attn_tp_aware``) are probed as
sites too (``kind: "attn_vo"`` in the report) now that the attention
runtime consumes them; their entries join the plan under the fold's
dotted path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.comm import dispatch as comm_dispatch
from repro.comm.spec import CollectivePlan, CollectiveSpec
from repro.core import reorder, schemes
from repro.core.quantization import choose_group_size

#: default max relative activation error a tuned collective may introduce
DEFAULT_BUDGET = 0.05

#: fold_in tag separating the tuner's calibration stream from the
#: quantize / attention-fold streams (same rng key, disjoint draws)
TUNE_RNG_STREAM = 0x54554E45  # "TUNE"


def candidate_specs() -> tuple[CollectiveSpec, ...]:
    """Tunable strategies: every registered full-output collective.

    ``none`` (partial sums) and scatter-output strategies are excluded —
    they change the epilogue's output contract, which is the caller's
    structural decision, not a quality/bytes trade-off.
    """
    out = []
    for name in comm_dispatch.strategies():
        if name == "none" or comm_dispatch.scatters_output(
                CollectiveSpec.parse(name)):
            continue
        out.append(CollectiveSpec.parse(name))
    return tuple(out)


def simulate_wire(partials, spec: CollectiveSpec) -> jax.Array:
    """Host-side simulation of ``comm.dispatch`` closing ``partials``.

    ``partials``: list of ``tp`` per-rank f32 partial sums (m, n).
    Reuses the dispatch module's own blockwise quantize/dequantize
    helpers, so the simulated wire loss is the runtime strategies' —
    phase 1 rounds every rank's contribution once, phase 2 rounds the
    re-quantized reduction once (the padded two-phase ring's numerics).
    """
    tp = len(partials)
    if spec.name in ("psum", "psum_scatter", "none") or tp == 1:
        return sum(partials[1:], partials[0])
    n = partials[0].shape[-1]
    if spec.name == "cast":
        # the all-reduce accumulates in the wire dtype on the wire
        acc = partials[0].astype(spec.wire_dtype)
        for p in partials[1:]:
            acc = (acc + p.astype(spec.wire_dtype)).astype(spec.wire_dtype)
        return acc.astype(partials[0].dtype)
    pad_to = tp * (8 if spec.bits == 4 else 1)
    bs = choose_group_size((n + (-n) % pad_to) // tp, spec.block_size)

    if spec.name == "quant-int8":
        def roundtrip(v):
            q, s = comm_dispatch._blockwise_quantize(v, bs)
            return comm_dispatch._blockwise_dequantize(q, s, bs)
    elif spec.name == "quant-int4":
        def roundtrip(v):
            q, s, z = comm_dispatch._blockwise_quantize_int4(v, bs)
            return comm_dispatch._blockwise_dequantize_int4(q, s, z, bs)
    else:
        raise ValueError(f"no wire simulation for collective {spec.name!r}")

    pad = (-n) % bs
    padded = [jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(0, pad)]) if pad else p
              for p in partials]
    red = sum(roundtrip(p) for p in padded)          # phase 1 per rank
    out = roundtrip(red)                             # phase 2 re-quantize
    return out[..., :n] if pad else out


def _site_pair(params, path: str, stacked):
    """The layer-0 ``PlannedPair`` at a dotted ``pair_meta`` path."""
    node = params
    for part in path.split("."):
        node = node[part]
    lead = len(stacked)
    if lead:
        node = jax.tree.map(lambda a: a[(0,) * lead], node)
    return node


def _site_attn_pair(plans):
    """The layer-0 V->O ``PlannedPair`` of a (possibly stacked) aux fold."""
    lead = plans.up.qweight.ndim - 2
    if lead:
        return jax.tree.map(lambda a: a[(0,) * lead], plans)
    return plans


def _probe_site(pp, tp: int, rng, calib_batch: int, candidates,
                activation: Optional[str]):
    """Score every candidate on one pair site; returns {shorthand: dict}."""
    shards = reorder.shard_pair(pp, tp)
    x = jax.random.normal(rng, (calib_batch, pp.k1), jnp.float32)
    partials = [
        jnp.asarray(schemes.pair_forward_reference(
            x, s, activation=activation), jnp.float32)
        for s in shards]
    exact = sum(partials[1:], partials[0])
    scale = float(jnp.max(jnp.abs(exact)))
    scores = {}
    for spec in candidates:
        sim = simulate_wire(partials, spec)
        err = float(jnp.max(jnp.abs(sim - exact))) / max(scale, 1e-30)
        scores[spec.shorthand()] = {
            "spec": spec,
            "rel_err": err,
            # per-token wire bytes (batch-independent ranking)
            "bytes_per_token": spec.bytes_on_wire((1, pp.n2), tp),
        }
    return scores


def autotune_collectives(state, mesh=None, *,
                         budget: float = DEFAULT_BUDGET,
                         calib_batch: int = 8,
                         candidates=None):
    """Compiler stage: choose a per-layer ``CollectivePlan`` for ``state``.

    ``mesh`` (optional) only supplies the TP degree when ``state.tp`` is
    unset — the probe itself is mesh-free (see ``simulate_wire``).
    Returns a new ``PlanState`` whose policy carries the tuned plan and
    whose ``tuner_report`` records every candidate's score per site.
    """
    tp = state.tp
    if tp is None and mesh is not None:
        tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model")
    if not tp:
        raise ValueError(
            "autotune_collectives needs a target TP degree (PlanState.tp "
            "or a mesh with a 'model' axis)")
    tp = int(tp)
    default = CollectiveSpec(name="psum")
    if candidates is None:
        candidates = candidate_specs()

    # probe sites: every planned MLP pair, then (when the attention-fold
    # stage ran) every aux V->O fold — the attention runtime consumes
    # those pairs now, so their epilogues are collective sites too.
    sites = [(meta["path"], "pair",
              lambda meta=meta: _site_pair(state.params, meta["path"],
                                           meta["stacked"]),
              state.cfg.activation)
             for meta in state.pair_meta]
    sites += [(path, "attn_vo",
               lambda plans=plans: _site_attn_pair(plans),
               None)   # no activation between the V and O GEMMs
              for path, plans in sorted((state.attn_plans or {}).items())]

    entries, report = [], []
    for i, (path, kind, get_pair, activation) in enumerate(sites):
        rng = jax.random.fold_in(
            jax.random.fold_in(state.rng, TUNE_RNG_STREAM), i)
        if tp == 1:
            chosen, scores, status = default, {}, "tp=1 (no collective)"
        else:
            try:
                scores = _probe_site(get_pair(), tp, rng, calib_batch,
                                     candidates, activation)
                status = "tuned"
            except ValueError as e:   # non-divisible / group-misaligned
                scores, status = {}, f"untunable: {e}"
            ok = [v for v in scores.values() if v["rel_err"] <= budget]
            # nothing scored / nothing within budget -> the safe default
            chosen = (min(ok, key=lambda v: v["bytes_per_token"])["spec"]
                      if ok else default)
        entries.append((path, chosen))
        report.append({
            "path": path, "kind": kind, "tp": tp, "budget": budget,
            "status": status, "chosen": chosen.shorthand(),
            "candidates": {
                short: {"rel_err": v["rel_err"],
                        "bytes_per_token": v["bytes_per_token"]}
                for short, v in scores.items()},
        })

    plan = CollectivePlan(entries=tuple(entries), default=default)
    return dataclasses.replace(
        state, policy=state.policy.with_(collective=plan),
        tuner_report=tuple(report))
