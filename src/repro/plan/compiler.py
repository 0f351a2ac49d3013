"""Staged offline plan compiler (the repo's "prepare" step).

The paper's premise is that reordering (Algorithm 1), the P2 fold
(Algorithm 3), and the TP collective schedule are all decided *before the
first token*.  This module is where that decision happens — once, offline
— as a pipeline of pure functions over a ``PlanState``:

1. ``stage_quantize``   — walk the raw fp pytree; every MLP weight dict
   (``{"w_up", "w_down"[, "w_gate"]}``, arbitrarily stacked over leading
   L / (L, E) dims) becomes a scheme-agnostic ``PairBundle`` (both
   layouts + perms, ``core/reorder.quantize_pair`` under nested vmap).
2. ``stage_layout``     — every bundle becomes a ``PlannedPair`` in the
   policy's deployment scheme (Algorithm-1 ordering; for ``tp-aware``
   additionally the offline P2 column fold).
3. ``stage_fold_attention`` — beyond-paper: when
   ``cfg.quant.attn_tp_aware`` is set, plan the V->out_proj pairs with
   the head-block-constrained fold (``core/attention_fold.py``) into the
   artifact's aux tree.
4. ``stage_attention_dtype`` — store the dense attention projections
   (``wq``/``wk``/``wv``/``wo`` of every attention dict) in the
   activation dtype when that is a 16-bit float: the dtype the chip's
   default-precision matmul reads them in, so the served program has no
   weight to convert on each call.  After the V->O fold, which plans
   from the f32 weights.
5. ``autotune_collectives`` (``plan/tuner.py``, opt-in) — score every
   registered full-output collective per pair site (analytic wire bytes
   + a measured activation-error probe on calibration batches) and write
   the chosen per-layer ``CollectivePlan`` into the policy.
6. ``stage_shard``      — pre-split the planned pytree into per-rank
   row/column shards for the target TP degree, driven by the model's own
   ``param_specs`` (any leaf whose spec names the model axis is sliced;
   non-divisible leaves stay replicated and are recorded as such).

``compile_params`` runs stages 1, 2 and 4 in memory — this is what
``models/registry.Model.init`` calls, so building a quantized model IS
running the compiler (bit-exact with serving from an artifact ``prepare``d
from the same seed).  ``compile_plan`` runs all stages and wraps the
result in a serializable ``DeploymentArtifact``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import attention_fold, reorder
from repro.core.policy import ExecutionPolicy
from repro.core.quantization import choose_group_size
from repro.core.reorder import PairBundle, PlannedPair

#: fold_in tag separating the quantization rng stream from the init stream
#: (``Model.init`` and ``prepare`` must derive identical plan rngs from the
#: same seed for the artifact path to be bit-exact with the in-memory one).
PLAN_RNG_STREAM = 0x504C414E  # "PLAN"


@dataclasses.dataclass(frozen=True)
class PlanState:
    """The value threaded through the compiler stages (pure functions)."""

    cfg: ModelConfig
    policy: ExecutionPolicy
    params: Any                      # raw fp -> bundles -> planned pytree
    rng: jax.Array
    tp: Optional[int] = None         # target TP degree (None: no pre-shard)
    pair_meta: tuple = ()            # per-pair layout metadata (manifest)
    attn_plans: Any = None           # beyond-paper V->O folds (aux tree)
    rank_params: Optional[tuple] = None  # per-rank trees after stage_shard
    leaf_shards: Optional[dict] = None   # {leaf key: sliced dim | None}
    tuner_report: tuple = ()         # per-pair collective scores (manifest)


def _is_mlp_dict(node: Any) -> bool:
    return isinstance(node, dict) and "w_up" in node and "w_down" in node


def _walk_mlp(node: Any, fn, path: tuple = ()) -> Any:
    """Recursively rebuild ``node``, applying ``fn(mlp_dict, path)`` to
    every MLP weight dict."""
    if _is_mlp_dict(node):
        return fn(node, path)
    if isinstance(node, dict):
        return {k: _walk_mlp(v, fn, path + (k,)) for k, v in node.items()}
    return node


def _pair_group_sizes(cfg: ModelConfig, w_up, w_down) -> tuple[int, int]:
    """The deployment group sizes for one pair — identical to what the
    (deleted) init-time quantization chose: the row-TP layer's K (= ff)
    shards over up to ``tp_groups`` ranks, so its group size must tile the
    per-rank shard exactly (paper Sec 2.1: quantize once, deploy at any
    TP)."""
    d = w_up.shape[-2]
    ff = w_down.shape[-2]
    ff_shard = ff // cfg.quant.tp_groups if ff % cfg.quant.tp_groups == 0 \
        else ff
    return (choose_group_size(d, cfg.quant.group_size),
            choose_group_size(ff_shard, cfg.quant.group_size))


def _vmap_stacked(fn, lead: int):
    for _ in range(lead):
        fn = jax.vmap(fn)
    return fn


def _quantize_stack(w_up, w_down, w_gate, rngs, *, gs_up: int, gs_down: int,
                    act_order: bool) -> PairBundle:
    """``quantize_pair`` over a flat ``(n, ...)`` stack, one pair at a
    time, then stacked: the transient codes and permuted copies are one
    layer's, not the whole stack's — what keeps a full-width prepare
    within a host's RAM.  Op by op like the stacked ``vmap`` it replaces,
    so the plan stays bit-identical (a jitted body may rewrite the
    scales' division by 15 into a multiply)."""
    def one(wu, wd, wg, r):
        return reorder.quantize_pair(
            wu, wd, w_gate=wg, group_size_up=gs_up, group_size_down=gs_down,
            act_order=act_order, rng=r)

    if rngs.shape[0] == 0:       # an empty stack: only the shapes matter
        return jax.vmap(one)(w_up, w_down, w_gate, rngs)
    out = [one(w_up[i], w_down[i], None if w_gate is None else w_gate[i],
               rngs[i]) for i in range(rngs.shape[0])]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *out)


# ---------------------------------------------------------------------------
# stage 1: quantize
# ---------------------------------------------------------------------------

def stage_quantize(state: PlanState) -> PlanState:
    """Raw fp MLP dicts -> scheme-agnostic ``PairBundle``s (+ metadata)."""
    cfg = state.cfg
    counter = [0]
    meta = []

    def quantize_one(node: dict, path: tuple) -> PairBundle:
        counter[0] += 1
        sub = jax.random.fold_in(state.rng, counter[0])
        w_up, w_down = node["w_up"], node["w_down"]
        w_gate = node.get("w_gate")
        lead = w_up.ndim - 2
        stack = w_up.shape[:lead]
        gs_up, gs_down = _pair_group_sizes(cfg, w_up, w_down)

        if lead == 0:
            rngs = sub[None]
        else:
            nstack = 1
            for d in stack:
                nstack *= d
            rngs = jax.random.split(sub, nstack)

        def flat(w):
            return None if w is None else w.reshape((-1,) + w.shape[lead:])

        bundle = _quantize_stack(
            flat(w_up), flat(w_down), flat(w_gate), rngs, gs_up=gs_up,
            gs_down=gs_down, act_order=cfg.quant.act_order)
        bundle = jax.tree.map(lambda a: a.reshape(stack + a.shape[1:]),
                              bundle)
        # dotted paths: the SAME string the runtime epilogues resolve
        # their per-layer collective by (models pass it to mlp_forward)
        meta.append({
            "path": ".".join(path), "stacked": list(w_up.shape[:lead]),
            "k1": int(w_up.shape[-2]), "n1": int(w_up.shape[-1]),
            "n2": int(w_down.shape[-1]), "gate": w_gate is not None,
            "group_size_up": gs_up, "group_size_down": gs_down,
        })
        return bundle

    params = _walk_mlp(state.params, quantize_one)
    return dataclasses.replace(state, params=params,
                               pair_meta=tuple(meta))


# ---------------------------------------------------------------------------
# stage 2: reorder / fold (layout)
# ---------------------------------------------------------------------------

def stage_layout(state: PlanState) -> PlanState:
    """``PairBundle``s -> ``PlannedPair``s in the policy's scheme."""
    scheme = state.policy.scheme

    def layout_one(node):
        if not isinstance(node, PairBundle):
            return node
        lead = node.up.naive.qweight.ndim - 2
        return _vmap_stacked(
            lambda b: reorder.layout_pair(b, scheme), lead)(node)

    params = jax.tree.map(layout_one, state.params,
                          is_leaf=lambda x: isinstance(x, PairBundle))
    meta = tuple(dict(m, scheme=scheme) for m in state.pair_meta)
    return dataclasses.replace(state, params=params, pair_meta=meta)


# ---------------------------------------------------------------------------
# stage 3: beyond-paper attention V->O fold
# ---------------------------------------------------------------------------

def _is_attn_dict(node: Any) -> bool:
    return isinstance(node, dict) and "wv" in node and "wo" in node


def stage_fold_attention(state: PlanState) -> PlanState:
    """Plan head-block-constrained V->O folds (``cfg.quant.attn_tp_aware``).

    The folded pairs land in ``state.attn_plans`` (mirroring the param
    paths) — serialized with the artifact so the attention runtime
    integration consumes precompiled plans instead of re-folding."""
    cfg = state.cfg
    if not cfg.quant.attn_tp_aware:
        return state
    from repro.models.common import head_grid

    kvp, _, hp = head_grid(cfg)
    hd = cfg.head_dim
    gs = choose_group_size(hd, cfg.quant.group_size)
    counter = [0]
    plans = {}

    def fold(node: Any, path: tuple = ()):
        if _is_attn_dict(node):
            counter[0] += 1
            # offset keeps the attention-fold stream disjoint from the MLP
            # quantize stage's fold_in counters
            sub = jax.random.fold_in(state.rng, 0x41545400 + counter[0])
            w_v, w_o = node["wv"], node["wo"]
            lead = w_v.ndim - 2
            nstack = 1
            for d in w_v.shape[:lead]:
                nstack *= d
            rngs = (sub if lead == 0 else
                    jax.random.split(sub, nstack).reshape(
                        *w_v.shape[:lead], 2))

            def fold_one(wv, wo, r):
                return attention_fold.plan_attention_vo(
                    wv, wo, n_heads=hp, n_kv_heads=kvp, head_dim=hd,
                    group_size=gs, rng=r)

            plans[".".join(path)] = _vmap_stacked(fold_one, lead)(
                w_v, w_o, rngs)
            return
        if isinstance(node, dict):
            for k, v in node.items():
                fold(v, path + (k,))

    fold(state.params)
    return dataclasses.replace(state, attn_plans=plans or None)


# ---------------------------------------------------------------------------
# stage 4: attention projection storage dtype
# ---------------------------------------------------------------------------

#: the dense projection leaves of an attention dict
ATTN_PROJ = ("wq", "wk", "wv", "wo")


def stage_attention_dtype(state: PlanState) -> PlanState:
    """Store every attention dict's projections in ``cfg.dtype`` when that
    is a 16-bit float (round to nearest even, as the compiler's own
    converts round); an f32 ``cfg.dtype`` keeps them f32.  The models read
    these leaves through ``models.common.project``, which accumulates in
    f32."""
    dt = jnp.dtype(state.cfg.dtype)
    if not (jnp.issubdtype(dt, jnp.floating) and dt.itemsize == 2):
        return state

    def store(node: Any) -> Any:
        if not isinstance(node, dict):
            return node
        if _is_attn_dict(node):
            return {k: v.astype(dt) if k in ATTN_PROJ else v
                    for k, v in node.items()}
        return {k: store(v) for k, v in node.items()}

    return dataclasses.replace(state, params=store(state.params))


# ---------------------------------------------------------------------------
# stage 6: TP pre-shard
# ---------------------------------------------------------------------------

def _model_axis_dim(spec, axis: str) -> Optional[int]:
    """Position of ``axis`` in a PartitionSpec (None: not sharded here)."""
    if spec is None:
        return None
    for i, entry in enumerate(spec):
        if entry == axis:
            return i
        if isinstance(entry, (tuple, list)) and axis in entry:
            return i
    return None


@dataclasses.dataclass(frozen=True)
class _PlanContext:
    """Duck-typed ``ParallelContext`` stand-in for spec queries at prepare
    time: no mesh exists, but ``axis_size(model)`` must report the target
    TP degree so specs (e.g. vocab-dim embedding sharding) match what the
    serving mesh will decide."""

    tp: int
    model_axis: str = "model"
    batch_axes: tuple = ("data",)
    mesh: Any = None

    def axis_size(self, name: str) -> int:
        return self.tp if name == self.model_axis else 1

    @property
    def batch_spec(self):
        return self.batch_axes if self.batch_axes else None

    @property
    def ep_axis(self):
        return self.batch_axes[-1] if self.batch_axes else None


def shard_params(cfg: ModelConfig, params: Any, tp: int,
                 axis: str = "model") -> tuple[list, dict]:
    """Pre-split a planned pytree into ``tp`` per-rank trees.

    Sharding is driven by the model's own ``param_specs``: any leaf whose
    spec names ``axis`` is sliced into ``tp`` equal parts along that dim
    (column-TP layers along N1, the row-TP layer along its packed K and
    metadata groups, P2 into local chunks — exactly the layout
    ``core/reorder.shard_pair`` produces for a single pair); leaves whose
    sharded dim does not divide ``tp`` stay replicated and are recorded so
    the loader reassembles faithfully.  Returns ``(rank_trees,
    {leaf key: sliced dim | None})``.
    """
    from repro.models.registry import build_model
    from repro.train import checkpoint

    model = build_model(cfg)
    specs = model.param_specs(params, _PlanContext(tp=tp, model_axis=axis))

    flat_p = checkpoint.flatten_keys(params)
    from jax.sharding import PartitionSpec as P
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    if len(spec_leaves) != len(flat_p):
        raise ValueError(
            f"param_specs tree ({len(spec_leaves)} leaves) does not match "
            f"params ({len(flat_p)} leaves) for {cfg.arch_id}; cannot "
            "pre-shard this model")

    leaf_shards: dict[str, Optional[int]] = {}
    sliced: dict[str, list] = {}
    for (key, leaf), spec in zip(flat_p.items(), spec_leaves):
        dim = _model_axis_dim(spec, axis)
        if dim is not None and leaf.shape[dim] % tp == 0 \
                and leaf.shape[dim] >= tp:
            n = leaf.shape[dim] // tp
            # one rank holds the whole leaf: no slice copy
            parts = [leaf] if tp == 1 else [
                jax.lax.slice_in_dim(leaf, r * n, (r + 1) * n, axis=dim)
                for r in range(tp)]
            leaf_shards[key] = dim
        else:
            parts = [leaf] * tp
            leaf_shards[key] = None
        sliced[key] = parts

    treedef = jax.tree_util.tree_structure(params)
    keys = list(flat_p)
    rank_trees = [
        jax.tree_util.tree_unflatten(treedef, [sliced[k][r] for k in keys])
        for r in range(tp)
    ]
    return rank_trees, leaf_shards


def stage_shard(state: PlanState) -> PlanState:
    if state.tp is None:
        return state
    rank_trees, leaf_shards = shard_params(state.cfg, state.params,
                                           state.tp)
    return dataclasses.replace(state, rank_params=tuple(rank_trees),
                               leaf_shards=leaf_shards)


# ---------------------------------------------------------------------------
# pipeline entry points
# ---------------------------------------------------------------------------

STAGES = (stage_quantize, stage_layout, stage_fold_attention,
          stage_attention_dtype, stage_shard)


def run_stages(state: PlanState, stages=STAGES) -> PlanState:
    for stage in stages:
        state = stage(state)
    return state


def compile_params(cfg: ModelConfig, raw_params: Any, *,
                   rng: Optional[jax.Array] = None,
                   policy: Optional[ExecutionPolicy] = None,
                   scheme: Optional[str] = None) -> Any:
    """In-memory compile: raw fp params -> planned pytree (stages 1, 2, 4).

    This is the single quantize/reorder call site model construction goes
    through (``Model.init``) and what ``quant/gptq.quantize_model`` wraps
    for trained checkpoints — and it is bit-exact with serving from an
    artifact ``prepare``d with the same config/policy/rng.
    """
    policy = policy if policy is not None else ExecutionPolicy.from_config(cfg)
    if scheme is not None:
        policy = policy.with_(scheme=scheme)
    state = PlanState(
        cfg=cfg, policy=policy, params=raw_params,
        rng=rng if rng is not None else jax.random.PRNGKey(0))
    return run_stages(state, (stage_quantize, stage_layout,
                              stage_attention_dtype)).params


def compile_plan(cfg: ModelConfig, raw_params: Any, *, tp: int,
                 rng: Optional[jax.Array] = None,
                 policy: Optional[ExecutionPolicy] = None,
                 seed: Optional[int] = None,
                 extra_manifest: Optional[dict] = None,
                 autotune: bool = False,
                 tune_budget: Optional[float] = None):
    """Full offline compile: raw fp params -> ``DeploymentArtifact``.

    Runs every stage (quantize, layout, attention fold, attention
    storage dtype, optional collective autotune, TP pre-shard) and
    freezes the result with its manifest.  ``autotune=True`` inserts
    ``plan/tuner.py``'s ``autotune_collectives`` (max rel-error
    ``tune_budget``; tuner default when None) so the artifact carries a
    per-layer ``CollectivePlan`` instead of one global collective.
    ``seed`` is provenance only (recorded so a served artifact can name
    the init stream it came from).
    """
    from repro.plan.artifact import DeploymentArtifact

    policy = policy if policy is not None else ExecutionPolicy.from_config(cfg)
    state = PlanState(
        cfg=cfg, policy=policy, params=raw_params, tp=int(tp),
        rng=rng if rng is not None else jax.random.PRNGKey(0))
    stages = [stage_quantize, stage_layout, stage_fold_attention,
              stage_attention_dtype]
    if autotune:
        from repro.plan import tuner

        kw = {} if tune_budget is None else {"budget": tune_budget}
        stages.append(lambda s: tuner.autotune_collectives(s, **kw))
    stages.append(stage_shard)
    state = run_stages(state, tuple(stages))
    return DeploymentArtifact.from_state(state, seed=seed,
                                         extra=extra_manifest)


def prepare(cfg: ModelConfig, *, tp: int, seed: int = 0,
            policy: Optional[ExecutionPolicy] = None,
            extra_manifest: Optional[dict] = None,
            autotune: bool = False,
            tune_budget: Optional[float] = None):
    """Seed -> artifact, the canonical prepare recipe.

    Derives the raw init and the plan rng exactly the way ``Model.init``
    does (``init_raw(key)`` + ``fold_in(key, PLAN_RNG_STREAM)``) — this
    is THE definition of "same seed" in the bit-exactness guarantee, so
    every prepare caller (CLI, examples, tests) must go through here.
    """
    from repro.models.registry import build_model

    key = jax.random.PRNGKey(seed)
    raw = build_model(cfg).init_raw(key)
    return compile_plan(
        cfg, raw, tp=tp, rng=jax.random.fold_in(key, PLAN_RNG_STREAM),
        policy=policy, seed=seed, extra_manifest=extra_manifest,
        autotune=autotune, tune_budget=tune_budget)
