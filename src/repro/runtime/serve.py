"""Serving engine: prefill + decode steps over any registry model.

``Engine`` owns jitted ``prefill`` and ``decode_step`` closures.  Prefill
runs the full forward and writes the prompt's KV into the cache by
replaying tokens through ``decode_step``'s cache writer in one fused scan
for attention archs; recurrent archs thread their O(1) state natively.

The engine is deliberately single-program: batching across requests is the
scheduler's job (``runtime/scheduler.py``) — requests are padded into the
fixed (B, S) program shapes so one compiled executable serves all traffic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.policy import ExecutionPolicy
from repro.models.common import ParallelContext, REPLICATED
from repro.models.registry import Model, build_model
from repro.runtime import sampling


def param_bytes(params: Any) -> dict:
    """Bytes of the whole parameter tree (global shapes) per dtype name;
    concrete or abstract leaves alike."""
    out: dict = {}
    for a in jax.tree_util.tree_leaves(params):
        dt = jnp.dtype(a.dtype)
        out[dt.name] = out.get(dt.name, 0) + int(np.prod(a.shape)) \
            * dt.itemsize
    return dict(sorted(out.items()))


def _exact(spec: P, shape, mesh) -> P:
    """``spec`` less the mesh axes that do not divide their dim: a jitted
    output must shard exactly (a sequence-sharded cache of odd length is
    then replicated along the sequence)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * len(shape)):
        names = (axes,) if isinstance(axes, str) else (axes or ())
        out.append(axes if dim % int(np.prod([sizes[a] for a in names]))
                   == 0 else None)
    return P(*out)


@dataclasses.dataclass
class Engine:
    model: Model
    params: Any
    ctx: ParallelContext = REPLICATED
    max_seq: int = 2048
    window: Optional[int] = None
    # The deployment plan every quantized GEMM — kernel backend, dtypes,
    # and the row-TP epilogue ``CollectiveSpec`` — executes under.  None
    # derives it from the model config; the resolved policy is injected
    # into ``ctx`` so model code sees one source of truth.
    policy: Optional[ExecutionPolicy] = None
    # The artifact's aux plans (precompiled attention V->O folds) — closed
    # over by the jitted step functions for families that consume them.
    aux: Optional[Any] = None
    # Per-rank load ledger (``dist.loader.RankLoadStats``) when the params
    # came from ``DeploymentArtifact.load_for_mesh`` — surfaced so the
    # launcher/banner can report which rank files this process read.
    load_stats: Optional[Any] = None

    def __post_init__(self):
        cfg = self.model.cfg
        mod = self.model

        if self.policy is None:
            self.policy = (self.ctx.policy if self.ctx.policy is not None
                           else ExecutionPolicy.from_config(cfg))
        if self.ctx.policy is None:
            self.ctx = dataclasses.replace(self.ctx, policy=self.policy)
        elif self.ctx.policy != self.policy:
            raise ValueError(
                "Engine got conflicting deployment plans: "
                f"policy={self.policy} but ctx.policy={self.ctx.policy}; "
                "pass one (the ctx policy is what model code executes)")
        aux = self.aux
        # once, here: what the served weights hold, in each dtype, and the
        # model-axis size they are split over
        self.param_bytes = param_bytes(self.params)
        self.tp = self.ctx.axis_size(self.ctx.model_axis)

        def prefill_logits(params, batch):
            return mod.forward(params, batch, self.ctx, window=self.window,
                               aux=aux)

        def decode(params, cache, tokens, pos, pages=None):
            return mod.decode_step(params, cache, tokens, pos, self.ctx,
                                   window=self.window, pages=pages, aux=aux)

        def reset_slot(cache, slot):
            # zero one slot's lane across every per-slot state leaf
            # (batch is dim 1 everywhere: (L/ns, B, ...)).  Used when a
            # recurrent family's slot is re-admitted mid-stream — unlike
            # KV rows, conv/lru/wkv state has no position mask to hide
            # the previous occupant.
            return jax.tree_util.tree_map(
                lambda leaf: leaf.at[:, slot].set(
                    jnp.zeros_like(leaf[:, slot])), cache)

        self._prefill = jax.jit(prefill_logits)
        self._decode = jax.jit(decode, donate_argnums=1)
        self._reset_slot = jax.jit(reset_slot, donate_argnums=0)
        self._replicate = None   # lazily-built logits all-gather (multiproc)

    # ------------------------------------------------------------------
    def _host(self, logits):
        """Logits -> host values the eager sampling/scheduling code may
        touch.  Single-controller: the array is fully addressable, return
        it as-is (zero cost).  Multi-controller: jitted outputs can be
        sharded over the data axis, and eager ops on non-addressable
        global arrays raise — all-gather to replicated (a jitted identity
        with ``out_shardings=P()``) and pull to numpy; every process then
        steps the same host-side sampling, keeping the controllers in
        lockstep."""
        if jax.process_count() == 1:
            return logits
        if self._replicate is None:
            self._replicate = jax.jit(
                lambda a: a,
                out_shardings=NamedSharding(self.ctx.mesh, P()))
        return np.asarray(self._replicate(logits))

    # ------------------------------------------------------------------
    @property
    def supports_continuous(self) -> bool:
        """True when the scheduler may run this model at token granularity
        with per-slot position vectors (continuous batching).

        dense/moe qualify because their ENTIRE decode state is the
        position-masked KV cache: a reused slot's stale rows are hidden by
        the ``j <= pos`` mask, so admission is bit-exact.  ssm/hybrid
        carry per-lane *recurrent* state (rwkv6 wkv/shift, rglru conv/lru)
        with no mask to reset it — the scheduler instead zeroes the
        re-admitted slot's lane (``reset_slot``), which is exactly the
        fresh-cache initial condition, so they run continuously too
        (their fixed-size state is a single accounting page).  audio/vlm
        stay batch-drained: the cross-attention prefill (frames/patches)
        is batch-global."""
        return self.model.cfg.family in ("dense", "moe", "hybrid", "ssm")

    @property
    def uses_page_table(self) -> bool:
        """True when decode steps take a page-table argument: a paged
        policy AND a family whose KV grows with the sequence.  Recurrent
        families under a paged policy keep dense fixed-size state."""
        return self.policy.kv.paged and self.model.supports_paged

    def init_cache(self, batch: int):
        """The dense per-slot cache; under a mesh, made in the sharding of
        the model's ``cache_specs``, so the first donated decode call and
        every later one see the same layout (one compile)."""
        make = functools.partial(self.model.init_cache, batch, self.max_seq,
                                 window=self.window)
        if self.ctx.mesh is None:
            return make()
        mesh = self.ctx.mesh
        shapes = jax.eval_shape(make)
        shard = jax.tree.map(
            lambda s, a: NamedSharding(mesh, _exact(s, a.shape, mesh)),
            self.model.cache_specs(self.ctx), shapes,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(make, out_shardings=shard)()

    def init_paged_cache(self, batch: int, n_pages: int):
        spec = self.policy.kv
        return self.model.init_paged_cache(batch, n_pages, spec.page_size,
                                           bits=spec.bits)

    def reset_slot(self, cache, slot: int):
        """Zero one slot's lane of a dense per-slot cache (recurrent
        state reset on re-admission)."""
        return self._reset_slot(cache, slot)

    def prefill(self, batch_inputs: dict, cache, prompt_len: jax.Array):
        """Run the prompt; returns (last_logits (B, V), cache).

        ``batch_inputs["tokens"]``: (B, S) right-padded prompts;
        ``prompt_len``: (B,) true lengths.  The cache is filled by replaying
        tokens through the decode path (one lax.scan over S) — identical
        numerics to the decode program that follows.
        """
        tokens = batch_inputs["tokens"]
        b, s = tokens.shape
        cfg = self.model.cfg

        if cfg.family == "audio":
            from repro.models import whisper

            enc = whisper.encode(cfg, self.params, batch_inputs["frames"],
                                 self.ctx)
            ks, vs = whisper.precompute_cross(cfg, self.params, enc, self.ctx)
            cache = dict(cache, cross_k=ks.astype(cache["cross_k"].dtype),
                         cross_v=vs.astype(cache["cross_v"].dtype))
        if cfg.family == "vlm":
            from repro.models import vision_llama

            ks, vs = vision_llama.precompute_cross(
                cfg, self.params, batch_inputs["patches"], self.ctx)
            cache = dict(cache, cross_k=ks.astype(cache["cross_k"].dtype),
                         cross_v=vs.astype(cache["cross_v"].dtype))

        decode = self._decode

        def scan_fn(carry, t):
            cache, last = carry
            logits, cache = decode(self.params, cache, tokens[:, t], t)
            keep = (t == prompt_len - 1)[:, None]
            last = jnp.where(keep, self._host(logits), last)
            return (cache, last), None

        # python loop over prompt positions (jit'd step): keeps memory flat
        # and matches decode numerics exactly.
        last = jnp.zeros((b, cfg.vocab_size), jnp.float32)
        carry = (cache, last)
        for t in range(s):
            carry, _ = scan_fn(carry, jnp.int32(t))
        cache, last = carry
        return last, cache

    def generate(self, rng, batch_inputs: dict, prompt_len, *,
                 max_new_tokens: int = 32,
                 scfg: sampling.SamplingConfig = sampling.SamplingConfig()):
        """Batched generation; returns (B, max_new_tokens) token ids."""
        tokens = batch_inputs["tokens"]
        b, s = tokens.shape
        prompt_len = jnp.asarray(prompt_len, jnp.int32)
        cache = self.init_cache(b)
        logits, cache = self.prefill(batch_inputs, cache, prompt_len)

        out = []
        pos = prompt_len.max()
        tok = sampling.sample(rng, logits, scfg)
        out.append(tok)
        for i in range(max_new_tokens - 1):
            rng, sub = jax.random.split(rng)
            logits, cache = self._decode(self.params, cache, tok, pos + i)
            tok = sampling.sample(sub, self._host(logits), scfg)
            out.append(tok)
        return jnp.stack(out, axis=1)


def make_engine(cfg, rng=None, *, ctx: ParallelContext = REPLICATED,
                max_seq: int = 2048, window=None,
                policy: Optional[ExecutionPolicy] = None,
                artifact=None, per_rank: Optional[bool] = None) -> Engine:
    """Build a serving engine.

    ``artifact``: a ``DeploymentArtifact`` (or its directory path) from
    ``plan`` / ``launch.serve prepare``.  The engine then serves the
    precompiled plan — no GPTQ, no layout planning at load time — after
    validating the artifact's manifest against ``cfg``, the effective
    policy, and the mesh's model-axis degree (a mismatched plan raises
    ``PlanMismatchError`` instead of silently serving).  Without an
    artifact, ``Model.init`` runs the identical compiler in memory.

    ``per_rank``: load the artifact via ``load_for_mesh`` — each process
    reads only its own ranks' ``rank_NN.npz`` files and assembles
    mesh-sharded global arrays (DESIGN.md §11).  Default (None): on when
    this is a multi-process launch.  Requires a directory path and a mesh.
    """
    model = build_model(cfg)
    aux = None
    load_stats = None
    if artifact is not None:
        from repro.plan import DeploymentArtifact

        if per_rank is None:
            per_rank = jax.process_count() > 1
        if isinstance(artifact, (str, bytes)):
            if per_rank:
                if ctx.mesh is None:
                    raise ValueError(
                        "per-rank artifact loading needs a mesh (pass a "
                        "ParallelContext with ctx.mesh set)")
                artifact = DeploymentArtifact.load_for_mesh(artifact,
                                                            ctx.mesh)
            else:
                artifact = DeploymentArtifact.load(artifact)
        eff_policy = policy
        if eff_policy is None:
            eff_policy = (ctx.policy if ctx.policy is not None
                          else ExecutionPolicy.from_config(cfg))
        tp = ctx.axis_size(ctx.model_axis) if ctx.mesh is not None else 1
        artifact.validate(cfg=cfg, policy=eff_policy, tp=tp)
        params = artifact.params()
        aux = artifact.aux   # precompiled V->O folds (None when absent)
        load_stats = artifact.load_stats
    else:
        params = model.init(rng if rng is not None else jax.random.PRNGKey(0))
    return Engine(model=model, params=params, ctx=ctx, max_seq=max_seq,
                  window=window, policy=policy, aux=aux,
                  load_stats=load_stats)
