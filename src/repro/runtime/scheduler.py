"""Batched request scheduler for the serving runtime.

Continuous-batching-lite: requests arrive with arbitrary prompt lengths;
the scheduler packs up to ``max_batch`` of them into one fixed-shape
(B, S) program, right-padding prompts, tracking per-slot progress, and
retiring finished slots so new requests can be admitted between decode
steps.  One compiled executable serves all traffic (shapes never change).

Two drain modes:

* **continuous** (token-granularity, the default wherever the family
  supports per-slot position vectors): one persistent decode program
  steps all ``max_batch`` slots together, each slot running its own
  clock.  A slot that finishes is refilled from the queue at the next
  step boundary — prompt replay and generation are the same decode loop,
  so admission never stalls the other slots.  Numerics per request are
  bit-identical to running it alone: for attention families the causal
  mask hides every other slot's cache rows; for recurrent families
  (rglru/rwkv6) the re-admitted slot's state lane is zeroed
  (``Engine.reset_slot``) — exactly the fresh-cache initial condition.
  This mode is incremental: ``step()`` runs exactly one admission +
  decode step and reports what happened as ``StepEvent``s, which is what
  the serving front end (``repro.serving``) builds its streaming loop
  on; ``run()`` just steps until the queue drains.
* **batch-drain** (legacy fallback, audio/vlm): popleft up to
  ``max_batch`` requests, run them to completion via ``Engine.generate``
  (those families need the batch-global cross-attention prefill).
  Per-request sampling overrides are a continuous-mode feature; this
  path samples with the scheduler-global config.

Cache lifecycle: the decode cache (dense rows or the paged pool) is
built lazily on the first step and — new in the paged-cache PR — freed
again by ``release_cache()`` once the engine idles, so a long-lived
serving loop doesn't pin peak-batch cache memory between traffic bursts.

Paged mode (``engine.uses_page_table``, DESIGN.md §9): a
``PagedCacheManager`` owns per-slot page tables over a shared page pool.
Admission reserves each request's worst-case page count (so mid-decode
growth never deadlocks), credits prefix-shared pages (identical leading
prompt pages skip replay entirely), and ``step()`` threads the table
into the jitted decode.  Exhaustion surfaces as ``can_admit() == False``
— the serving loop then leaves requests queued and its admission queue
backs up into 429s, never a mid-decode failure.

**One scheduler serves one family.**  Continuous and batch-drain
requests cannot interleave inside one queue: a batch-drain wave holds
every lane until its slowest request finishes, so a mixed queue would
silently serialize the continuous traffic behind it.  ``submit``
therefore rejects any request whose declared ``family`` differs from
the engine's — run one ``Scheduler`` (and one engine) per family and
split traffic upstream.

Per-request sampling: ``Request`` carries optional ``temperature`` /
``top_p`` / ``seed`` overriding the scheduler-global ``SamplingConfig``
(``top_k`` stays global).  Each slot owns an independent PRNG chain
seeded from the request (``seed`` if given, else the scheduler seed
folded with the rid), advanced only on emission steps — so a request's
tokens are bit-identical to a solo ``Engine.generate(PRNGKey(seed),
...)`` run with the same params, no matter which other requests share
the batch.  The chains live on the device, one (B, 2) key array written
at admission, and advance inside the step's one compiled sampling
program (``sampling.sample_step``); the host only fills and uploads
numpy inputs, and blocks once a step, reading the tokens back.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.runtime import sampling
from repro.runtime.serve import Engine


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray             # (L,) int32
    max_new_tokens: int = 16
    # per-request sampling overrides (None -> the scheduler's global
    # SamplingConfig value); ``seed`` pins this request's sample stream
    # so its output is reproducible independent of batch composition
    temperature: Optional[float] = None
    top_p: Optional[float] = None
    seed: Optional[int] = None
    # declared model family; None means "the engine's own".  Anything
    # else is rejected at submit (one scheduler per family — see module
    # docstring).
    family: Optional[str] = None
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """What one decode step did to one request (continuous mode)."""

    rid: int
    token: Optional[int]           # None for a pure retire (cancel)
    final: bool                    # request left the engine this step
    cancelled: bool = False


@dataclasses.dataclass
class _Slot:
    """One live lane of the fixed-shape decode program."""

    req: Request
    fed: int = 0                   # tokens fed so far == this slot's pos
    last: int = 0                  # last sampled token (next input when
                                   # the prompt is exhausted)


class Scheduler:
    def __init__(self, engine: Engine, *, max_batch: int = 8,
                 prompt_budget: int = 128,
                 scfg: sampling.SamplingConfig = sampling.SamplingConfig(),
                 seed: int = 0, n_pages: Optional[int] = None):
        self.engine = engine
        self.max_batch = max_batch
        self.prompt_budget = prompt_budget
        self.scfg = scfg
        self.seed = seed
        self.queue: deque[Request] = deque()
        self.finished: dict[int, Request] = {}
        self.rng = jax.random.PRNGKey(seed)   # batch-drain global chain
        #: (step, rid) log of admissions — step > 0 entries are requests
        #: admitted into retired slots *between* decode steps.
        self.admissions: list[tuple[int, int]] = []
        # continuous-mode engine state, built lazily on the first step
        # and releasable between traffic bursts (release_cache)
        self._cache = None
        self._slots: list[Optional[_Slot]] = []
        self._dirty: list[bool] = []   # slot lanes a retired request used
        self._step_no = 0
        #: lane-steps that fed a prompt token whose logits were discarded
        #: (prompt replay), lane-steps that emitted a token, and those of
        #: the emitting ones whose token was drawn (temperature > 0)
        self.lanes_replay = 0
        self.lanes_emit = 0
        self.lanes_drawn = 0
        # per-slot sampling state: the PRNG keys on the device, the
        # parameters on the host (rows written at admission) with their
        # uploaded copy (None until the next step uploads them)
        mesh = engine.ctx.mesh
        self._replicated = None if mesh is None else NamedSharding(mesh,
                                                                   P())
        self._sample = sampling.compile_sample_step(self._replicated)
        self._keys = None
        self._params = None
        self._params_dev = None
        self._cache_builds = 0
        self.manager = None
        if engine.uses_page_table:
            from repro.cache import PagedCacheManager

            self.manager = PagedCacheManager(
                engine.policy.kv, max_batch=max_batch,
                max_seq=engine.max_seq, n_pages=n_pages)
        self._recurrent = engine.model.cfg.family in ("hybrid", "ssm")

    def submit(self, req: Request):
        family = self.engine.model.cfg.family
        if req.family is not None and req.family != family:
            raise ValueError(
                f"request {req.rid} is for family '{req.family}' but this "
                f"scheduler's engine serves '{family}': continuous and "
                "batch-drain families cannot share a queue (a batch-drain "
                "wave would hold every lane until its slowest request "
                "finishes, silently serializing the continuous traffic "
                "behind it) — run one Scheduler per family")
        if req.prompt.size > self.prompt_budget:
            raise ValueError(
                f"prompt {req.prompt.size} > budget {self.prompt_budget}")
        if req.prompt.size + req.max_new_tokens > self.engine.max_seq:
            raise ValueError(
                f"prompt {req.prompt.size} + max_new {req.max_new_tokens} "
                f"> engine max_seq {self.engine.max_seq}")
        if self.manager is not None:
            worst = self.manager.pages_needed(req.prompt.size,
                                              req.max_new_tokens)
            if worst > self.manager.n_pages:
                raise ValueError(
                    f"request {req.rid} needs {worst} pages worst-case but "
                    f"the pool only has {self.manager.n_pages} — it can "
                    "never be admitted")
        self.queue.append(req)

    def can_admit(self, req: Request) -> bool:
        """Would ``step()`` admit this request right now (given a free
        slot)?  Always true for dense caches; in paged mode the request's
        worst-case page reservation must fit the pool next to everything
        live or already queued."""
        if self.manager is None:
            return True
        pending = sum(self.manager.pages_needed(r.prompt.size,
                                                r.max_new_tokens)
                      for r in self.queue)
        return self.manager.can_admit(req.prompt.size, req.max_new_tokens,
                                      pending_pages=pending)

    def cancel(self, rid: int) -> bool:
        """Retire a request: a queued one is dropped immediately, a live
        one at the next step boundary (its slot then frees for
        admission).  Returns False for unknown/already-finished rids."""
        for req in self.queue:
            if req.rid == rid and not req.cancelled:
                req.cancelled = True
                return True
        for slot in self._slots:
            if (slot is not None and slot.req.rid == rid
                    and not slot.req.cancelled):
                slot.req.cancelled = True
                return True
        return False

    @property
    def live_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.live_slots > 0

    def run(self) -> dict[int, Request]:
        """Drain the queue; returns {rid: finished request}."""
        if self.engine.supports_continuous:
            while self.has_work:
                self.step()
            return self.finished
        while self.queue:
            batch = [self.queue.popleft()
                     for _ in range(min(self.max_batch, len(self.queue)))]
            self._run_batch(batch)
        return self.finished

    # ------------------------------------------------------------------
    # continuous mode: admit into retired slots between decode steps
    # ------------------------------------------------------------------

    def _request_key(self, req: Request) -> jax.Array:
        if req.seed is not None:
            return jax.random.PRNGKey(req.seed)
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), req.rid)

    def step(self) -> list[StepEvent]:
        """One admission + decode step over the fixed-shape program.

        Returns a ``StepEvent`` per request that emitted a token or was
        retired this step.  Safe to call with an empty engine (returns
        ``[]`` without touching the device).

        Each phase runs inside a profiler span named ``serve.<phase>``
        that carries the step number as ``step``; the spans are flat, so
        a trace attributes device idle time to the phase the host was in.
        """
        if not self.engine.supports_continuous:
            raise RuntimeError(
                f"family '{self.engine.model.cfg.family}' does not support "
                "token-granularity stepping (batch-drain only) — use run()")
        n = self._step_no
        with TraceAnnotation("serve.admit", step=n):
            events = self._admit()
        if not any(self._slots):
            return events
        with TraceAnnotation("serve.prepare", step=n):
            args, advance, params = self._prepare()
        with TraceAnnotation("serve.decode", step=n):
            logits, self._cache = self.engine._decode(
                self.engine.params, self._cache, *args)
        with TraceAnnotation("serve.sample", step=n):
            sampled, self._keys = self._sample(self._keys, advance, logits,
                                               *params)
        with TraceAnnotation("serve.readback", step=n):
            sampled = np.asarray(sampled)
        with TraceAnnotation("serve.update", step=n):
            self._update(sampled, events)
        return events

    def _admit(self) -> list[StepEvent]:
        """Build the cache on first use, retire cancelled requests, and
        admit queued ones into free slots."""
        b = self.max_batch
        if self._cache is None:
            if self.manager is not None:
                # pool_pages = n_pages + 1: the extra scratch page is
                # where idle lanes' dummy scatters land (manager docs)
                self._cache = self.engine.init_paged_cache(
                    b, self.manager.pool_pages)
                from repro.cache import paged as paged_pool

                pool = self._cache if "k" in self._cache \
                    else self._cache["self"]
                (self.manager.page_bytes,
                 self.manager.page_bytes_fp) = paged_pool.pool_page_bytes(
                     pool, self.manager.pool_pages)
            else:
                self._cache = self.engine.init_cache(b)
            self._slots = [None] * b
            self._dirty = [False] * b
            self._keys = jax.device_put(np.zeros((b, 2), np.uint32),
                                        self._replicated)
            # temperature, top-p, top-k (0 disables) per slot
            self._params = (np.zeros((b,), np.float32),
                            np.ones((b,), np.float32),
                            np.zeros((b,), np.int32))
            self._params_dev = None
            self._cache_builds += 1
        slots = self._slots
        events: list[StepEvent] = []

        # cancellation: purge queued + retire live cancelled requests at
        # the step boundary, freeing their slots for admission below
        if any(r.cancelled for r in self.queue):
            kept: deque[Request] = deque()
            for req in self.queue:
                if req.cancelled:
                    req.done = True
                    self.finished[req.rid] = req
                    events.append(StepEvent(req.rid, None, True,
                                            cancelled=True))
                else:
                    kept.append(req)
            self.queue = kept
        for i in range(b):
            if slots[i] is not None and slots[i].req.cancelled:
                req = slots[i].req
                req.done = True
                self.finished[req.rid] = req
                events.append(StepEvent(req.rid, None, True,
                                        cancelled=True))
                self._retire_slot(i)

        # admission: every retired (or never-used) slot takes the next
        # queued request NOW — between decode steps, not after a wave.
        # Paged mode additionally requires the head-of-queue's worst-case
        # page reservation to fit; the queue stays FIFO (no skipping), so
        # a too-big head waits rather than being starved by later
        # requests.
        admitted = []
        for i in range(b):
            if slots[i] is None and self.queue:
                req = self.queue[0]
                fed0 = 0
                if self.manager is not None:
                    if not self.manager.can_admit(req.prompt.size,
                                                  req.max_new_tokens):
                        break
                    fed0 = self.manager.admit(i, req.prompt,
                                              req.max_new_tokens)
                elif self._recurrent and self._dirty[i]:
                    # recurrent state has no position mask to hide the
                    # previous occupant — zero the lane (== fresh cache)
                    self._cache = self.engine.reset_slot(self._cache, i)
                    self._dirty[i] = False
                self.queue.popleft()
                slots[i] = _Slot(req=req, fed=fed0)
                admitted.append(i)
                self.admissions.append((self._step_no, req.rid))
        if admitted:
            self._admit_sampling(admitted)
        return events

    def _admit_sampling(self, admitted: list[int]):
        """Write the admitted slots' request keys into the device key
        array (read back whole: the last step's readback already waited
        for it) and their sampling parameters into the host rows."""
        keys = np.array(self._keys)
        temperature, top_p, top_k = self._params
        for i in admitted:
            req = self._slots[i].req
            keys[i] = np.asarray(self._request_key(req))
            temperature[i] = (self.scfg.temperature if req.temperature is None
                              else req.temperature)
            p = self.scfg.top_p if req.top_p is None else req.top_p
            top_p[i] = 1.0 if p is None else p
            top_k[i] = 0 if self.scfg.top_k is None else self.scfg.top_k
        self._keys = jax.device_put(keys, self._replicated)
        self._params_dev = None

    def _prepare(self):
        """The step's inputs, filled on the host and uploaded, with no
        other device work: ``(decode args after params and cache; the
        sampler's (B,) advance mask; its temperature, top-p and top-k)``.
        """
        slots = self._slots
        b = self.max_batch
        tokens = np.zeros((b,), np.int32)
        pos = np.zeros((b,), np.int32)
        advance = np.zeros((b,), bool)
        for i, s in enumerate(slots):
            if s is None:
                continue
            plen = s.req.prompt.size
            tokens[i] = (s.req.prompt[s.fed] if s.fed < plen else s.last)
            pos[i] = s.fed
            # the chain mirrors Engine.generate exactly: the first
            # emission samples with the request key itself, every later
            # one splits first — prompt replay never advances it
            advance[i] = bool(s.req.output)

        args = [jnp.asarray(tokens), jnp.asarray(pos)]
        if self.manager is not None:
            for i, s in enumerate(slots):
                if s is not None:
                    self.manager.ensure(i, s.fed)   # page for this scatter
            args.append(jnp.asarray(self.manager.table()))
        if self._params_dev is None:
            self._params_dev = tuple(jnp.asarray(a) for a in self._params)
        return args, jnp.asarray(advance), self._params_dev

    def _update(self, sampled: np.ndarray, events: list[StepEvent]):
        """Advance every live slot past the token it fed, record what
        it emitted, retire finished requests and count the lane-steps."""
        replay = emit = drawn = 0
        temperature = self._params[0]
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            s.fed += 1
            if self.manager is not None:
                # owned prompt pages now fully written become shareable
                self.manager.advance(i, s.fed)
            if s.fed >= s.req.prompt.size:
                # this step consumed the prompt's last token (or a
                # generated one): its logits yield the next token
                emit += 1
                drawn += int(temperature[i] > 0)
                s.last = int(sampled[i])
                s.req.output.append(s.last)
                final = len(s.req.output) >= s.req.max_new_tokens
                events.append(StepEvent(s.req.rid, s.last, final))
                if final:
                    s.req.done = True
                    self.finished[s.req.rid] = s.req
                    self._retire_slot(i)  # retired: refill next step
            else:
                replay += 1
        self.lanes_replay += replay
        self.lanes_emit += emit
        self.lanes_drawn += drawn
        self._step_no += 1

    def _retire_slot(self, i: int):
        """Free slot ``i``'s lane: paged mode returns its pages (shared
        complete prefix pages park in the allocator's LRU), recurrent
        mode marks the lane dirty so the next occupant resets it."""
        self._slots[i] = None
        self._dirty[i] = True
        if self.manager is not None:
            self.manager.release(i)

    def release_cache(self) -> bool:
        """Drop the decode cache while the engine is idle, so a
        long-lived serving loop doesn't pin peak-batch cache memory
        between traffic bursts.  The paged manager's prefix LRU goes
        with it (its pages index into the freed pool).  No-op (False)
        while any request is live or queued; the next ``step()``
        rebuilds the cache lazily."""
        if self.live_slots or self.queue or self._cache is None:
            return False
        if self.manager is not None:
            self.manager.reset()
        self._cache = None
        self._slots = []
        self._dirty = []
        self._keys = None
        return True

    def cache_stats(self) -> dict:
        """Cache telemetry for the stats endpoint (DESIGN.md §9)."""
        out: dict = {
            "allocated": self._cache is not None,
            "builds": self._cache_builds,
        }
        if self.manager is None:
            out["spec"] = "dense"
            if self._cache is not None:
                out["bytes"] = {"pool": int(sum(
                    leaf.nbytes for leaf in jax.tree_util.tree_leaves(
                        self._cache)))}
            return out
        out.update(self.manager.stats())
        out["per_request_pages"] = {
            s.req.rid: self.manager.slot_pages(i)
            for i, s in enumerate(self._slots) if s is not None}
        return out

    # ------------------------------------------------------------------
    # legacy batch-drain mode (families needing batch-global prefill)
    # ------------------------------------------------------------------

    def _run_batch(self, batch: list[Request]):
        b = len(batch)
        s = self.prompt_budget
        cfg = self.engine.model.cfg
        tokens = np.zeros((b, s), np.int32)
        plen = np.zeros((b,), np.int32)
        for i, r in enumerate(batch):
            tokens[i, :r.prompt.size] = r.prompt
            plen[i] = r.prompt.size

        inputs = {"tokens": jnp.asarray(tokens)}
        if cfg.family == "audio":
            inputs["frames"] = jnp.zeros(
                (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            inputs["patches"] = jnp.zeros(
                (b, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)

        max_new = max(r.max_new_tokens for r in batch)
        self.rng, sub = jax.random.split(self.rng)
        out = self.engine.generate(sub, inputs, plen,
                                   max_new_tokens=max_new, scfg=self.scfg)
        out = np.asarray(out)
        for i, r in enumerate(batch):
            r.output = out[i, :r.max_new_tokens].tolist()
            r.done = True
            self.finished[r.rid] = r
