"""Token sampling: greedy / temperature / top-k / top-p (pure jnp).

Two entry points share one masking pipeline so their numerics are
bit-identical:

* ``sample(rng, logits, cfg)`` — program-global params, one PRNG key for
  the whole (B, V) batch (``Engine.generate`` and the batch-drain
  scheduler path).
* ``sample_slots(keys, logits, temperature, top_p, top_k)`` — per-slot
  parameter *vectors* with one PRNG key per row, for the continuous
  decode loop where every live slot may carry its own request's
  ``temperature``/``top_p``/``seed``.  A single row of ``sample_slots``
  equals ``sample`` on the (1, V) slice with the same key: the masking
  math is the same code, and ``jax.random.categorical`` draws identical
  gumbel bits for shapes (1, V) and (V,).

The continuous scheduler runs ``sample_step``: ``sample_slots`` with each
slot's PRNG chain advanced beside it, compiled into one program per
decode step by ``compile_sample_step`` (``jit_sample_step`` in a trace),
so the keys stay on the device from step to step.  Compiled, it draws
the same bits as the eager calls.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0     # 0 -> greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None  # nucleus: keep smallest prefix with
                                   # cumulative prob >= top_p (None/1.0
                                   # -> no-op)


def _masked_logits(logits: jax.Array, temperature: jax.Array,
                   top_p: jax.Array, top_k: jax.Array) -> jax.Array:
    """Shared mask pipeline: scale -> top-k -> top-p.  All params are
    per-row vectors (B,); ``top_k == 0`` / ``top_p == 1.0`` disable the
    respective mask; ``temperature <= 0`` rows are scaled by 1 (their
    result is replaced by argmax in the callers)."""
    b, v = logits.shape
    t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / t[:, None]

    # top-k: kth-largest per row via take_along_axis on the sorted copy
    # (k == 0 -> index 0, i.e. the row minimum -> keeps everything).
    srt = jnp.sort(scaled, axis=-1)                       # ascending
    k = jnp.clip(top_k, 0, v)
    kth = jnp.take_along_axis(
        srt, jnp.maximum(v - k, 0)[:, None], axis=-1)     # (B, 1)
    scaled = jnp.where((k > 0)[:, None] & (scaled < kth),
                       -jnp.inf, scaled)

    # top-p over the post-top-k distribution: keep every token whose
    # preceding cumulative mass (descending order) is < top_p; the
    # top-1 token always survives.  top_p == 1.0 keeps every token of
    # nonzero probability, which leaves the categorical unchanged.
    probs = jax.nn.softmax(scaled, axis=-1)
    srt_p = jnp.sort(probs, axis=-1)[:, ::-1]             # descending
    cum = jnp.cumsum(srt_p, axis=-1)
    keep = (cum - srt_p) < top_p[:, None]
    thr = jnp.min(jnp.where(keep, srt_p, jnp.inf), axis=-1)  # (B,)
    return jnp.where(probs < thr[:, None], -jnp.inf, scaled)


def _param_vectors(b: int, cfg: SamplingConfig):
    temperature = jnp.full((b,), cfg.temperature, jnp.float32)
    top_p = jnp.full((b,), 1.0 if cfg.top_p is None else cfg.top_p,
                     jnp.float32)
    top_k = jnp.full((b,), 0 if cfg.top_k is None else cfg.top_k,
                     jnp.int32)
    return temperature, top_p, top_k


def sample(rng, logits: jax.Array, cfg: SamplingConfig) -> jax.Array:
    """logits: (B, V) -> token ids (B,).  One key, program-global cfg."""
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temperature, top_p, top_k = _param_vectors(logits.shape[0], cfg)
    masked = _masked_logits(logits, temperature, top_p, top_k)
    return jax.random.categorical(rng, masked, axis=-1).astype(jnp.int32)


def sample_slots(keys: jax.Array, logits: jax.Array,
                 temperature: jax.Array, top_p: jax.Array,
                 top_k: jax.Array) -> jax.Array:
    """Per-slot sampling for the continuous decode loop.

    ``keys``: (B,) stacked PRNG keys (i.e. shape (B, 2) uint32) — one
    independent stream per slot so a request's tokens do not depend on
    which other requests share the batch; ``temperature``/``top_p``:
    (B,) float32; ``top_k``: (B,) int32 (0 disables).  Rows with
    ``temperature <= 0`` are greedy (no randomness consumed).
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _masked_logits(logits, temperature, top_p, top_k)
    drawn = jax.vmap(
        lambda k, row: jax.random.categorical(k, row))(keys, masked)
    return jnp.where(temperature <= 0.0, greedy,
                     drawn.astype(jnp.int32))


def sample_step(keys: jax.Array, advance: jax.Array, logits: jax.Array,
                temperature: jax.Array, top_p: jax.Array,
                top_k: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One decode step of the per-slot key chain and ``sample_slots``.

    ``keys``: (B, 2) uint32, each slot's current key; ``advance``: (B,)
    bool.  An advancing row does ``key, sub = jax.random.split(key)`` and
    draws with ``sub``; any other row draws with ``key`` and keeps it.
    This is ``Engine.generate``'s chain: a request's first emission draws
    with its key itself, every later one splits first, and steps that
    emit nothing (prompt replay, empty slots) leave the key alone.
    Returns ``(tokens (B,) int32, next keys (B, 2))``.
    """
    split = jax.vmap(jax.random.split)(keys)              # (B, 2, 2)
    adv = advance[:, None]
    draw = jnp.where(adv, split[:, 1], keys)
    nxt = jnp.where(adv, split[:, 0], keys)
    return sample_slots(draw, logits, temperature, top_p, top_k), nxt


def compile_sample_step(out_sharding=None):
    """``sample_step`` as one compiled program that donates ``keys``.

    ``out_sharding``: where the tokens and keys come out; under a mesh,
    replicated on it, so reading the tokens back is one small transfer
    whatever the logits' sharding, and every controller of a
    multi-process mesh can read them.  Every parameter is a traced
    input, so one program serves any mix of greedy, sampled, replaying
    and empty slots.  Like ``Engine``'s step functions, each call wraps
    a function of its own, which traces the module's ``sample_slots``
    as it stands at the program's first call.
    """
    @functools.wraps(sample_step)
    def step(*args):
        return sample_step(*args)

    return jax.jit(step, donate_argnums=0, out_shardings=out_sharding)
