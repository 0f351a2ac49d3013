"""Seeded weights, in the program's parameter layout.

A configuration's family (``families/<family>.py``) makes each layer
from these generators, and the same function feeds the program (which
quantizes and lays the MLP weights out itself) and the plain reference,
so the reference needs nothing the program made.  The MLP weights lie on
an int4 grid: 16
evenly spaced levels with one step per column, zero at level 8 and a
quarter of the codes at each end.  Then any grouping of at least 76 rows
holds both ends of every column but for a chance of 2 * 0.75**76 (about
one group in 1.6e9), so group-wise min/max round-to-nearest at
4 bits gives these values back to the last float32 bit whatever row
order the program's act-order plan draws.

Sixteen levels around a zero at level 8 run from -8 to 7 steps, so a
column's mean is half a step off zero.  Each column takes a random sign:
with one sign for all, a residual stream with a common offset along
every dimension is fed back by gate, up and down in the same direction
and grows by some hundreds a layer at the positions that cross the
threshold and not at the others, which makes the model's greedy answers
repeat one token and tip between precisions at the crossings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import spec

LEVELS = 16
ZERO = 8
#: share of the codes at each end of the grid
END_SHARE = 0.25
#: standard deviation of ``code - ZERO`` under those shares
CODE_STD = 6.02


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's exceed 32 bits)."""
    state = np.random.SeedSequence(int(seed) % 2**64).generate_state(1)
    return jax.random.PRNGKey(int(state[0]) & 0x7FFFFFFF)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _norm_scale(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, 0.9, 1.1)


def int4_grid(key, shape) -> jax.Array:
    """``(K, N)`` float32 weights on the 16-level grid, std ~ 1/sqrt(K),
    each column's levels multiplied by a random sign."""
    ku, ks, kz = jax.random.split(key, 3)
    k, n = shape
    u = jax.random.uniform(ku, shape, jnp.float32)
    mid = 1.0 + jnp.floor((u - END_SHARE) / (1.0 - 2.0 * END_SHARE)
                          * (LEVELS - 2))
    code = jnp.where(u < END_SHARE, 0.0,
                     jnp.where(u >= 1.0 - END_SHARE, LEVELS - 1.0, mid))
    step = jax.random.uniform(ks, (1, n), jnp.float32, 0.75, 1.25) \
        / (CODE_STD * k ** 0.5)
    sign = jnp.where(jax.random.bernoulli(kz, 0.5, (1, n)), 1.0, -1.0)
    return (code - ZERO) * step * sign


def top(conf: dict, key) -> dict:
    """Embedding, LM head and final norm, as the configuration's family
    makes them."""
    return spec.family(conf).top(conf, key)


def layer(conf: dict, key, index) -> dict:
    """Raw layer ``index``, as the configuration's family makes it; may be
    traced where every layer is of one kind."""
    fam = spec.family(conf)
    kinds = fam.layer_kinds(conf)
    kind = kinds[0] if len(set(kinds)) == 1 else kinds[index]
    return fam.layer(conf, key, index, kind)
