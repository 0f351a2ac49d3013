"""Seeded weights of a dense decoder, in the program's parameter layout.

The same function feeds the program (which quantizes and lays the MLP
weights out itself) and the plain reference, so the reference needs
nothing the program made.  The MLP weights lie on an int4 grid: 16
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
    """Embedding, LM head and final norm."""
    kt = jax.random.fold_in(key, 0)
    ke, kh, kn = jax.random.split(kt, 3)
    d, v = conf["hidden_size"], conf["vocab_size"]
    emb = _normal(ke, (v, d), d ** -0.5)
    head = emb.T if conf["tie_word_embeddings"] else _normal(kh, (d, v),
                                                            d ** -0.5)
    return {"embed": {"embedding": emb, "lm_head": head},
            "final_norm": {"scale": _norm_scale(kn, (d,))}}


def layer(conf: dict, key, index) -> dict:
    """Layer ``index`` (may be traced): norms, attention, raw MLP."""
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                            index), 10)
    d, ff, hd = conf["hidden_size"], conf["intermediate_size"], \
        conf["head_dim"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    attn = {"wq": _normal(k[0], (d, q), d ** -0.5),
            "wk": _normal(k[1], (d, kv), d ** -0.5),
            "wv": _normal(k[2], (d, kv), d ** -0.5),
            "wo": _normal(k[3], (q, d), q ** -0.5)}
    if conf["qk_norm"]:
        attn["q_norm"] = _norm_scale(k[4], (hd,))
        attn["k_norm"] = _norm_scale(k[5], (hd,))
    return {"ln1": {"scale": _norm_scale(k[6], (d,))},
            "attn": attn,
            "ln2": {"scale": _norm_scale(k[7], (d,))},
            "mlp": {"w_up": int4_grid(k[8], (d, ff)),
                    "w_gate": int4_grid(jax.random.fold_in(k[8], 1), (d, ff)),
                    "w_down": int4_grid(k[9], (ff, d))}}
