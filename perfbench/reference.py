"""Plain float32 forward of the dense decoder, and its lower-precision
control.

Straight ``jax.numpy`` in float32 on the host CPU: pre-norm GQA attention
with RoPE (and Qwen3's per-head q/k norm) and a SwiGLU MLP, over whole
sequences with a causal mask, one layer at a time so a full-width model
fits beside its activations.  No kernel, no cache, no slots, and nothing
of the program: the weights come from ``weights`` and the MLP weights are
the int4 grid values themselves.

The control computes the same forward with both operands of every matmul
rounded to float8 e4m3's precision: the step below the configurations'
bfloat16 that a later change could be tempted to take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MANTISSA = 3
#: rows of the LM head computed at once (bounds the (rows, vocab) logits)
HEAD_ROWS = 512
#: sequence lengths are padded to a multiple of this (fewer compiles)
SEQ_PAD = 128


def _fp8(t):
    """Round float32 to float8 e4m3's 3 mantissa bits, to nearest even,
    by integer ops on the bits (a compiler may not skip a round trip
    through a narrower float type).  The exponent keeps float32's range,
    as a per-tensor scale would give e4m3."""
    u = jax.lax.bitcast_convert_type(t, jnp.uint32)
    drop = 23 - FP8_MANTISSA
    half = jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & 1)
    u = (u + half) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _mm(spec, a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D); rotate the two halves (RoPE, positions 0..S-1)."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(conf, low, x, w):
    b, s, _ = x.shape
    eps, hd = conf["rms_norm_eps"], conf["head_dim"]
    nh, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    a = w["attn"]
    h = _rms(x, w["ln1"]["scale"], eps)
    q = _mm("bsd,dn->bsn", h, a["wq"], low).reshape(b, s, nh, hd)
    k = _mm("bsd,dn->bsn", h, a["wk"], low).reshape(b, s, nkv, hd)
    v = _mm("bsd,dn->bsn", h, a["wv"], low).reshape(b, s, nkv, hd)
    if conf["qk_norm"]:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = _mm("bshd,bthd->bhst", q, k, low) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("bhst,bthd->bshd", p, v, low).reshape(b, s, nh * hd)
    x = x + _mm("bsn,nd->bsd", o, a["wo"], low)
    m = w["mlp"]
    h = _rms(x, w["ln2"]["scale"], eps)
    g = _mm("bsd,df->bsf", h, m["w_gate"], low)
    u = _mm("bsd,df->bsf", h, m["w_up"], low)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], low)


class Reference:
    """The plain forward for one configuration and weight seed.

    The weights are made where the program's were (the default device)
    and the forward runs on the host's CPU, whose float32 matmuls are
    exact IEEE arithmetic: no precision setting of the accelerator's
    compiler stands between the reference and float32."""

    def __init__(self, conf: dict, key):
        self.conf = conf
        self.key = key
        self.host = jax.devices("cpu")[0]
        self._layer_w = jax.jit(functools.partial(weights.layer, conf))
        self._top = jax.jit(functools.partial(weights.top, conf))
        self._layer = {low: jax.jit(functools.partial(_layer, conf, low))
                       for low in (False, True)}
        self._head = {c: jax.jit(functools.partial(self._head_rows, conf, c))
                      for c in (False, True)}

    def _on_host(self, tree):
        return jax.device_put(jax.device_get(tree), self.host)

    @staticmethod
    def _head_rows(conf, control, rows_ref, rows_low, final_scale, head,
                   served):
        eps = conf["rms_norm_eps"]
        ref = jnp.einsum("rd,dv->rv", _rms(rows_ref, final_scale, eps), head,
                         precision=HIGHEST)
        best = jnp.max(ref, -1)
        gap_served = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if not control:
            return gap_served, gap_served
        xl = _rms(rows_low, final_scale, eps)
        low = _mm("rd,dv->rv", xl, head, True)
        top_low = jnp.argmax(low, -1)
        gap_low = best - jnp.take_along_axis(ref, top_low[:, None], -1)[:, 0]
        return gap_served, gap_low

    def gaps(self, seqs: list, prompt_lens: list, control: bool = False):
        """For each sequence (prompt + served tokens), the gap by which
        each served token's reference logit lies below the reference's
        best at its position; with ``control``, also the gap of the
        token the float8 forward puts first there.  Returns a list of
        ``(gap_served, gap_control | None)`` numpy arrays."""
        b = len(seqs)
        s = -(-max(len(q) for q in seqs) // SEQ_PAD) * SEQ_PAD
        tokens = np.zeros((b, s), np.int32)
        for i, q in enumerate(seqs):
            tokens[i, :len(q)] = q
        top = self._on_host(self._top(self.key))
        x = jnp.take(top["embed"]["embedding"],
                     jax.device_put(tokens, self.host), axis=0)
        xs = {False: x, True: x} if control else {False: x}
        for li in range(self.conf["num_hidden_layers"]):
            # the CPU runs a layer asynchronously: fetch the next layer's
            # weights meanwhile, but wait for the layer before dispatching
            # another, or every layer's weights (0.8 GB each at 8B width)
            # would pile up on the host ahead of the arithmetic
            w = self._on_host(self._layer_w(self.key, li))
            jax.block_until_ready(xs)
            xs = {low: self._layer[low](h, w) for low, h in xs.items()}
            del w
        # rows predicting each served token: positions plen-1 .. len-2
        idx, served, owner = [], [], []
        for i, (q, plen) in enumerate(zip(seqs, prompt_lens)):
            for t in range(plen - 1, len(q) - 1):
                idx.append(i * s + t)
                served.append(q[t + 1])
                owner.append(i)
        n = len(idx)
        pad = -(-n // HEAD_ROWS) * HEAD_ROWS
        idx = np.asarray(idx + [0] * (pad - n), np.int32)
        served = np.asarray(served + [0] * (pad - n), np.int32)
        flat = {low: h.reshape(b * s, -1) for low, h in xs.items()}
        out_s, out_c = [], []
        for r in range(0, pad, HEAD_ROWS):
            sl = jax.device_put(idx[r:r + HEAD_ROWS], self.host)
            rows_ref = flat[False][sl]
            rows_low = flat[True][sl] if control else rows_ref
            gs, gc = self._head[control](
                rows_ref, rows_low, top["final_norm"]["scale"],
                top["embed"]["lm_head"],
                jax.device_put(served[r:r + HEAD_ROWS], self.host))
            out_s.append(np.asarray(gs))
            out_c.append(np.asarray(gc))
        gs = np.concatenate(out_s)[:n]
        gc = np.concatenate(out_c)[:n]
        owner = np.asarray(owner)
        return [(gs[owner == i], gc[owner == i] if control else None)
                for i in range(b)]
