"""Quickstart: the paper's three deployment schemes on one MLP pair.

Shows the whole story in ~80 lines:
  1. quantize a (gate/up -> down) pair with act_order (GPTQ Eq. 3),
  2. describe each deployment as one ``ExecutionPolicy`` (scheme, kernel
     backend, dtypes, TP collective spec),
  3. run ``PlannedPair.forward(x, policy, mesh=...)`` — the canonical
     runtime entry point — and verify all three compute the same function,
  4. count the collectives each one needs under tensor parallelism,
  5. swap the trailing collective for a *quantized* one
     (``collective="quant-int8"``) and compare wire bytes and error.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import reorder
from repro.core.policy import ExecutionPolicy
from repro.launch import roofline
from repro.launch.mesh import make_mesh

K1, N1, N2, M, TP = 512, 1024, 512, 8, 4

rng = jax.random.PRNGKey(0)
r = jax.random.split(rng, 4)
w_gate = jax.random.normal(r[0], (K1, N1)) * 0.02
w_up = jax.random.normal(r[1], (K1, N1)) * 0.02
w_down = jax.random.normal(r[2], (N1, N2)) * 0.02
x = jax.random.normal(r[3], (M, K1))

print(f"MLP pair: ({K1}, {N1}) -> ({N1}, {N2}), batch {M}, TP={TP}\n")

mesh = make_mesh((len(jax.devices()) // TP, TP), ("data", "model"))
outs = {}
for scheme in ("naive-actorder", "exllama", "tp-aware"):
    # offline: quantize int4 (group 128, act_order) + lay out for `scheme`
    pp = reorder.plan_pair(w_up, w_down, w_gate=w_gate, scheme=scheme,
                           group_size_up=128, group_size_down=128, rng=rng)
    # the deployment plan as one object: layout scheme + kernel backend
    # (auto: pallas on TPU for ordered layouts, jnp here) + collective
    policy = ExecutionPolicy.auto(scheme)
    # online: tensor-parallel forward with explicit collectives
    with mesh:
        fn = lambda xx, p=pp, pol=policy: p.forward(
            xx, pol, mesh, activation="silu")
        y = jax.jit(fn)(x)
        hlo = jax.jit(fn).lower(x).compile().as_text()
    outs[scheme] = np.asarray(y)
    coll = roofline.parse_collective_bytes(hlo, chips=mesh.devices.size)
    print(f"{scheme:15s} collectives: "
          + ", ".join(f"{k}={v}" for k, v in coll["counts"].items() if v)
          + f"  ({roofline.fmt_bytes(coll['total_per_device'])}/device)")

print("\nmax |tp-aware - naive| =",
      np.abs(outs["tp-aware"] - outs["naive-actorder"]).max(),
      "(same arithmetic, different layout/communication)")
print("max |exllama  - naive| =",
      np.abs(outs["exllama"] - outs["naive-actorder"]).max())

# --- communication compression: a quantized trailing collective -----------
# The collective is a CollectiveSpec on the policy, dispatched by the
# comm/dispatch registry — swapping the f32 AllReduce for a blockwise-int8
# one is a one-field change, no model code involved.
from repro.comm import CollectiveSpec

pp = reorder.plan_pair(w_up, w_down, w_gate=w_gate, scheme="tp-aware",
                       group_size_up=128, group_size_down=128, rng=rng)
print(f"\ntrailing collective on the ({M}, {N2}) partials at TP={TP}:")
for shorthand in ("psum", "cast:bfloat16", "quant-int8"):
    spec = CollectiveSpec.parse(shorthand)
    policy = ExecutionPolicy.auto("tp-aware", collective=spec)
    with mesh:
        y = np.asarray(jax.jit(
            lambda xx: pp.forward(xx, policy, mesh, activation="silu"))(x))
    err = np.abs(y - outs["tp-aware"]).max() / np.abs(outs["tp-aware"]).max()
    print(f"  {shorthand:14s} "
          f"{roofline.fmt_bytes(spec.bytes_on_wire((M, N2), TP)):>8s}/device"
          f"  rel_err={err:.1e}")
