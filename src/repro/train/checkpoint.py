"""npz-based checkpointing (no orbax dependency).

Pytrees are flattened to ``path/sep/arated/keys`` -> arrays.  Two restore
paths:

* ``restore(path, template)`` — rebuild into the structure of a template
  pytree (shapes must match); the historical training-loop path.
* ``load(path)`` — template-free: ``save`` embeds a JSON schema of the
  tree (dict nesting, ``PlannedPair``/``QuantizedLinear`` static fields,
  ``None`` markers) under the reserved ``__tree__`` key, so quantized
  deployment plans — packed uint32 weights, perms, scales, and the static
  scheme/group_size/kind fields — round-trip without re-running any
  quantization.  This is what ``plan/artifact.py`` serves from.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

_SEP = "||"
_TREE_KEY = "__tree__"
_SCHEMA_VERSION = 1


def _flatten(tree: Any) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_path_str(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def flatten_keys(tree: Any) -> dict[str, Any]:
    """Public ``{checkpoint key: leaf}`` view of a pytree (leaves NOT
    converted to numpy) — the key naming ``save``/``load`` use, so callers
    (the plan artifact's shard manifest) can address leaves stably."""
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[_SEP.join(_path_str(p) for p in path)] = leaf
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


# ---------------------------------------------------------------------------
# tree schema (template-free load)
# ---------------------------------------------------------------------------

def _schema(node: Any) -> dict:
    """JSON-serializable structure descriptor for the trees this repo
    checkpoints: nested dicts, the quantized-plan dataclasses, arrays."""
    from repro.core.quantization import QuantizedLinear
    from repro.core.reorder import PlannedPair

    if node is None:
        return {"t": "none"}
    if isinstance(node, QuantizedLinear):
        return {"t": "qlinear", "group_size": int(node.group_size),
                "kind": node.kind,
                "fields": {f: _schema(getattr(node, f))
                           for f in ("qweight", "scales", "zeros", "g_idx")}}
    if isinstance(node, PlannedPair):
        return {"t": "pair", "scheme": node.scheme,
                "fields": {f: _schema(getattr(node, f))
                           for f in ("up", "gate", "down", "p1_up",
                                     "p1_gate", "p2")}}
    if isinstance(node, dict):
        return {"t": "dict", "keys": {str(k): _schema(v)
                                      for k, v in node.items()}}
    if isinstance(node, (list, tuple)):
        return {"t": "list" if isinstance(node, list) else "tuple",
                "items": [_schema(v) for v in node]}
    arr = np.asarray(node)
    return {"t": "array", "dtype": str(arr.dtype), "shape": list(arr.shape)}


def _from_schema(schema: dict, leaves: dict[str, np.ndarray],
                 prefix: tuple[str, ...] = (), asarray=jnp.asarray) -> Any:
    from repro.core.quantization import QuantizedLinear
    from repro.core.reorder import PlannedPair

    t = schema["t"]
    if t == "none":
        return None
    if t == "qlinear":
        f = {k: _from_schema(v, leaves, prefix + (k,), asarray)
             for k, v in schema["fields"].items()}
        return QuantizedLinear(group_size=schema["group_size"],
                               kind=schema["kind"], **f)
    if t == "pair":
        f = {k: _from_schema(v, leaves, prefix + (k,), asarray)
             for k, v in schema["fields"].items()}
        return PlannedPair(scheme=schema["scheme"], **f)
    if t == "dict":
        return {k: _from_schema(v, leaves, prefix + (k,), asarray)
                for k, v in schema["keys"].items()}
    if t in ("list", "tuple"):
        items = [_from_schema(v, leaves, prefix + (str(i),), asarray)
                 for i, v in enumerate(schema["items"])]
        return items if t == "list" else tuple(items)
    key = _SEP.join(prefix)
    if key not in leaves:
        raise KeyError(f"checkpoint missing leaf {key}")
    return asarray(_typed(leaves[key], schema["dtype"]), dtype=schema["dtype"])


def _typed(arr: np.ndarray, dtype) -> np.ndarray:
    """``.npz`` keeps a leaf of an ml_dtypes type (bfloat16) as raw bytes
    (``V2``): view those bytes in the saved dtype again."""
    return arr.view(jnp.dtype(dtype)) if arr.dtype.kind == "V" else arr


def save(path: str, tree: Any, *, step: int | None = None) -> str:
    """Save pytree to ``path`` (.npz).  Returns the file written."""
    if step is not None:
        root, ext = os.path.splitext(path)
        path = f"{root}_step{step:08d}{ext or '.npz'}"
    if not path.endswith(".npz"):
        path += ".npz"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    flat = _flatten(tree)
    if _TREE_KEY in flat:
        raise ValueError(f"pytree key collides with reserved {_TREE_KEY!r}")
    meta = json.dumps({"version": _SCHEMA_VERSION, "tree": _schema(tree)})
    np.savez(path, **flat, **{_TREE_KEY: np.asarray(meta)})
    return path


def load(path: str, *, host: bool = False) -> Any:
    """Template-free restore: rebuild the exact saved pytree — including
    quantized-plan statics (scheme / group_size / kind) — from the schema
    ``save`` embedded.  Raises on checkpoints written before the schema
    existed (use ``restore`` with a template for those).

    ``host=True`` leaves the leaves as numpy arrays in host memory, for a
    caller that places each one itself; by default they are ``jax.Array``
    leaves on the default device."""
    with np.load(path) as data:
        if _TREE_KEY not in data:
            raise ValueError(
                f"checkpoint {path} has no embedded tree schema; "
                "restore(path, template) is required for legacy files")
        meta = json.loads(str(data[_TREE_KEY][()]))
        if meta["version"] != _SCHEMA_VERSION:
            raise ValueError(
                f"checkpoint {path} schema v{meta['version']} != "
                f"supported v{_SCHEMA_VERSION}")
        leaves = {k: data[k] for k in data.files if k != _TREE_KEY}
    return _from_schema(meta["tree"], leaves,
                        asarray=np.asarray if host else jnp.asarray)


def restore(path: str, template: Any) -> Any:
    """Restore into the structure of ``template`` (shapes must match)."""
    with np.load(path) as data:
        leaves_t, treedef = jax.tree_util.tree_flatten_with_path(template)
        out = []
        for kpath, leaf in leaves_t:
            key = _SEP.join(_path_str(p) for p in kpath)
            if key not in data:
                raise KeyError(f"checkpoint {path} missing {key}")
            arr = data[key]
            if tuple(arr.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"{key}: checkpoint shape {arr.shape} != template "
                    f"{leaf.shape}")
            out.append(jnp.asarray(_typed(arr, leaf.dtype),
                                   dtype=leaf.dtype))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), out)


def latest(dirpath: str, prefix: str) -> str | None:
    """Newest ``<prefix>_stepNNNNNNNN.npz`` in ``dirpath``."""
    if not os.path.isdir(dirpath):
        return None
    pat = re.compile(re.escape(prefix) + r"_step(\d+)\.npz$")
    best, best_step = None, -1
    for f in os.listdir(dirpath):
        m = pat.match(f)
        if m and int(m.group(1)) > best_step:
            best, best_step = os.path.join(dirpath, f), int(m.group(1))
    return best
