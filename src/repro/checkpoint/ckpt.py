"""Fault-tolerant checkpointing (CKP/MDR of paper §V + elastic restore).

Layout: <dir>/step_<N>/  arrays.npz  (flattened pytree leaves)
                         meta.json   (step, treedef repr, leaf paths, extras)
Writes are atomic (tmp dir + rename); ``latest_step`` skips partial writes,
so a job killed mid-checkpoint restarts from the previous complete one.
``restore_pytree`` accepts a target MeshSpec: leaves are re-placed under the
*new* mesh's partition specs — this is the elastic re-mesh path (restart on a
different pod count after node failure).
"""
from __future__ import annotations

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.distributed.sharding import MeshSpec, param_specs, path_str


def _flatten(tree):
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path) for path, _ in leaves_with_paths]
    leaves = [leaf for _, leaf in leaves_with_paths]
    return paths, leaves


def save_pytree(tree, directory: str, step: int, extras: dict | None = None):
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f".tmp_step_{step}_{os.getpid()}")
    final = os.path.join(directory, f"step_{step}")
    os.makedirs(tmp, exist_ok=True)
    paths, leaves = _flatten(tree)
    arrays = {}
    dtypes = []
    for i, x in enumerate(leaves):
        arr = np.asarray(jax.device_get(x))
        dtypes.append(str(arr.dtype))
        if arr.dtype.name == "bfloat16":       # npz has no bf16: store bits
            arr = arr.view(np.uint16)
        arrays[f"a{i}"] = arr
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    meta = {"step": step, "paths": paths, "dtypes": dtypes,
            "extras": extras or {}, "wall_time": time.time()}
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = [int(d.split("_", 1)[1]) for d in os.listdir(directory)
             if d.startswith("step_")]
    return max(steps) if steps else None


def restore_pytree(template, directory: str, step: int | None = None,
                   ms: MeshSpec | None = None, specs=None):
    """Restore into the structure of ``template`` (pytree of arrays or
    ShapeDtypeStruct). With ``ms`` given, leaves are placed under that mesh's
    param specs (elastic re-mesh restore)."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    d = os.path.join(directory, f"step_{step}")
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    data = np.load(os.path.join(d, "arrays.npz"))
    leaves = []
    for i, dt in enumerate(meta["dtypes"]):
        arr = data[f"a{i}"]
        if dt == "bfloat16":
            import ml_dtypes
            arr = arr.view(ml_dtypes.bfloat16)
        leaves.append(arr)
    treedef = jax.tree_util.tree_structure(template)
    tmpl_leaves = jax.tree_util.tree_leaves(template)
    assert len(tmpl_leaves) == len(leaves), \
        f"checkpoint has {len(leaves)} leaves, template {len(tmpl_leaves)}"
    out = []
    if ms is not None and specs is None:
        specs_tree = param_specs(template, ms)
        spec_leaves = jax.tree_util.tree_leaves(
            specs_tree, is_leaf=lambda x: not isinstance(x, dict))
    elif specs is not None:
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: not isinstance(x, dict))
    else:
        spec_leaves = [None] * len(leaves)
    for arr, tmpl, spec in zip(leaves, tmpl_leaves, spec_leaves):
        if ms is not None and spec is not None:
            # host -> shards directly: never the whole leaf on one device
            x = jax.device_put(np.asarray(arr, dtype=tmpl.dtype),
                               NamedSharding(ms.mesh, spec))
        else:
            x = jnp.asarray(arr, dtype=tmpl.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out), meta


class CheckpointManager:
    """Periodic checkpointing with retention (fault-tolerance substrate)."""

    def __init__(self, directory: str, every: int = 100, keep: int = 3):
        self.directory = directory
        self.every = every
        self.keep = keep

    def maybe_save(self, tree, step: int, extras: dict | None = None):
        if self.every <= 0 or step % self.every:
            return None
        path = save_pytree(tree, self.directory, step, extras)
        self._gc()
        return path

    def _gc(self):
        if not os.path.isdir(self.directory):
            return
        steps = sorted(int(d.split("_", 1)[1]) for d in os.listdir(self.directory)
                       if d.startswith("step_"))
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"),
                          ignore_errors=True)

    def restore_latest(self, template, ms: MeshSpec | None = None):
        return restore_pytree(template, self.directory, ms=ms)
