"""Atomic, reshardable checkpointing.

Layout: ``<dir>/step_<k>/`` holding one ``.npy`` per pytree leaf (path-
encoded filenames) plus ``meta.json`` (step, mesh shape, config name,
tree structure).  Writes go to ``step_<k>.tmp`` and are renamed only
after fsync — a crash mid-write never corrupts the latest checkpoint.

Restore is *elastic*: arrays are loaded host-side and ``device_put``
with whatever sharding the new mesh dictates, so a run checkpointed on
16x16 restarts cleanly on 4x4 (or on 1 CPU in tests).  At real scale
the same interface would write per-shard files (Orbax/OCDBT style); the
single-file path keeps the repo self-contained.
"""
from __future__ import annotations

import json
import os
import shutil
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(_key_str(k) for k in path)
        flat[key] = leaf
    return flat


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return f"#{k.idx}"
    return str(k)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------
    def save(self, step: int, tree, extra_meta: Optional[dict] = None):
        final = os.path.join(self.dir, f"step_{step:08d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten(tree)
        manifest = {}
        for key, leaf in flat.items():
            arr = np.asarray(jax.device_get(leaf))
            fname = key.replace("/", "__") + ".npy"
            np.save(os.path.join(tmp, fname), arr)
            manifest[key] = {"file": fname, "shape": list(arr.shape),
                             "dtype": str(arr.dtype)}
        meta = {"step": step, "manifest": manifest}
        meta.update(extra_meta or {})
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)                      # atomic publish
        self._gc()
        return final

    def _gc(self):
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)

    # ------------------------------------------------------------------
    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and not name.endswith(".tmp"):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, template, step: Optional[int] = None,
                shardings=None):
        """Load into the structure of ``template`` (a pytree of arrays or
        ShapeDtypeStructs).  ``shardings``: optional matching pytree of
        NamedSharding for elastic resharding onto a new mesh."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        flat_t = _flatten(template)
        flat_s = _flatten(shardings) if shardings is not None else {}
        loaded = {}
        for key, leaf in flat_t.items():
            info = meta["manifest"][key]
            arr = np.load(os.path.join(d, info["file"]))
            want_dtype = np.dtype(jax.numpy.dtype(leaf.dtype))
            if arr.dtype != want_dtype:
                arr = arr.astype(want_dtype)
            if key in flat_s and flat_s[key] is not None:
                loaded[key] = jax.device_put(arr, flat_s[key])
            else:
                loaded[key] = jax.numpy.asarray(arr)
        # rebuild tree
        paths, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = []
        for path, _ in paths:
            key = "/".join(_key_str(k) for k in path)
            leaves.append(loaded[key])
        return jax.tree_util.tree_unflatten(treedef, leaves), meta

    def meta(self, step: Optional[int] = None) -> dict:
        if step is None:
            step = self.latest_step()
        with open(os.path.join(self.dir, f"step_{step:08d}",
                               "meta.json")) as f:
            return json.load(f)
