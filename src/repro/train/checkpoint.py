"""Fault-tolerant checkpointing: atomic, async, mesh-independent (elastic).

Format: one directory per step, ``arrays.npz`` keyed by flattened tree paths
+ ``meta.json``.  Arrays are saved as full logical arrays (gathered to host),
so a checkpoint written on one mesh restores onto ANY mesh / device count —
this is the elastic-scaling path: on restart with a different topology the
restore device_puts each array with the new mesh's NamedSharding.

Commit protocol: write to ``<dir>/tmp.<step>``, fsync, atomic rename to
``<dir>/step_<n>`` — a crash mid-save never corrupts the latest checkpoint.
Saves can run on a background thread (``async_save``).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import uuid
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
_SEP = "\x1f"  # unit separator: safe key joiner


def _flatten(tree: PyTree) -> Dict[str, np.ndarray]:
    flat = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in kp)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def save(tree: PyTree, ckpt_dir: str, step: int,
         keep: int = 3, async_save: bool = False,
         extra_meta: Optional[Dict] = None,
         publish: Optional[Callable[[int], None]] = None,
         ) -> Optional[threading.Thread]:
    """Write one checkpoint (see module docstring for the commit protocol).

    ``publish``, if given, is called as ``publish(step)`` after the
    checkpoint directory is durably in place — the serve-while-train
    hook: hand it ``AdapterFeed.notify`` (thread-safe; async saves call
    it from the writer thread) so a live engine streams the new step into
    its adapter bank without polling the directory."""
    os.makedirs(ckpt_dir, exist_ok=True)
    flat = _flatten(tree)   # device_get happens on the caller thread
    meta = {"step": int(step), **(extra_meta or {})}
    # npz stores ml_dtypes arrays (bfloat16, ...) as raw bytes: keep their
    # dtype names so restore can view the bytes as what they were
    raw = {k: a.dtype.name for k, a in flat.items() if a.dtype.kind == "V"}
    if raw:
        meta["raw_dtypes"] = raw

    def _write():
        # unique tmp name: concurrent async+sync saves of the same step
        # must never collide mid-rename
        tmp = os.path.join(ckpt_dir, f"tmp.{step}.{uuid.uuid4().hex[:8]}")
        final = os.path.join(ckpt_dir, f"step_{step:08d}")
        os.makedirs(tmp)
        np.savez(os.path.join(tmp, "arrays.npz"), **flat)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
        dfd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        if os.path.exists(final):
            shutil.rmtree(final, ignore_errors=True)
        try:
            os.rename(tmp, final)
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)  # concurrent writer won
        _gc(ckpt_dir, keep)
        if publish is not None:
            # after the rename (either writer's): the step is durably
            # restorable by the time a subscriber hears about it
            publish(int(step))

    if async_save:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        return t
    _write()
    return None


def _gc(ckpt_dir: str, keep: int):
    steps = sorted(all_steps(ckpt_dir))
    for s in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)


def all_steps(ckpt_dir: str) -> List[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_"):
            try:
                out.append(int(name[5:]))
            except ValueError:
                pass
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore(template: PyTree, ckpt_dir: str, step: Optional[int] = None,
            shardings: Optional[PyTree] = None) -> PyTree:
    """Restore into the structure of ``template``.

    ``shardings`` (same structure) triggers sharded device_put — this is how
    a checkpoint written on mesh A loads onto mesh B (elastic restart).
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:08d}", "arrays.npz")
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    for k, name in load_meta(ckpt_dir, step).get("raw_dtypes", {}).items():
        flat[k] = flat[k].view(jnp.dtype(name))

    leaves_kp, treedef = jax.tree_util.tree_flatten_with_path(template)
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(leaves_kp))
    out = []
    for (kp, leaf), sh in zip(leaves_kp, shard_leaves):
        key = _SEP.join(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in kp)
        if key not in flat:
            raise KeyError(f"checkpoint missing {key!r}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"{key}: ckpt shape {arr.shape} != {leaf.shape}")
        arr = arr.astype(leaf.dtype)
        out.append(jax.device_put(arr, sh) if sh is not None
                   else jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


def load_meta(ckpt_dir: str, step: int) -> Dict:
    with open(os.path.join(ckpt_dir, f"step_{step:08d}", "meta.json")) as f:
        return json.load(f)
