"""Fault-tolerant checkpointing: atomic step-tagged snapshots + async writer.

Requirements at 1000+ nodes:
  * ATOMIC: a checkpoint is visible only when complete. Writes land in
    ``step_NNNNNNNN.tmp-<pid>`` and are ``os.rename``d (atomic on POSIX)
    to ``step_NNNNNNNN`` last — a job killed mid-write never leaves a
    half-readable "latest".
  * ASYNC: `save(..., blocking=False)` snapshots device arrays to host
    (jax.device_get — this is the only sync point) and hands serialization
    + fsync to a writer thread, so the train loop stalls for the copy, not
    the disk.
  * SELF-DESCRIBING: the manifest stores the pytree structure and per-leaf
    dtype/shape; restore rebuilds the tree and (optionally) re-shards onto
    a DIFFERENT mesh via jax.device_put with new shardings — this is what
    makes elastic re-scaling (runtime/elastic.py) work.
  * BOUNDED: keeps the newest ``keep`` checkpoints, deletes older ones
    after a successful write (never before).

Format: one ``.npz`` per checkpoint (flat leaf arrays keyed by index) plus a
JSON manifest with the treedef + step + user metadata. No pickle.
"""
from __future__ import annotations

import json
import os
import queue
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

Params = Any
_STEP_RE = re.compile(r"^step_(\d{8})$")


def _flatten_with_paths(tree: Params) -> Tuple[List[Tuple[str, np.ndarray]], Any]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    paths = [f"leaf_{i}" for i in range(len(leaves))]
    return list(zip(paths, leaves)), treedef


def _step_dir(root: str, step: int) -> str:
    return os.path.join(root, f"step_{step:08d}")


def latest_step(root: str) -> Optional[int]:
    if not os.path.isdir(root):
        return None
    steps = []
    for name in os.listdir(root):
        m = _STEP_RE.match(name)
        if m and os.path.exists(os.path.join(root, name, "manifest.json")):
            steps.append(int(m.group(1)))
    return max(steps) if steps else None


def save(root: str, step: int, tree: Params,
         metadata: Optional[Dict[str, Any]] = None) -> str:
    """Synchronous atomic save. Returns the final directory path."""
    os.makedirs(root, exist_ok=True)
    final = _step_dir(root, step)
    tmp = f"{final}.tmp-{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)

    host_leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
    treedef = jax.tree_util.tree_structure(tree)
    np.savez(os.path.join(tmp, "arrays.npz"),
             **{f"leaf_{i}": a for i, a in enumerate(host_leaves)})
    manifest = {
        "step": step,
        "treedef": str(treedef),
        "n_leaves": len(host_leaves),
        "leaves": [{"dtype": str(a.dtype), "shape": list(a.shape)}
                   for a in host_leaves],
        "metadata": metadata or {},
        "time": time.time(),
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):  # re-save of same step (restart race): replace
        os.rename(final, final + f".old-{os.getpid()}")
    os.rename(tmp, final)
    return final


def restore(root: str, tree_like: Params, step: Optional[int] = None,
            shardings: Optional[Params] = None) -> Tuple[Params, int, Dict]:
    """Restore into the structure of ``tree_like``.

    ``shardings``: optional pytree of jax.sharding.Sharding matching
    tree_like — leaves are device_put with them (the re-mesh path).
    """
    if step is None:
        step = latest_step(root)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {root}")
    d = _step_dir(root, step)
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(d, "arrays.npz"))
    leaves = [data[f"leaf_{i}"] for i in range(manifest["n_leaves"])]
    treedef = jax.tree_util.tree_structure(tree_like)
    if treedef.num_leaves != len(leaves):
        raise ValueError(
            f"checkpoint has {len(leaves)} leaves; target tree has "
            f"{treedef.num_leaves} — structure changed?")
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    if shardings is not None:
        tree = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), tree, shardings)
    return tree, step, manifest["metadata"]


class CheckpointManager:
    """Async checkpointing with retention. One background writer thread."""

    def __init__(self, root: str, keep: int = 3):
        self.root = root
        self.keep = keep
        self._q: "queue.Queue" = queue.Queue()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    # -- public API ---------------------------------------------------------
    def save(self, step: int, tree: Params,
             metadata: Optional[Dict[str, Any]] = None,
             blocking: bool = False) -> None:
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError("previous async checkpoint failed") from err
        # Snapshot to host NOW (cheap, synchronous) so the caller may donate/
        # mutate device buffers immediately after.
        host_tree = jax.tree_util.tree_map(lambda x: np.asarray(x), tree)
        if blocking:
            self._write(step, host_tree, metadata)
        else:
            self._q.put((step, host_tree, metadata))

    def wait(self) -> None:
        self._q.join()
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError("async checkpoint failed") from err

    def latest_step(self) -> Optional[int]:
        return latest_step(self.root)

    def restore(self, tree_like: Params, step: Optional[int] = None,
                shardings: Optional[Params] = None):
        return restore(self.root, tree_like, step, shardings)

    # -- internals ----------------------------------------------------------
    def _write(self, step, host_tree, metadata):
        save(self.root, step, host_tree, metadata)
        self._gc()

    def _gc(self):
        steps = sorted(
            int(m.group(1)) for m in
            (_STEP_RE.match(n) for n in os.listdir(self.root)) if m)
        for s in steps[:-self.keep] if len(steps) > self.keep else []:
            d = _step_dir(self.root, s)
            for name in os.listdir(d):
                os.unlink(os.path.join(d, name))
            os.rmdir(d)

    def _worker(self):
        while True:
            item = self._q.get()
            try:
                self._write(*item)
            except BaseException as e:  # surfaced on next save()/wait()
                self._err = e
            finally:
                self._q.task_done()
