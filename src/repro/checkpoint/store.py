"""Checkpointing: atomic save/restore of arbitrary pytrees + elastic resume.

Layout: ``<dir>/step_<k>/`` with one ``.npy`` per leaf (flattened key path
as filename) plus ``manifest.json`` (treedef + shapes + dtypes + step).
Writes go to a temp dir renamed into place (atomic on POSIX), so a crash
mid-save never corrupts the latest checkpoint.  ``AsyncCheckpointer``
snapshots device arrays to host, then writes on a worker thread so the train
loop resumes immediately (the standard TPU pattern).

Elastic resume: arrays are stored unsharded; ``restore`` takes an optional
``sharding_tree`` and ``jax.device_put``s each leaf with its (possibly new)
sharding — restoring a 16x16-trained checkpoint onto any other mesh shape is
the same code path.  Fault tolerance: ``install_sigterm_handler`` triggers a
final synchronous save on preemption.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import tempfile
import threading
from typing import Any, Callable, Optional

import jax
import numpy as np

__all__ = ["save", "restore", "latest_step", "AsyncCheckpointer",
           "install_sigterm_handler"]


def _leafname(path) -> str:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "idx"):
            keys.append(str(p.idx))
        else:
            keys.append(str(p))
    name = "__".join(keys)
    return re.sub(r"[^A-Za-z0-9_.-]", "_", name)


def save(ckpt_dir: str, step: int, tree: Any) -> str:
    """Atomically write ``tree`` as ``<ckpt_dir>/step_<step>/``."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_")
    leaves, treedef = jax.tree.flatten_with_path(tree)
    manifest = {"step": step, "leaves": []}
    for path, leaf in leaves:
        name = _leafname(path)
        arr = np.asarray(jax.device_get(leaf))
        np.save(os.path.join(tmp, name + ".npy"), arr)
        manifest["leaves"].append({"name": name,
                                   "dtype": str(arr.dtype),
                                   "shape": list(arr.shape)})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
             if d.startswith("step_")]
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int, target_tree: Any,
            sharding_tree: Any = None) -> Any:
    """Load ``step_<step>`` into the structure of ``target_tree``.

    ``sharding_tree`` (same structure, jax.sharding.Sharding leaves or None)
    re-shards on load — elastic resume onto a different mesh.
    """
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    leaves, treedef = jax.tree.flatten_with_path(target_tree)
    shardings = (jax.tree.leaves(sharding_tree)
                 if sharding_tree is not None else [None] * len(leaves))
    out = []
    for (path, leaf), shard in zip(leaves, shardings):
        arr = np.load(os.path.join(d, _leafname(path) + ".npy"))
        want = getattr(leaf, "shape", None)
        if want is not None and tuple(arr.shape) != tuple(want):
            raise ValueError(
                f"checkpoint leaf {_leafname(path)} shape {arr.shape} != "
                f"expected {want}")
        if shard is not None:
            out.append(jax.device_put(arr, shard))
        else:
            out.append(jax.device_put(arr))
    return treedef.unflatten(out)


class AsyncCheckpointer:
    """Snapshot-to-host + background write; at most one write in flight."""

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def save(self, step: int, tree: Any) -> None:
        self.wait()
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                 tree)

        def work():
            try:
                save(self.ckpt_dir, step, host_tree)
                self._gc()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self) -> None:
        steps = sorted(s for s in (
            int(d.split("_")[1]) for d in os.listdir(self.ckpt_dir)
            if d.startswith("step_")))
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.ckpt_dir,
                                       f"step_{s:08d}"),
                          ignore_errors=True)


def install_sigterm_handler(fn: Callable[[], None]) -> None:
    """Run ``fn`` (e.g. a final synchronous checkpoint) on SIGTERM."""
    def handler(signum, frame):
        fn()
        raise SystemExit(143)
    signal.signal(signal.SIGTERM, handler)
