"""Checkpointing: sharded tensor save/restore with async write, atomic
publish, integrity manifest, and mesh-independent restore (elastic restarts).

Layout (one directory per step)::

    <dir>/step_000123/
        manifest.json        # tree structure, dtypes, shapes, checksums
        arr_00000.npy ...    # one file per leaf (full logical array)
    <dir>/LATEST             # atomic pointer file

Tensors are written as *logical* arrays (gathered from the mesh), so a
checkpoint taken on a 16x16 mesh restores onto 8x16, 2x16x16, or a single
CPU — resharding is just a ``device_put`` with the target sharding. Writes
happen on a background thread (training continues) and publish atomically
via directory rename; a crash mid-write can never corrupt LATEST.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclass
class WriteHandle:
    """Tracks one (possibly background) checkpoint write.

    ``event`` is set when the write finishes — successfully OR not; a
    failed write records its exception in ``error`` instead of dying
    silently on the daemon thread. :meth:`CheckpointManager.wait` re-raises
    it on the caller's thread.
    """

    event: threading.Event
    error: BaseException | None = None
    path: str | None = None


def _tree_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, jax.tree_util.tree_structure(tree)


def _checksum(arr: np.ndarray) -> str:
    return hashlib.sha256(arr.tobytes()).hexdigest()[:16]


def save(ckpt_dir: str, step: int, state, *, async_write: bool = False,
         _done_event: threading.Event | None = None,
         _handle: WriteHandle | None = None) -> str:
    """Save ``state`` (any pytree of arrays) for ``step``. Returns the path
    (final path; with ``async_write`` the data lands shortly after).

    ``_handle``: a :class:`WriteHandle` to report completion/failure
    through — a background write that throws records the exception there
    (and still sets the event) instead of evaporating with the daemon
    thread; a synchronous write re-raises immediately.
    """
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"

    paths, leaves, treedef = _tree_paths(state)
    # materialize on host BEFORE backgrounding (snapshot semantics)
    host_leaves = [np.asarray(jax.device_get(l)) for l in leaves]

    def write():
        os.makedirs(tmp, exist_ok=True)
        manifest = {"step": step, "leaves": [], "treedef": paths}
        for i, (p, arr) in enumerate(zip(paths, host_leaves)):
            fn = f"arr_{i:05d}.npy"
            np.save(os.path.join(tmp, fn), arr)
            manifest["leaves"].append({
                "path": p, "file": fn, "shape": list(arr.shape),
                "dtype": str(arr.dtype), "sha": _checksum(arr)})
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)                      # atomic publish
        latest_tmp = os.path.join(ckpt_dir, ".LATEST.tmp")
        with open(latest_tmp, "w") as f:
            f.write(os.path.basename(final))
        os.replace(latest_tmp, os.path.join(ckpt_dir, "LATEST"))

    def run_write():
        try:
            write()
            if _handle is not None:
                _handle.path = final
        except BaseException as e:                 # noqa: BLE001
            if _handle is not None:
                _handle.error = e
            else:
                raise
        finally:
            if _handle is not None:
                _handle.event.set()
            if _done_event is not None:
                _done_event.set()

    if async_write:
        threading.Thread(target=run_write, daemon=True).start()
    else:
        run_write()
        if _handle is not None and _handle.error is not None:
            raise _handle.error
    return final


def latest_step(ckpt_dir: str) -> int | None:
    latest = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(latest):
        return None
    with open(latest) as f:
        name = f.read().strip()
    return int(name.split("_")[-1])


def restore(ckpt_dir: str, target_tree, step: int | None = None,
            shardings=None, verify: bool = True):
    """Restore into the structure of ``target_tree``.

    ``shardings``: optional pytree of ``NamedSharding`` (same structure) for
    direct resharded placement onto a (possibly different) mesh.
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)

    by_path = {e["path"]: e for e in manifest["leaves"]}
    tgt_paths, tgt_leaves, treedef = _tree_paths(target_tree)
    flat_shardings = (treedef.flatten_up_to(shardings)
                      if shardings is not None else [None] * len(tgt_leaves))

    out = []
    for p, tgt, sh in zip(tgt_paths, tgt_leaves, flat_shardings):
        entry = by_path[p]
        arr = np.load(os.path.join(path, entry["file"]))
        if arr.dtype.kind == "V":
            # .npy keeps an extension dtype (bfloat16, float8) as raw bytes
            arr = arr.view(jnp.dtype(entry["dtype"]))
        if verify and _checksum(arr) != entry["sha"]:
            raise IOError(f"checksum mismatch for {p} in {path}")
        tgt_arr = np.asarray(tgt)
        if tuple(arr.shape) != tuple(tgt_arr.shape):
            raise ValueError(
                f"checkpoint leaf {p!r} has logical shape {arr.shape} but "
                f"the restore target expects {tgt_arr.shape} — the "
                "checkpoint was taken for a different model/engine "
                "configuration")
        # cast to the TARGET dtype on both branches: the sharded branch
        # used to skip it, so restoring e.g. an old fp32 save onto an int8
        # q8 layout silently kept the on-disk dtype and flowed wrong-width
        # arrays into the kernels
        arr = arr.astype(tgt_arr.dtype)
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


class CheckpointManager:
    """Cadence + retention + async orchestration."""

    def __init__(self, ckpt_dir: str, every: int = 100, keep: int = 3,
                 async_write: bool = True):
        self.dir = ckpt_dir
        self.every = every
        self.keep = keep
        self.async_write = async_write
        os.makedirs(ckpt_dir, exist_ok=True)
        self._pending: list[WriteHandle] = []

    def maybe_save(self, step: int, state) -> bool:
        if step % self.every:
            return False
        handle = WriteHandle(threading.Event())
        save(self.dir, step, state, async_write=self.async_write,
             _handle=handle)
        self._pending.append(handle)
        self._gc()
        return True

    def wait(self, timeout: float = 60.0) -> bool:
        """Block until every pending async write has published.

        Returns ``True`` when all pending writes landed; ``False`` when one
        timed out (it stays pending for the next ``wait``). A write that
        FAILED re-raises its exception here, on the caller's thread — the
        old implementation discarded ``Event.wait``'s return value and
        swallowed background-thread exceptions, so a hung or failed write
        passed silently and the "checkpoint" a restart would rely on never
        existed.
        """
        still_pending: list[WriteHandle] = []
        first_error: BaseException | None = None
        for handle in self._pending:
            if not handle.event.wait(timeout):
                still_pending.append(handle)
                continue
            if handle.error is not None and first_error is None:
                first_error = handle.error
        self._pending = still_pending
        if first_error is not None:
            raise first_error
        return not still_pending

    def _gc(self):
        steps = sorted(
            int(d.split("_")[-1]) for d in os.listdir(self.dir)
            if d.startswith("step_") and not d.endswith(".tmp"))
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)
