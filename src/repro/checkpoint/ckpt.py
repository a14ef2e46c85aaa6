"""Sharded, atomic, async checkpointing with elastic (mesh-agnostic) restore.

Layout:  <dir>/step_<N>/
           manifest.json      tree structure, shapes, dtypes, step
           <leaf-id>.npy      one file per leaf (host-gathered values)

Writes go to ``step_<N>.tmp`` then os.rename -> crash-safe; an interrupted
save can never be mistaken for a complete checkpoint. Every file is fsync'd
before the rename and the parent directory entry after it, so a power loss
(not just a process crash) can never surface a renamed-but-torn checkpoint.
:func:`atomic_write_json` exports the same tmp+fsync+rename discipline for
every other JSON artifact the repo persists (BENCH_* results).
``save_async`` hands the (host-copied) pytree to a writer thread so the
train loop is not blocked.
Restore maps leaves back by tree path and ``jax.device_put``s them with the
*target* mesh's NamedShardings — a checkpoint written on a 256-chip mesh
restores onto 512 or 8 chips unchanged (elastic resharding).

Every saved leaf carries a CRC32 in the manifest; ``restore(verify=True)``
re-checksums the bytes read back and refuses a silently-corrupted file
(the same bit-rot defence the serving registry's alpha-bank scrub applies
to RESIDENT weights — see ``repro.serving.model_registry``). Manifests
from before this field verify trivially (no stored CRC, nothing to check).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import zlib
from typing import Any, Optional

import jax
import numpy as np


def _fsync_path(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def fsync_dir(directory: str) -> None:
    """fsync a directory entry (durability of renames/creates within it)."""
    _fsync_path(directory or ".")


def atomic_write_json(path: str, obj: Any, *, indent: Optional[int] = None
                      ) -> None:
    """Crash-safe JSON write: tmp file + flush + fsync + atomic rename +
    parent-directory fsync. A crash at ANY point leaves either the old
    complete file or the new complete file — never a torn one."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=indent)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    fsync_dir(os.path.dirname(os.path.abspath(path)))


def _leaf_paths(tree: Any) -> list[tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out.append((name, leaf))
    return out


def save(tree: Any, directory: str, step: int) -> str:
    """Blocking atomic save. Returns the final checkpoint path."""
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    manifest = {"step": step, "leaves": []}
    for i, (name, leaf) in enumerate(_leaf_paths(tree)):
        arr = np.asarray(leaf)
        fn = f"leaf_{i:05d}.npy"
        fp = os.path.join(tmp, fn)
        np.save(fp, arr)
        _fsync_path(fp)
        manifest["leaves"].append(
            {"path": name, "file": fn, "shape": list(arr.shape),
             "dtype": str(arr.dtype),
             "crc32": zlib.crc32(np.ascontiguousarray(arr).tobytes())})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    fsync_dir(tmp)      # leaf/manifest dir entries durable before the rename
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    fsync_dir(directory)
    return final


class AsyncSaver:
    """Single background writer; joins pending work before a new save."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self.last_path: Optional[str] = None

    def save_async(self, tree: Any, directory: str, step: int) -> None:
        self.wait()
        host_tree = jax.tree_util.tree_map(np.asarray, tree)  # snapshot now

        def _work():
            self.last_path = save(host_tree, directory, step)

        self._thread = threading.Thread(target=_work, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(directory)
             if d.startswith("step_") and not d.endswith(".tmp")]
    return max(steps) if steps else None


def restore(directory: str, step: Optional[int] = None, *,
            template: Any = None, shardings: Any = None,
            verify: bool = True) -> tuple[Any, int]:
    """Load a checkpoint. With ``template`` (pytree of like-structured leaves)
    the arrays are mapped back into that structure by tree path; with
    ``shardings`` each leaf is device_put onto the current mesh (elastic).
    ``verify=True`` (the DEFAULT — every loader path checks unless the
    caller explicitly opts out, e.g. launch ``--no-verify-ckpt``)
    re-checksums every leaf against the manifest's CRC32 and raises
    ``ValueError`` naming the corrupt leaf on a mismatch (on-disk bit
    rot). Manifests predating the CRC field verify trivially."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    by_path = {}
    for e in manifest["leaves"]:
        arr = np.load(os.path.join(path, e["file"]))
        if verify and "crc32" in e:
            crc = zlib.crc32(np.ascontiguousarray(arr).tobytes())
            if crc != e["crc32"]:
                raise ValueError(
                    f"checkpoint restore: leaf {e['path']!r} in {path} "
                    f"failed its CRC32 check (stored {e['crc32']:#010x}, "
                    f"read {crc:#010x}) — the file rotted on disk; restore "
                    "an older step or re-save")
        by_path[e["path"]] = arr
    if template is None:
        return by_path, step

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    shard_leaves = (jax.tree_util.tree_leaves(shardings)
                    if shardings is not None else [None] * len(flat))
    leaves = []
    for (pth, leaf), sh in zip(flat, shard_leaves):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in pth)
        arr = by_path[name]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(
                f"checkpoint restore: leaf {name!r} shape mismatch — "
                f"checkpoint has {tuple(arr.shape)}, template expects "
                f"{tuple(leaf.shape)}; the checkpoint was likely written "
                f"for a different model config or mesh layout")
        # elastic restore casts float<->float (e.g. f32 -> bf16) freely, but a
        # float<->int cast would silently corrupt quantised leaves (int8/int4
        # alphas must round-trip bit-exactly): refuse with a clear error.
        if (np.issubdtype(np.dtype(leaf.dtype), np.integer)
                != np.issubdtype(arr.dtype, np.integer)):
            raise TypeError(
                f"{name}: refusing float<->int cast on restore "
                f"(ckpt {arr.dtype} -> template {leaf.dtype}); re-convert the "
                "checkpoint to the template's alpha_dtype instead")
        arr = arr.astype(leaf.dtype)
        leaves.append(jax.device_put(arr, sh) if sh is not None
                      else jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves), step


def gc_old(directory: str, keep: int = 3) -> None:
    if not os.path.isdir(directory):
        return
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(directory)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(directory, f"step_{s:08d}"),
                      ignore_errors=True)
