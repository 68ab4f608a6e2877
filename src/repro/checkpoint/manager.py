"""Fault-tolerant checkpointing: atomic, async, mesh-reshardable.

Fault-tolerance contract (DESIGN.md §6):

- **atomic**: writes go to ``step_XXXXXXXX.tmp/`` and are renamed only after
  the manifest (tree structure + shapes + dtypes + CRC32 per leaf) has been
  fsync'd — a crash mid-write can never corrupt the latest checkpoint;
- **async**: `save()` snapshots device arrays to host and hands the file I/O
  to a background thread, returning control to the training loop immediately
  (`wait()` joins before the next save or at exit);
- **elastic restarts**: `restore()` takes the *current* mesh/sharding spec;
  arrays are loaded as full logical values and re-placed with the new
  sharding, so a job restarted on a different pod count (e.g. after losing
  a pod) resumes from the same step with a different layout.
"""

from __future__ import annotations

import json
import os
import re
import threading
import zlib
from typing import Any, Optional

import jax
import numpy as np

_STEP_RE = re.compile(r"^step_(\d{8})$")


def _flatten(tree) -> tuple[list[tuple[str, Any]], Any]:
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    named = [
        (
            "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path),
            leaf,
        )
        for path, leaf in leaves
    ]
    return named, treedef


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # -- save -----------------------------------------------------------------

    def save(self, step: int, tree, blocking: bool = False) -> None:
        self.wait()
        named, _ = _flatten(tree)
        # copy to host now, so the caller may donate the device buffers to its
        # next step right away (np.asarray would alias CPU device memory)
        host = [(name, np.array(leaf)) for name, leaf in named]
        if blocking:
            self._write(step, host)
        else:
            self._thread = threading.Thread(
                target=self._write_guarded, args=(step, host)
            )
            self._thread.start()

    def _write_guarded(self, step: int, host) -> None:
        # A bare thread target swallows exceptions: a failed async write
        # (disk full, the FileExistsError re-save guard, a permissions
        # error) would otherwise leave the caller believing the checkpoint
        # landed.  Capture and surface on the next wait()/save().
        try:
            self._write(step, host)
        except BaseException as exc:  # noqa: BLE001 - resurfaced in wait()
            self._error = exc

    def _write(self, step: int, host) -> None:
        final = os.path.join(self.dir, f"step_{step:08d}")
        tmp = final + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        manifest = {}
        arrays = {}
        for i, (name, arr) in enumerate(host):
            key = f"a{i}"
            arrays[key] = arr
            manifest[name] = {
                "key": key,
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "crc32": zlib.crc32(np.ascontiguousarray(arr).tobytes()),
            }
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump({"step": step, "leaves": manifest}, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            raise FileExistsError(final)
        os.rename(tmp, final)
        self._gc()

    def _gc(self) -> None:
        steps = sorted(self.all_steps())
        for s in steps[: -self.keep]:
            path = os.path.join(self.dir, f"step_{s:08d}")
            for root, dirs, files in os.walk(path, topdown=False):
                for fn in files:
                    os.unlink(os.path.join(root, fn))
                for dn in dirs:
                    os.rmdir(os.path.join(root, dn))
            os.rmdir(path)

    def wait(self) -> None:
        """Join a pending async save; re-raise its exception if it failed."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            exc, self._error = self._error, None
            raise exc

    # -- restore ----------------------------------------------------------------

    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            m = _STEP_RE.match(name)
            if m and not name.endswith(".tmp"):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like, step: Optional[int] = None, shardings=None):
        """Restore into the structure of `like` (a pytree of arrays or
        ShapeDtypeStructs).  `shardings`: optional matching pytree of
        NamedShardings for elastic re-placement on the current mesh."""
        self.wait()
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        path = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)["leaves"]
        data = np.load(os.path.join(path, "arrays.npz"))

        named, treedef = _flatten(like)
        out_leaves = []
        for name, leaf in named:
            if name not in manifest:
                raise KeyError(f"checkpoint missing leaf {name!r}")
            meta = manifest[name]
            arr = data[meta["key"]]
            if zlib.crc32(np.ascontiguousarray(arr).tobytes()) != meta["crc32"]:
                raise IOError(f"CRC mismatch for {name!r} (corrupt checkpoint)")
            want_shape = tuple(leaf.shape)
            if tuple(arr.shape) != want_shape:
                raise ValueError(f"{name}: shape {arr.shape} != {want_shape}")
            out_leaves.append(arr)
        tree = jax.tree_util.tree_unflatten(treedef, out_leaves)
        if shardings is not None:
            tree = jax.tree.map(
                lambda a, s: jax.device_put(a, s), tree, shardings
            )
        else:
            tree = jax.tree.map(jax.numpy.asarray, tree)
        return tree, step
