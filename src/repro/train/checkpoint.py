"""Distributed checkpointing: per-leaf shard files + manifest, async save,
atomic commit, and **elastic restore** (resume onto a different mesh shape).

Layout of one checkpoint::

    <dir>/step_000120.tmp/            # written first
        manifest.json                 # step, leaf paths, shapes, dtypes, data state
        <leaf-key>.npy                # one file per pytree leaf
    <dir>/step_000120/                # atomic rename on completion

On a multi-controller deployment each host writes only its addressable
shards and the manifest records the global shape + index map; this
single-process implementation writes full leaves but keeps the same
manifest contract, so ``restore(..., mesh=other_mesh, shardings=...)``
re-places every leaf under the *new* mesh — the elastic-scaling path
(tested in tests/test_checkpoint.py).
"""
from __future__ import annotations

import json
import shutil
import threading
from pathlib import Path
from typing import Any

import jax
import numpy as np



def _flatten_with_paths(tree) -> list[tuple[str, Any]]:
    flat, _ = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(_path_str(p) for p in path)
        out.append((key, leaf))
    return out


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"[{p.idx}]"
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


class CheckpointManager:
    def __init__(self, directory: str | Path, keep: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._async_thread: threading.Thread | None = None
        self._async_err: list[BaseException] = []

    # -- save -------------------------------------------------------------------

    def save(self, step: int, state: dict, extra: dict | None = None) -> Path:
        """Blocking save.  ``state`` is any pytree of arrays."""
        host_state = jax.tree.map(np.asarray, jax.device_get(state))
        return self._write(step, host_state, extra or {})

    def save_async(self, step: int, state: dict, extra: dict | None = None):
        """Non-blocking save: device→host copy happens now (so training can
        mutate buffers), file IO happens on a worker thread."""
        self.wait()
        host_state = jax.tree.map(np.asarray, jax.device_get(state))

        def work():
            try:
                self._write(step, host_state, extra or {})
            except BaseException as e:  # surfaced by wait()
                self._async_err.append(e)

        self._async_thread = threading.Thread(target=work, daemon=True)
        self._async_thread.start()

    def wait(self):
        if self._async_thread is not None:
            self._async_thread.join()
            self._async_thread = None
        if self._async_err:
            raise self._async_err.pop()

    def _write(self, step: int, host_state, extra: dict) -> Path:
        final = self.dir / f"step_{step:08d}"
        tmp = self.dir / f"step_{step:08d}.tmp"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        leaves = _flatten_with_paths(host_state)
        manifest = {"step": step, "extra": extra, "leaves": {}}
        for key, arr in leaves:
            fname = key.replace("/", "__").replace("[", "_").replace("]", "_")
            np.save(tmp / f"{fname}.npy", arr)
            manifest["leaves"][key] = {
                "file": f"{fname}.npy",
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
            }
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)
        self._gc()
        return final

    def _gc(self):
        ckpts = sorted(self.all_steps())
        for step in ckpts[:-self.keep] if self.keep else []:
            shutil.rmtree(self.dir / f"step_{step:08d}", ignore_errors=True)

    # -- restore ------------------------------------------------------------------

    def all_steps(self) -> list[int]:
        out = []
        for p in self.dir.glob("step_*"):
            if p.suffix == ".tmp" or not (p / "manifest.json").exists():
                continue
            out.append(int(p.name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, template, step: int | None = None,
                shardings=None) -> tuple[int, Any, dict]:
        """Restore into the structure of ``template``.

        ``shardings``: optional pytree of Shardings for the *current* mesh —
        pass a different mesh's shardings to reshard elastically.
        Returns (step, state, extra).
        """
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = self.dir / f"step_{step:08d}"
        manifest = json.loads((d / "manifest.json").read_text())
        keyed = dict(_flatten_with_paths(template))
        arrays = {}
        for key, meta in manifest["leaves"].items():
            if key not in keyed:
                continue
            arr = np.load(d / meta["file"])
            arrays[key] = arr
        flat, treedef = jax.tree.flatten_with_path(template)
        out_leaves = []
        shard_flat = (jax.tree.leaves(shardings) if shardings is not None
                      else [None] * len(flat))
        for (path, leaf), shard in zip(flat, shard_flat):
            key = "/".join(_path_str(p) for p in path)
            arr = arrays.get(key)
            if arr is None:
                raise KeyError(f"checkpoint {d} missing leaf {key}")
            dtype = getattr(leaf, "dtype", arr.dtype)
            v = jax.device_put(arr.astype(dtype), shard) if shard is not None \
                else jax.device_put(np.asarray(arr, dtype=dtype))
            out_leaves.append(v)
        state = jax.tree.unflatten(treedef, out_leaves)
        return step, state, manifest.get("extra", {})
