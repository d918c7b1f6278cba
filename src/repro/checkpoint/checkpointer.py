"""Sharded, async, integrity-checked checkpointing with elastic restore.

Layout:  <dir>/step_<N>/
           manifest.json       tree structure, shapes, dtypes, crc32 per leaf
           <leaf-id>.npy       one file per pytree leaf

Design points for 1000+-node operation (DESIGN.md §5):
  * save is ASYNC and the device→host transfer is OVERLAPPED: the caller
    thread only dispatches a donation-safe on-device snapshot + async D2H
    copy per leaf; a background thread completes the transfer and writes
    — training blocks on neither the interconnect nor the filesystem
    (double-buffered: at most two snapshots in flight, see
    AsyncCheckpointer);
  * writes are ATOMIC: a step directory is staged as .tmp and renamed only
    after every leaf + manifest hit disk, so a mid-write failure never
    corrupts the latest checkpoint;
  * restore is ELASTIC: leaves are loaded as full arrays and re-placed
    with ``jax.device_put`` against the *current* mesh's shardings — a job
    restarted on a different device count resumes from the same file set;
  * integrity: per-leaf crc32 is verified on load (bit-rot / truncation).
"""
from __future__ import annotations

import json
import pathlib
import queue
import shutil
import threading
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def _flatten(state):
    leaves, treedef = jax.tree.flatten(state)
    return leaves, treedef


def _leaf_paths(state):
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    return [jax.tree_util.keystr(kp, simple=True, separator=".") for kp, _ in flat]


class Checkpointer:
    def __init__(self, directory: str | pathlib.Path, keep: int = 3):
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep

    # -- save ------------------------------------------------------------------
    def save(self, step: int, state) -> pathlib.Path:
        leaves, _ = _flatten(state)
        names = _leaf_paths(state)
        host = [np.asarray(jax.device_get(l)) for l in leaves]
        return self._write(step, names, host)

    def _write(self, step: int, names, host_leaves) -> pathlib.Path:
        final = self.dir / f"step_{step:08d}"
        tmp = self.dir / f".tmp_step_{step:08d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        manifest = {"step": step, "leaves": []}
        for i, (name, arr) in enumerate(zip(names, host_leaves)):
            fn = f"leaf_{i:05d}.npy"
            logical_dtype = str(arr.dtype)
            to_write = arr
            if logical_dtype == "bfloat16":
                # numpy can't serialize ml_dtypes natively: store raw bits
                to_write = arr.view(np.uint16)
            np.save(tmp / fn, to_write, allow_pickle=False)
            manifest["leaves"].append({
                "name": name, "file": fn,
                "shape": list(arr.shape), "dtype": logical_dtype,
                "crc32": zlib.crc32(np.ascontiguousarray(to_write).tobytes()),
            })
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)                      # atomic publish
        self._gc()
        return final

    def _gc(self) -> None:
        steps = sorted(self.all_steps())
        for s in steps[: max(len(steps) - self.keep, 0)]:
            shutil.rmtree(self.dir / f"step_{s:08d}", ignore_errors=True)

    # -- restore ---------------------------------------------------------------
    def all_steps(self) -> list[int]:
        return sorted(int(p.name.split("_")[1]) for p in self.dir.glob("step_*"))

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, state_like, step: int | None = None,
                shardings=None, verify: bool = True):
        """state_like: pytree with the target structure (abstract ok).
        shardings: optional matching pytree of NamedSharding for elastic
        re-placement on the current mesh."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = self.dir / f"step_{step:08d}"
        manifest = json.loads((d / "manifest.json").read_text())
        _, treedef = _flatten(state_like)
        shard_leaves = (jax.tree.leaves(shardings)
                        if shardings is not None else None)
        out = []
        for i, ent in enumerate(manifest["leaves"]):
            arr = np.load(d / ent["file"], allow_pickle=False)
            if verify:
                crc = zlib.crc32(np.ascontiguousarray(arr).tobytes())
                if crc != ent["crc32"]:
                    raise IOError(
                        f"checkpoint corruption in {ent['name']}: "
                        f"crc {crc} != {ent['crc32']}")
            if ent["dtype"] == "bfloat16":
                import ml_dtypes
                arr = arr.view(ml_dtypes.bfloat16)
            if shard_leaves is not None:
                # put_global handles process-spanning shardings (each
                # process feeds its addressable slice); it degenerates to
                # device_put on ordinary meshes
                from repro.sharding.fleet import put_global
                arr = put_global(arr, shard_leaves[i])
            out.append(arr)
        return jax.tree.unflatten(treedef, out)


class AsyncCheckpointer(Checkpointer):
    """save_async(): snapshot now, write in the background.

    With ``overlap_transfer=True`` (the default) the device→host transfer
    itself moves off the caller thread: ``save_async`` dispatches an
    on-device SNAPSHOT copy per jax leaf (eager ``jnp.copy`` — enqueued
    on the device stream before any later computation, and never itself
    donated, so a donating caller like the sharded fleet runner's
    in-place chunk scan cannot invalidate it), starts its async D2H copy,
    and hands the snapshot references to the background worker, which
    blocks on the transfer there and then serializes.  The caller —
    typically a chunked training loop — dispatches its next chunk
    immediately, so accelerator meshes keep scanning while the previous
    chunk's snapshot drains over PCIe/ICI and hits disk.

    The queue is DOUBLE-BUFFERED (``max_inflight=1``): one snapshot being
    written plus one queued; a third ``save_async`` blocks until the
    oldest write completes, bounding host memory at ~2 snapshots no matter
    how fast chunks finish.  ``overlap_transfer=False`` restores the old
    synchronous-transfer behavior (host copies taken on the caller thread
    before ``save_async`` returns — needed if the caller mutates buffers
    in place outside jax's view)."""

    def __init__(self, directory, keep: int = 3,
                 overlap_transfer: bool = True, max_inflight: int = 1):
        super().__init__(directory, keep)
        self.overlap_transfer = overlap_transfer
        self._q: queue.Queue = queue.Queue(maxsize=max(int(max_inflight), 1))
        self._err: list[BaseException] = []
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            step, names, leaves = item
            try:
                # completes the D2H transfer when leaves are device arrays
                # (overlap path); no-op copies when already host snapshots
                host = [np.asarray(jax.device_get(l)) for l in leaves]
                self._write(step, names, host)
            except BaseException as e:  # surfaced on wait()
                self._err.append(e)
            finally:
                self._q.task_done()

    def save_async(self, step: int, state) -> None:
        leaves, _ = _flatten(state)
        names = _leaf_paths(state)
        if self.overlap_transfer:
            payload = []
            for leaf in leaves:
                if isinstance(leaf, jax.Array):
                    # device-side snapshot: ordered after the producing
                    # computation, independent of the original buffer (a
                    # later donating dispatch deletes the ORIGINAL, not
                    # this copy), then start its D2H transfer
                    leaf = jnp.copy(leaf)
                    leaf.copy_to_host_async()    # enqueue DMA, don't block
                payload.append(leaf)
        else:
            payload = [np.asarray(jax.device_get(l)) for l in leaves]
        self._q.put((step, names, payload))      # blocks when 2 in flight

    def wait(self) -> None:
        self._q.join()
        if self._err:
            raise self._err.pop()

    def close(self) -> None:
        self.wait()
        self._q.put(None)
        self._worker.join()
