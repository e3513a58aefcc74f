"""Fault-tolerant checkpointing through a PAIO data-plane stage.

Design (paper §5 applied to the training stack):

* **Background flow**: every shard write flows through an ``ArrayInstance``
  with ``bg_checkpoint`` context, so the stage's DRL object can rate-limit
  checkpoint I/O to the leftover bandwidth the control plane allocates — a
  checkpoint burst can never starve the input pipeline.
* **Transformation objects**: the channel may hold ``compress`` (zstd) and/or
  ``quantize_int8`` objects; the manifest records which transformation was
  applied per tensor so restore inverts it.
* **Atomicity / crash safety**: writes go to ``step_<n>.tmp/``; the manifest
  (with per-file CRC32) is written last, the directory fsync'd and renamed to
  ``step_<n>/``. A crash mid-save leaves the previous checkpoint intact; a
  crash mid-rename is resolved by the loader ignoring ``.tmp`` dirs.
* **Elastic resharding**: tensors are saved as *global* arrays (gathered from
  devices), so a checkpoint taken on one mesh restores onto any other mesh —
  the loader shards according to the target sharding tree.
* **Async**: ``AsyncCheckpointer`` snapshots device arrays to host on the
  caller's thread (cheap, consistent) and performs enforcement + file I/O on
  a worker thread, overlapping checkpoint writes with training compute.
"""
from __future__ import annotations

import json
import os
import re
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax

from repro.core import BG_CHECKPOINT, ArrayInstance, RequestType, Stage, propagate_context
from repro.core.objects import QuantizeInt8

PyTree = Any

_STEP_RE = re.compile(r"^step_(\d+)$")


def _flatten_with_names(tree: PyTree) -> List[Tuple[str, np.ndarray]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path).replace("/", "_")
        out.append((name, leaf))
    return out


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [int(m.group(1)) for d in os.listdir(directory) if (m := _STEP_RE.match(d))]
    return max(steps) if steps else None


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        stage: Optional[Stage] = None,
        channel_context: str = BG_CHECKPOINT,
        transform: str = "none",  # none | compress | quantize
        keep: int = 3,
    ) -> None:
        self.directory = directory
        self.instance = ArrayInstance(stage) if stage is not None else None
        self.channel_context = channel_context
        self.transform = transform
        self.keep = keep
        #: one quantizer per manager: its backend (Pallas on a TPU, else
        #: numpy) is resolved once, and ``kernel_calls`` shows which ran
        self.quantizer = QuantizeInt8(block=256)
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------ #
    # save                                                                #
    # ------------------------------------------------------------------ #
    def _write_array(self, path: str, name: str, arr: np.ndarray, manifest: Dict) -> None:
        entry: Dict[str, Any] = {"shape": list(arr.shape), "dtype": str(arr.dtype), "transform": self.transform}
        if self.transform == "quantize" and arr.dtype in (np.float32, np.float16) and arr.ndim >= 1 and arr.size >= 256:
            from repro.core import Context

            res = self.quantizer.obj_enf(Context(0, RequestType.write, arr.nbytes), arr)
            qarr, scale = res.content
            payload = qarr.tobytes() + scale.tobytes()
            entry.update(res.meta)
            entry["scale_elems"] = int(scale.size)
            entry["q_elems"] = int(qarr.size)
        elif self.transform == "compress":
            import zstandard

            payload = zstandard.ZstdCompressor(level=3).compress(arr.tobytes())
        else:
            entry["transform"] = "none"
            payload = arr.tobytes()
        entry["crc32"] = zlib.crc32(payload) & 0xFFFFFFFF
        entry["nbytes"] = len(payload)
        fname = f"{name}.bin"
        entry["file"] = fname
        manifest["tensors"][name] = entry

        def sink(buf: Any) -> None:
            with open(os.path.join(path, fname), "wb") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())

        if self.instance is not None:
            with propagate_context(self.channel_context):
                # enforcement sees the payload size (rate limiting is by bytes)
                self.instance.enforce(RequestType.write, size=len(payload))
        sink(payload)

    def save(self, step: int, state: PyTree, extra: Optional[Dict[str, Any]] = None) -> str:
        """Blocking save of a (host or device) pytree. Returns final path."""
        host_state = jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)), state)
        tmp = os.path.join(self.directory, f"step_{step}.tmp")
        final = os.path.join(self.directory, f"step_{step}")
        os.makedirs(tmp, exist_ok=True)
        manifest: Dict[str, Any] = {"step": step, "tensors": {}, "extra": extra or {}}
        for name, arr in _flatten_with_names(host_state):
            self._write_array(tmp, name, arr, manifest)
        mpath = os.path.join(tmp, "manifest.json")
        with open(mpath, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.isdir(final):  # overwrite-safe
            import shutil

            shutil.rmtree(final)
        os.rename(tmp, final)
        self._gc()
        return final

    def _gc(self) -> None:
        steps = sorted(
            int(m.group(1)) for d in os.listdir(self.directory) if (m := _STEP_RE.match(d))
        )
        import shutil

        for s in steps[: -self.keep] if self.keep > 0 else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"), ignore_errors=True)

    # ------------------------------------------------------------------ #
    # restore                                                             #
    # ------------------------------------------------------------------ #
    def restore(
        self,
        step: int,
        target: PyTree,
        shardings: Optional[PyTree] = None,
        verify: bool = True,
    ) -> PyTree:
        """Restore into the structure of ``target`` (a pytree of arrays or
        ShapeDtypeStructs). ``shardings`` (same structure) enables elastic
        resharding: global arrays are placed with the *target* sharding,
        whatever mesh produced the checkpoint."""
        path = os.path.join(self.directory, f"step_{step}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        names = [n for n, _ in _flatten_with_names(target)]
        leaves, treedef = jax.tree_util.tree_flatten(target)
        shard_leaves = treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(leaves)
        out = []
        for name, leaf, shard in zip(names, leaves, shard_leaves):
            entry = manifest["tensors"][name]
            with open(os.path.join(path, entry["file"]), "rb") as f:
                payload = f.read()
            if verify and (zlib.crc32(payload) & 0xFFFFFFFF) != entry["crc32"]:
                raise IOError(f"checksum mismatch for {name} in {path}")
            if entry["transform"] == "quantize" and "q_elems" in entry:
                q = np.frombuffer(payload[: entry["q_elems"]], np.int8)
                scale = np.frombuffer(payload[entry["q_elems"] :], np.float32).reshape(-1, 1)
                arr = QuantizeInt8.dequantize((q.reshape(-1, entry["block"]), scale), entry)
            elif entry["transform"] == "compress":
                import zstandard

                raw = zstandard.ZstdDecompressor().decompress(payload)
                arr = np.frombuffer(raw, entry["dtype"]).reshape(entry["shape"])
            else:
                arr = np.frombuffer(payload, entry["dtype"]).reshape(entry["shape"])
            if shard is not None:
                out.append(jax.device_put(arr, shard))
            else:
                out.append(jax.numpy.asarray(arr).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def manifest(self, step: int) -> Dict[str, Any]:
        with open(os.path.join(self.directory, f"step_{step}", "manifest.json")) as f:
            return json.load(f)


class AsyncCheckpointer:
    """Overlap checkpoint writes with training: snapshot on caller thread,
    enforce + write on a worker. ``wait()`` joins outstanding saves (call
    before exit or before starting a save of the same step)."""

    def __init__(self, manager: CheckpointManager) -> None:
        self.manager = manager
        self._lock = threading.Lock()
        self._pending: List[threading.Thread] = []
        self.errors: List[BaseException] = []

    def save(self, step: int, state: PyTree, extra: Optional[Dict[str, Any]] = None) -> None:
        host_state = jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)), state)

        def work() -> None:
            try:
                self.manager.save(step, host_state, extra)
            except BaseException as exc:  # noqa: BLE001 — surfaced via .errors
                self.errors.append(exc)

        t = threading.Thread(target=work, daemon=True, name=f"paio-ckpt-{step}")
        with self._lock:
            self._pending.append(t)
        t.start()

    def wait(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, []
        for t in pending:
            t.join()
        if self.errors:
            raise self.errors[0]
