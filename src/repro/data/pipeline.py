"""Input pipeline with a PAIO stage on the read path.

Every shard read flows through an :class:`ArrayInstance` with the ``fg_fetch``
request context (the *foreground flow* of the training job's I/O stack —
paper §5 mapping). The control plane observes the pipeline's bandwidth via the
stage's statistics and allocates leftover bandwidth to background flows
(checkpoints, eval) — PAIO's tail-latency policy applied to training.

The pipeline prefetches on a background thread into a bounded queue so the
device never blocks on storage unless the storage is genuinely saturated —
which is exactly the condition the control plane reacts to.
"""
from __future__ import annotations

import os
import queue
import threading
from typing import Iterator, Optional

import numpy as np

from repro.core import FG_FETCH, ArrayInstance, RequestType, Stage, propagate_context
from repro.telemetry.spans import span


class SyntheticTokenSource:
    """Deterministic synthetic tokens (seeded per batch index).

    Tokens follow a Zipf-like unigram distribution so a model has learnable
    structure (loss drops from ln(V) toward the source entropy) — uniform
    noise would make smoke-training flat.
    """

    def __init__(self, vocab: int, batch: int, seq: int, seed: int = 0) -> None:
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed
        ranks = np.arange(vocab, dtype=np.float64)
        p = 1.0 / (ranks + 5.0)
        self._p = p / p.sum()

    def read(self, index: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed + index)
        flat = rng.choice(self.vocab, size=self.batch * self.seq, p=self._p)
        return flat.reshape(self.batch, self.seq).astype(np.int32)

    @property
    def nbytes_per_batch(self) -> int:
        return self.batch * self.seq * 4


#: request context carried by dataset-preparation shard writes (free-form
#: context string, paper §3.3 — lets a policy throttle shard prep against
#: the foreground fetch flow)
DATA_PREP = "bg_data_prep"


class FileTokenSource:
    """Memory-mapped token shards on disk (one flat int32 stream per shard)."""

    def __init__(self, paths: list[str], batch: int, seq: int) -> None:
        self.paths = list(paths)
        self.batch, self.seq = batch, seq
        self._maps = [np.memmap(p, dtype=np.int32, mode="r") for p in self.paths]
        self._sizes = [m.shape[0] for m in self._maps]

    @staticmethod
    def write_shard(path: str, tokens: np.ndarray) -> None:
        arr = np.asarray(tokens, np.int32)
        with open(path, "wb") as f:
            f.write(arr.tobytes())
            f.flush()
            os.fsync(f.fileno())

    @staticmethod
    def write_shards(
        paths: list[str],
        token_arrays: list[np.ndarray],
        stage: Optional[Stage] = None,
        channel_context: str = DATA_PREP,
    ) -> None:
        """Write a shard set through the Instance batch submit API.

        With a stage attached, all shard writes are admitted as ONE
        ``enforce_batch`` pass (per-write routing/stats/rate-limit cost paid
        once per burst) under the ``bg_data_prep`` request context, so a
        control-plane policy can cap shard preparation against foreground
        fetches. Without a stage this is a plain loop over ``write_shard``.
        """
        if len(paths) != len(token_arrays):
            raise ValueError(f"{len(paths)} paths vs {len(token_arrays)} arrays")
        arrays = [np.asarray(t, np.int32) for t in token_arrays]
        if stage is None:
            for path, arr in zip(paths, arrays):
                FileTokenSource.write_shard(path, arr)
            return
        instance = ArrayInstance(stage)
        with propagate_context(channel_context):
            instance.on_write_batch(
                arrays, lambda i, payload: FileTokenSource.write_shard(paths[i], payload)
            )

    def read(self, index: int) -> np.ndarray:
        need = self.batch * self.seq
        shard = self._maps[index % len(self._maps)]
        n_windows = max(shard.shape[0] - need, 1)
        off = (index * 9973) % n_windows
        return np.array(shard[off : off + need]).reshape(self.batch, self.seq)

    @property
    def nbytes_per_batch(self) -> int:
        return self.batch * self.seq * 4


class DataPipeline:
    """Prefetching loader; reads are enforced by the given PAIO stage."""

    def __init__(
        self,
        source,
        stage: Optional[Stage] = None,
        prefetch: int = 2,
        channel_context: str = FG_FETCH,
    ) -> None:
        self.source = source
        self.instance = ArrayInstance(stage) if stage is not None else None
        self.channel_context = channel_context
        self._queue: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._index = 0
        self._thread: Optional[threading.Thread] = None

    # -- synchronous read (used by tests and the quickstart) ---------------
    def read_batch(self, index: int) -> np.ndarray:
        with span("paio.data.fetch", batch=index):
            if self.instance is None:
                return self.source.read(index)
            with propagate_context(self.channel_context):
                return self.instance.on_read(self.source.nbytes_per_batch, lambda: self.source.read(index))

    # -- background prefetch ------------------------------------------------
    def start(self) -> "DataPipeline":
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True, name="paio-data-pipeline")
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            batch = self.read_batch(self._index)
            self._index += 1
            while not self._stop.is_set():
                try:
                    self._queue.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self) -> Iterator[np.ndarray]:
        return self

    def __next__(self) -> np.ndarray:
        if self._thread is None:
            batch = self.read_batch(self._index)
            self._index += 1
            return batch
        return self._queue.get()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            while not self._queue.empty():  # unblock producer
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
            self._thread.join(timeout=5.0)
            self._thread = None
