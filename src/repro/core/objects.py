"""Enforcement objects (paper §3.1, §3.4, Table 2).

An enforcement object is a self-contained, single-purposed mechanism holding
the I/O logic applied over requests. The paper ships two (``Noop`` and ``DRL``
— a dynamically-rate-limiting token bucket); we keep those paper-faithful and
add transformation objects (zstd compression, int8 quantization, checksums) —
the class of mechanisms the paper lists (§3.1 "data transformations") — plus a
priority scheduler used by the tail-latency use case.

API (Table 2, enforcement-object row):
  ``obj_init(s)``    → the constructor,
  ``obj_enf(ctx,r)`` → apply the mechanism, return a ``Result``,
  ``obj_config(s)``  → retune from an enforcement rule.
"""
from __future__ import annotations

import functools
import sys
import threading
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .clock import Clock, DEFAULT_CLOCK
from .context import Context


@dataclass(slots=True)
class Result:
    """Outcome of enforcing one request (paper §3.4).

    ``content`` is the (possibly transformed) request payload; ``None`` for
    context-only enforcement (performance-control objects never touch bytes —
    the paper's zero-copy fast path). ``wait_seconds`` reports scheduling delay
    imposed by performance-control objects, which feeds telemetry.

    ``slots=True``: Results are created once per enforced request, so their
    allocation cost is on the batched hot path.
    """

    content: Any = None
    wait_seconds: float = 0.0
    meta: Optional[Dict[str, Any]] = None


class EnforcementObject:
    """Base class. Subclasses must be thread-safe on ``obj_enf``."""

    #: human-readable kind, used by housekeeping rules
    kind: str = "abstract"

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        raise NotImplementedError

    def obj_enf_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        """Enforce a whole batch; elementwise equivalent to ``obj_enf``.

        Default falls back to per-item enforcement so every object is batch
        callable; hot objects override this to amortize locks, clock reads and
        byte-touching work across the batch.
        """
        if requests is None:
            return [self.obj_enf(ctx) for ctx in ctxs]
        return [self.obj_enf(ctx, r) for ctx, r in zip(ctxs, requests)]

    def obj_config(self, state: Dict[str, Any]) -> None:
        raise NotImplementedError

    def describe(self) -> Dict[str, Any]:
        return {"kind": self.kind}


class Noop(EnforcementObject):
    """Pass-through (paper §4.3). Optionally copies the buffer, which is what
    the paper's Fig-4 loop-back benchmark exercises."""

    kind = "noop"

    def __init__(self, copy_content: bool = False) -> None:
        self.copy_content = copy_content

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        if request is None or not self.copy_content:
            return Result(content=request)
        if isinstance(request, (bytes, bytearray, memoryview)):
            return Result(content=bytes(request))
        if isinstance(request, np.ndarray):
            return Result(content=request.copy())
        return Result(content=request)

    def obj_enf_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        if requests is None:
            return [Result() for _ in ctxs]
        if not self.copy_content:
            return list(map(Result, requests))  # C-level loop, no Python frame
        first = requests[0] if requests else None
        if type(first) is bytes and all(type(r) is bytes for r in requests):
            # bytes are immutable: bytes(r) is the identity (same as obj_enf),
            # so skip the conversion entirely; the all() guard keeps mixed
            # batches (None/ndarray/bytearray tails) on the per-item path
            return list(map(Result, requests))
        if isinstance(first, (bytearray, memoryview)) and all(
            isinstance(r, (bytes, bytearray, memoryview)) for r in requests
        ):
            # mutable buffers need a real copy: ONE bulk copy for the whole
            # batch, carved into independent immutable slices (no view into
            # the joined buffer survives, so nothing pins the batch)
            joined = b"".join(requests)
            out: List[Result] = []
            off = 0
            for r in requests:
                end = off + len(r)
                out.append(Result(joined[off:end]))
                off = end
            return out
        if isinstance(first, np.ndarray):
            # per-item C-level memcpys; deliberately NOT one np.stack carved
            # into views — a retained Result must not pin the whole batch
            return [
                self.obj_enf(c, r) if not isinstance(r, np.ndarray) else Result(r.copy())
                for c, r in zip(ctxs, requests)
            ]
        return [self.obj_enf(c, r) for c, r in zip(ctxs, requests)]

    def obj_config(self, state: Dict[str, Any]) -> None:
        if "copy_content" in state:
            self.copy_content = bool(state["copy_content"])


class TokenBucket:
    """Virtual-time pacing token bucket.

    Cumulative-debt formulation: each ``consume(n)`` debits ``n`` tokens under
    a lock and then sleeps exactly long enough for the refill to cover any
    deficit. This serializes admission decisions (so concurrent consumers
    cannot over-admit) while keeping the lock hold time O(1) and never held
    across a sleep. Refill is continuous (the paper's discrete *refill period*
    is the granularity at which a controller would adjust; continuous refill is
    the limit behaviour and strictly fairer).

    Invariant (tested by property tests): for any sequence of consumes, the
    total admitted by time ``T`` is ≤ ``capacity + rate·(T - t0)``.
    """

    def __init__(self, rate: float, capacity: float, clock: Clock = DEFAULT_CLOCK) -> None:
        if rate <= 0:
            raise ValueError("rate must be positive")
        self._rate = float(rate)
        self._capacity = float(max(capacity, 1.0))
        self._tokens = self._capacity
        self._clock = clock
        self._last = clock.now()
        self._lock = threading.Lock()

    # -- accessors -------------------------------------------------------
    @property
    def rate(self) -> float:
        return self._rate

    @property
    def capacity(self) -> float:
        return self._capacity

    def _refill_locked(self, now: float) -> None:
        if now > self._last:
            self._tokens = min(self._capacity, self._tokens + (now - self._last) * self._rate)
            self._last = now

    # -- operations ------------------------------------------------------
    def set_rate(self, rate: float, capacity: Optional[float] = None) -> None:
        with self._lock:
            now = self._clock.now()
            self._refill_locked(now)
            self._rate = float(max(rate, 1e-9))
            if capacity is not None:
                self._capacity = float(max(capacity, 1.0))
                self._tokens = min(self._tokens, self._capacity)

    def try_consume(self, n: float) -> bool:
        with self._lock:
            now = self._clock.now()
            self._refill_locked(now)
            if self._tokens >= n:
                self._tokens -= n
                return True
            return False

    #: max single sleep while paying off deficit — keeps blocked consumers
    #: responsive to dynamic rate changes (enf_rules) within one slice
    WAIT_SLICE = 0.05

    def consume(self, n: float) -> float:
        """Blocking consume; returns the wait imposed (seconds).

        The debit is committed once (serializing admission under the lock);
        the deficit is then paid off in bounded sleep slices, re-reading the
        current rate each slice so a control-plane rate increase takes effect
        mid-wait instead of leaving the consumer stranded on a stale rate.
        """
        with self._lock:
            now = self._clock.now()
            self._refill_locked(now)
            self._tokens -= n
            deficit = -self._tokens if self._tokens < 0 else 0.0
        waited = 0.0
        while deficit > 1e-9:
            with self._lock:
                rate = self._rate
            step = min(deficit / rate, self.WAIT_SLICE)
            self._clock.sleep(step)
            deficit -= step * rate  # credited at the rate in effect this slice
            waited += step
        return waited

    def available(self) -> float:
        with self._lock:
            self._refill_locked(self._clock.now())
            return self._tokens


class DRL(EnforcementObject):
    """Dynamic Rate Limiter — the paper's token-bucket object (§4.3).

    The request cost model is the paper's: one token per byte (constant cost);
    the surrounding control loop continuously re-calibrates the rate so the
    observed throughput converges to the policy goal, which absorbs cost-model
    error (§4.3). ``obj_config`` implements the paper's ``rate(r)`` routine:
    the bucket size is derived from the rate and the refill period.
    """

    kind = "drl"

    def __init__(
        self,
        rate: float,
        refill_period: float = 0.1,
        clock: Clock = DEFAULT_CLOCK,
        min_rate: float = 1.0,
    ) -> None:
        self.refill_period = float(refill_period)
        self.min_rate = float(min_rate)
        rate = max(float(rate), self.min_rate)
        self._bucket = TokenBucket(rate=rate, capacity=rate * self.refill_period, clock=clock)

    @property
    def rate(self) -> float:
        return self._bucket.rate

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        wait = self._bucket.consume(max(ctx.size, 1))
        return Result(content=request, wait_seconds=wait)

    def obj_enf_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        """Admit the whole batch with ONE bucket consume: one lock acquisition,
        one clock read, and a single computed sleep for the batch's cumulative
        debt. The admitted ≤ capacity + rate·(T − t0) invariant is preserved
        exactly — an atomic consume of ``sum(sizes)`` debits the same tokens a
        sequential per-request walk would. The imposed wait is attributed to
        requests proportionally to their cost so telemetry sums are unchanged.
        """
        sizes = [max(c.size, 1) for c in ctxs]
        total = float(sum(sizes))
        wait = self._bucket.consume(total)
        if requests is None:
            requests = [None] * len(ctxs)
        if wait == 0.0:
            return [Result(content=r) for r in requests]
        per_token = wait / total
        return [
            Result(content=r, wait_seconds=s * per_token) for r, s in zip(requests, sizes)
        ]

    def obj_config(self, state: Dict[str, Any]) -> None:
        if "refill_period" in state:
            self.refill_period = float(state["refill_period"])
        if "rate" in state:
            rate = max(float(state["rate"]), self.min_rate)
            self._bucket.set_rate(rate, capacity=rate * self.refill_period)

    def describe(self) -> Dict[str, Any]:
        return {"kind": self.kind, "rate": self.rate, "refill_period": self.refill_period}


class PriorityGate(EnforcementObject):
    """Priority admission gate: requests above ``threshold`` pass immediately;
    lower-priority requests wait while any higher-priority request is inside a
    configurable window. A lightweight I/O-scheduler enforcement object used to
    emulate SILK-style preemption *outside* the targeted engine."""

    kind = "priority_gate"

    def __init__(self, priority_of: Optional[Dict[str, int]] = None, clock: Clock = DEFAULT_CLOCK) -> None:
        self.priority_of = dict(priority_of or {})
        self._clock = clock
        self._lock = threading.Lock()
        self._last_high = 0.0
        self.low_hold = 0.005  # seconds a low-priority req yields when high active

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        prio = self.priority_of.get(ctx.request_context, 0)
        now = self._clock.now()
        waited = 0.0
        if prio > 0:
            with self._lock:
                self._last_high = now
            return Result(content=request)
        # low priority: yield while a high-priority request was seen recently
        for _ in range(32):
            with self._lock:
                recent = (self._clock.now() - self._last_high) < self.low_hold
            if not recent:
                break
            self._clock.sleep(self.low_hold)
            waited += self.low_hold
        return Result(content=request, wait_seconds=waited)

    def obj_enf_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        """Sorted batch admission: all high-priority requests are admitted
        first under a single lock/clock read; the low-priority remainder then
        yields ONCE for the whole batch (instead of each low request spinning
        on the gate separately). Result order matches submission order.
        """
        if requests is None:
            requests = [None] * len(ctxs)
        prios = [self.priority_of.get(c.request_context, 0) for c in ctxs]
        any_high = any(p > 0 for p in prios)
        if any_high:
            with self._lock:
                self._last_high = self._clock.now()
        waited = 0.0
        if any(p <= 0 for p in prios):
            for _ in range(32):
                with self._lock:
                    recent = (self._clock.now() - self._last_high) < self.low_hold
                if not recent:
                    break
                self._clock.sleep(self.low_hold)
                waited += self.low_hold
        # the single shared yield is attributed to the FIRST low-priority
        # request (as in the sequential walk, where later lows find the
        # window already expired) so summed wait telemetry is not inflated
        out: List[Result] = []
        first_low = True
        for r, p in zip(requests, prios):
            if p > 0:
                out.append(Result(content=r))
            elif first_low:
                out.append(Result(content=r, wait_seconds=waited))
                first_low = False
            else:
                out.append(Result(content=r))
        return out

    def obj_config(self, state: Dict[str, Any]) -> None:
        if "priority_of" in state:
            self.priority_of.update(state["priority_of"])
        if "low_hold" in state:
            self.low_hold = float(state["low_hold"])


class Compress(EnforcementObject):
    """zstd data-transformation object (paper §3.1 "data transformations").

    Used on the checkpoint write path; ``level`` is tunable by ``enf_rule`` so
    the control plane can trade CPU for bytes when the storage tier is the
    bottleneck.
    """

    kind = "compress"

    def __init__(self, level: int = 3) -> None:
        import zstandard

        self._zstd = zstandard
        self.level = int(level)
        self._cctx = zstandard.ZstdCompressor(level=self.level)

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        if request is None:
            return Result(content=None)
        buf = request.tobytes() if isinstance(request, np.ndarray) else bytes(request)
        out = self._cctx.compress(buf)
        return Result(content=out, meta={"raw_bytes": len(buf), "compressed_bytes": len(out)})

    def obj_config(self, state: Dict[str, Any]) -> None:
        if "level" in state:
            self.level = int(state["level"])
            self._cctx = self._zstd.ZstdCompressor(level=self.level)


class Decompress(EnforcementObject):
    kind = "decompress"

    def __init__(self) -> None:
        import zstandard

        self._dctx = zstandard.ZstdDecompressor()

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        if request is None:
            return Result(content=None)
        return Result(content=self._dctx.decompress(bytes(request)))

    def obj_config(self, state: Dict[str, Any]) -> None:
        pass


class Checksum(EnforcementObject):
    """CRC32 integrity transformation — checksums are recorded in ``meta`` so a
    checkpoint manifest can verify shards on restore (fault-tolerance path)."""

    kind = "checksum"

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        if request is None:
            return Result(content=None)
        buf = request.tobytes() if isinstance(request, np.ndarray) else bytes(request)
        return Result(content=request, meta={"crc32": zlib.crc32(buf) & 0xFFFFFFFF})

    def obj_enf_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        if requests is None:
            return [Result() for _ in ctxs]
        # zlib.crc32 is a C single-pass; the batch win is skipping per-request
        # routing/stats, so a tight loop here is the whole cost.
        crc = zlib.crc32
        out: List[Result] = []
        for r in requests:
            if r is None:
                out.append(Result())
                continue
            buf = r.tobytes() if isinstance(r, np.ndarray) else bytes(r)
            out.append(Result(content=r, meta={"crc32": crc(buf) & 0xFFFFFFFF}))
        return out

    def obj_config(self, state: Dict[str, Any]) -> None:
        pass


def _quantize_blocks_numpy(blocks: np.ndarray):
    """[M, block] float32 → (int8 [M, block], float32 scales [M, 1]). One
    vectorized pass — shared by the per-request and batched quantize paths."""
    scale = np.maximum(np.abs(blocks).max(axis=1, keepdims=True), 1e-12) / 127.0
    q = np.clip(np.rint(blocks / scale), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32)


def _process_runs_jax_on_tpu() -> bool:
    """True when this process has already initialized JAX on a TPU.

    Never initializes a backend itself: a process that has not touched JAX
    (a forked stage server, say) stays off the chip, which belongs to the
    job's own process.
    """
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return False
    import jax

    return jax.default_backend() == "tpu"


class QuantizeInt8(EnforcementObject):
    """Host-side int8 symmetric per-block quantization transformation.

    The device-side twin (Pallas kernel, ``repro.kernels.quantize``) runs on
    TPU for gradient compression; this object serves the checkpoint write
    path. Block size is per-row groups of ``block`` elements.

    ``obj_enf_batch`` packs the whole batch into one ``[M, block]`` matrix and
    quantizes it with a single fused call — the Pallas rows kernel, else one
    vectorized numpy pass — instead of N Python-level loops. ``use_pallas``
    None picks the kernel only where this process already runs JAX on a TPU;
    True always runs it (``interpret=True`` in the Pallas interpreter). A
    kernel failure propagates: it never falls back to numpy.
    """

    kind = "quantize_int8"

    def __init__(
        self, block: int = 256, use_pallas: Optional[bool] = None, interpret: bool = False
    ) -> None:
        self.block = int(block)
        self.use_pallas = use_pallas
        self.interpret = bool(interpret)
        #: batches quantized by the Pallas kernel (the numpy path is not counted)
        self.kernel_calls = 0
        self._pallas_rows = None  # resolved lazily; jax import stays off core

    def _resolve_pallas(self):
        if self._pallas_rows is None:
            # lane-aligned blocks only; otherwise the tile padding would
            # change per-block scales vs the numpy semantics
            aligned = self.block % 128 == 0
            if self.use_pallas and not aligned:
                raise ValueError(f"Pallas quantize needs a block multiple of 128, got {self.block}")
            want = self.use_pallas
            if want is None:
                want = aligned and _process_runs_jax_on_tpu()
            if want:
                from repro.kernels.quantize.ops import quantize_rows_int8

                self._pallas_rows = functools.partial(quantize_rows_int8, interpret=self.interpret)
            else:
                self._pallas_rows = False
        return self._pallas_rows or None

    def _quantize_blocks(self, blocks: np.ndarray):
        rows = self._resolve_pallas()
        if rows is None:
            return _quantize_blocks_numpy(blocks)
        q, s = rows(blocks)
        self.kernel_calls += 1
        return np.asarray(q), np.asarray(s)

    def obj_enf(self, ctx: Context, request: Any = None) -> Result:
        if request is None:
            return Result(content=None)
        arr = np.asarray(request)
        flat = arr.reshape(-1).astype(np.float32)
        pad = (-flat.size) % self.block
        if pad:
            flat = np.concatenate([flat, np.zeros(pad, np.float32)])
        q, scale = self._quantize_blocks(flat.reshape(-1, self.block))
        return Result(
            content=(q, scale),
            meta={"shape": arr.shape, "dtype": str(arr.dtype), "pad": pad, "block": self.block},
        )

    def obj_enf_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        if requests is None:
            return [Result() for _ in ctxs]
        arrs = [None if r is None else np.asarray(r) for r in requests]
        flats = [
            None if a is None else a.reshape(-1).astype(np.float32, copy=False) for a in arrs
        ]
        pads = [None if f is None else (-f.size) % self.block for f in flats]
        sizes = {f.size + p for f, p in zip(flats, pads) if f is not None}
        if len(sizes) != 1:  # ragged batch: per-item path (still one kernel each)
            return [self.obj_enf(c, r) for c, r in zip(ctxs, requests)]
        padded = sizes.pop()
        live = [i for i, f in enumerate(flats) if f is not None]
        packed = np.zeros((len(live), padded), np.float32)
        for row, i in enumerate(live):
            packed[row, : flats[i].size] = flats[i]
        blocks_per = padded // self.block
        q_all, s_all = self._quantize_blocks(packed.reshape(-1, self.block))
        q_all = q_all.reshape(len(live), blocks_per, self.block)
        s_all = s_all.reshape(len(live), blocks_per, 1)
        out: List[Result] = [Result() for _ in ctxs]
        for row, i in enumerate(live):
            # per-row copies so a retained Result doesn't pin the batch output
            out[i] = Result(
                content=(q_all[row].copy(), s_all[row].copy()),
                meta={
                    "shape": arrs[i].shape,
                    "dtype": str(arrs[i].dtype),
                    "pad": pads[i],
                    "block": self.block,
                },
            )
        return out

    @staticmethod
    def dequantize(content, meta) -> np.ndarray:
        q, scale = content
        flat = (q.astype(np.float32) * scale).reshape(-1)
        if meta["pad"]:
            flat = flat[: flat.size - meta["pad"]]
        return flat.reshape(meta["shape"]).astype(meta["dtype"])

    def obj_config(self, state: Dict[str, Any]) -> None:
        if "block" in state:
            self.block = int(state["block"])


#: registry used by housekeeping rules (create-object by kind)
OBJECT_KINDS = {
    "noop": Noop,
    "drl": DRL,
    "priority_gate": PriorityGate,
    "compress": Compress,
    "decompress": Decompress,
    "checksum": Checksum,
    "quantize_int8": QuantizeInt8,
}
