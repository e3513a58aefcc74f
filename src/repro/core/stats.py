"""Per-workflow statistics counters (paper §4.3).

Channels register every enforced request. ``collect`` (the control-plane call)
returns windowed metrics — ops, bytes, and mean throughput since the previous
collection — and resets the window, exactly the semantics the paper's feedback
loops (Algorithms 1–2) rely on.

Counters are updated on the stage hot path, so the fast path is two integer
adds under a lock that is never held across I/O.

Wait telemetry is a **fixed-bucket mergeable histogram**
(:mod:`repro.telemetry.histogram`): every enforced request contributes one
bucket increment (batches contribute per-op, not a collapsed mean), snapshots
carry the window's bucket counts, and those counts merge *exactly* — across
consecutive windows (algorithm cadence gating) and across stages (the fleet
metric plane's ``@fleet.*`` views).

All window arithmetic runs on the injected :class:`Clock` (monotonic by
default — ``time.monotonic_ns``): a wall-clock step (NTP, suspend/resume)
cannot stretch or invert a collect window. ``time.time()`` is reserved for
user-facing timestamps and appears nowhere in interval math.
"""
from __future__ import annotations

import threading
from bisect import bisect_left
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from repro.telemetry.histogram import (
    NBUCKETS,
    WAIT_BOUNDS_MS,
    merge_counts,
    quantile_from_counts,
)

from .clock import Clock, DEFAULT_CLOCK


@dataclass
class StatsSnapshot:
    """Windowed metrics returned by ``collect`` for one channel."""

    channel: str
    ops: int
    bytes: int
    window_seconds: float
    #: mean throughput over the window, bytes/s
    throughput: float
    #: mean op rate over the window, ops/s
    iops: float
    cumulative_ops: int = 0
    cumulative_bytes: int = 0
    #: requests currently blocked inside enforcement objects — lets control
    #: algorithms treat a starved-but-waiting flow as active
    inflight: int = 0
    #: total scheduling delay imposed by enforcement objects over the window;
    #: the policy trigger engine derives per-op wait (a latency proxy) from it
    wait_seconds: float = 0.0
    #: per-op imposed-wait percentiles (ms) over the window's histogram; an
    #: idle window holds the previous window's values (hold-last) so a
    #: one-tick traffic gap does not read as a latency collapse
    wait_p50_ms: float = 0.0
    wait_p95_ms: float = 0.0
    wait_p99_ms: float = 0.0
    #: the window's wait histogram: per-bucket op counts over the shared
    #: WAIT_BOUNDS_MS layout (+ one +Inf bucket). Empty tuple = no histogram
    #: (old-wire snapshots); merges exactly across windows and stages
    wait_hist: Tuple[int, ...] = ()
    #: filter-plane window counters keyed by dotted metric suffix (e.g.
    #: ``cache.hits``). Every value is *summable*: extras add across
    #: consecutive windows and across stages/shards, so ratio metrics (hit
    #: rates) are derived control-plane side from the merged raw counts,
    #: never averaged from pre-divided members
    extras: Dict[str, float] = field(default_factory=dict)
    #: total scheduling delay imposed since the channel was created: unlike
    #: ``wait_seconds`` no collect resets it, so any reader (not only the
    #: control plane that owns the collect ticks) can take a run's total as
    #: the difference of two snapshots. Old-wire peers read 0
    cumulative_wait_seconds: float = 0.0

    def __post_init__(self) -> None:
        # v1 JSON transports round-trip tuples as lists; normalize so wire
        # equality and merge arithmetic hold regardless of the path taken
        if not isinstance(self.wait_hist, tuple):
            self.wait_hist = tuple(self.wait_hist)
        if not isinstance(self.extras, dict):
            self.extras = dict(self.extras)

    @property
    def mean_wait_ms(self) -> float:
        """Mean imposed wait per op over the window, milliseconds."""
        return (self.wait_seconds / self.ops) * 1e3 if self.ops else 0.0


def _sum_extras(dicts: Iterable[Dict[str, float]]) -> Dict[str, float]:
    """Key-wise sum of extras maps (all extras are summable by contract)."""
    out: Dict[str, float] = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + v
    return out


def _hist_percentiles(counts: Sequence[int]) -> Tuple[float, float, float]:
    return (
        quantile_from_counts(counts, 0.5),
        quantile_from_counts(counts, 0.95),
        quantile_from_counts(counts, 0.99),
    )


class ChannelStats:
    __slots__ = (
        "_lock", "_clock", "_ops", "_bytes", "_cum_ops", "_cum_bytes", "_cum_wait",
        "_window_start", "_inflight", "_wait", "_hist", "_last_percentiles", "name"
    )

    def __init__(self, name: str, clock: Clock = DEFAULT_CLOCK) -> None:
        self.name = name
        self._lock = threading.Lock()
        self._clock = clock
        self._ops = 0
        self._bytes = 0
        self._cum_ops = 0
        self._cum_bytes = 0
        self._cum_wait = 0.0
        self._inflight = 0
        self._wait = 0.0
        #: windowed wait histogram (bucket counts over WAIT_BOUNDS_MS), reset
        #: by collect like the other window counters; plain list + precomputed
        #: bucket index keeps the hot path to one increment under the lock
        self._hist: List[int] = [0] * NBUCKETS
        #: hold-last percentiles for idle windows
        self._last_percentiles: Tuple[float, float, float] = (0.0, 0.0, 0.0)
        self._window_start = clock.now()

    def begin_op(self) -> None:
        with self._lock:
            self._inflight += 1

    def begin_ops(self, n: int) -> None:
        """Batch twin of ``begin_op``: one lock acquisition for ``n`` requests."""
        with self._lock:
            self._inflight += n

    def record(self, size: int, wait: float = 0.0) -> None:
        # bucket resolution is pure (bisect over a shared tuple) — keep it
        # outside the lock so the locked section stays a handful of adds
        idx = bisect_left(WAIT_BOUNDS_MS, wait * 1e3)
        with self._lock:
            self._ops += 1
            self._bytes += size
            self._hist[idx] += 1
            if wait:
                self._wait += wait
            if self._inflight > 0:
                self._inflight -= 1

    def record_batch(
        self,
        ops: int,
        nbytes: int,
        wait: float = 0.0,
        waits: Optional[Sequence[float]] = None,
    ) -> None:
        """Register ``ops`` enforced requests totalling ``nbytes`` under one
        lock acquisition — the batch hot path pays lock traffic per *batch*,
        not per request, while ``collect`` windows stay exactly equivalent to
        ``ops`` individual ``record`` calls.

        ``waits`` (per-op wait seconds, len == ops) feeds the histogram one
        bucket increment per request — batched and sequential enforcement of
        the same latency distribution produce identical percentiles. The
        increments are folded into a local vector outside the lock, so the
        locked section is O(buckets), not O(ops). Without ``waits``, the
        total ``wait`` contributes ``ops`` weighted observations at the
        batch mean (the best a total can say)."""
        inc: Optional[List[int]] = None
        if waits is not None:
            inc = [0] * NBUCKETS
            bounds = WAIT_BOUNDS_MS
            total = 0.0
            for w in waits:
                inc[bisect_left(bounds, w * 1e3)] += 1
                total += w
            wait = total
        with self._lock:
            self._ops += ops
            self._bytes += nbytes
            if inc is not None:
                hist = self._hist
                for i, c in enumerate(inc):
                    if c:
                        hist[i] += c
            elif ops:
                self._hist[bisect_left(WAIT_BOUNDS_MS, (wait / ops) * 1e3)] += ops
            if wait:
                self._wait += wait
            if self._inflight > 0:
                self._inflight = self._inflight - ops if self._inflight >= ops else 0

    def collect(self) -> StatsSnapshot:
        now = self._clock.now()
        with self._lock:
            window = max(now - self._window_start, 1e-9)
            ops, nbytes, wait = self._ops, self._bytes, self._wait
            cum_ops, cum_bytes = self._cum_ops + ops, self._cum_bytes + nbytes
            cum_wait = self._cum_wait + wait
            inflight = self._inflight
            hist = tuple(self._hist)
            self._cum_ops, self._cum_bytes, self._cum_wait = cum_ops, cum_bytes, cum_wait
            self._ops = 0
            self._bytes = 0
            self._wait = 0.0
            self._hist = [0] * NBUCKETS
            self._window_start = now
        if ops:
            percentiles = _hist_percentiles(hist)
            with self._lock:
                self._last_percentiles = percentiles
        else:
            percentiles = self._last_percentiles
        return StatsSnapshot(
            channel=self.name,
            ops=ops,
            bytes=nbytes,
            window_seconds=window,
            throughput=nbytes / window,
            iops=ops / window,
            cumulative_ops=cum_ops,
            cumulative_bytes=cum_bytes,
            inflight=inflight,
            wait_seconds=wait,
            wait_p50_ms=percentiles[0],
            wait_p95_ms=percentiles[1],
            wait_p99_ms=percentiles[2],
            wait_hist=hist,
            cumulative_wait_seconds=cum_wait,
        )


def merge_snapshots(a: StatsSnapshot, b: StatsSnapshot) -> StatsSnapshot:
    """Combine two consecutive windows of the same channel into one.

    Counters add, the window spans both, rates are recomputed over the
    combined window; point-in-time fields (cumulative totals, inflight) take
    the later snapshot's values. Wait histograms merge exactly (bucket counts
    add), so the combined percentiles are computed, not approximated; only
    when neither window carries a histogram (old-wire peers) do the later
    snapshot's percentiles pass through. Used by the control plane to
    accumulate collect ticks for algorithms stepping slower than the loop.
    """
    window = a.window_seconds + b.window_seconds
    ops = a.ops + b.ops
    nbytes = a.bytes + b.bytes
    hist = merge_counts(a.wait_hist, b.wait_hist)
    if any(hist):
        p50, p95, p99 = _hist_percentiles(hist)
    else:
        p50, p95, p99 = b.wait_p50_ms, b.wait_p95_ms, b.wait_p99_ms
    return StatsSnapshot(
        channel=b.channel,
        ops=ops,
        bytes=nbytes,
        window_seconds=window,
        throughput=nbytes / max(window, 1e-9),
        iops=ops / max(window, 1e-9),
        cumulative_ops=b.cumulative_ops,
        cumulative_bytes=b.cumulative_bytes,
        inflight=b.inflight,
        wait_seconds=a.wait_seconds + b.wait_seconds,
        wait_p50_ms=p50,
        wait_p95_ms=p95,
        wait_p99_ms=p99,
        wait_hist=hist,
        extras=_sum_extras((a.extras, b.extras)),
        cumulative_wait_seconds=b.cumulative_wait_seconds,
    )


def merge_parallel(snaps: Iterable[StatsSnapshot], channel: str) -> StatsSnapshot:
    """Fold *parallel* windows (same channel name on different stages, one
    collect tick) into a fleet view of the channel.

    Extensive counters (ops, bytes, waits, cumulative totals, inflight) and
    rates sum across members; the window spans the longest member window (the
    windows overlap in time — adding them would halve every rate). Wait
    histograms merge exactly, so ``<flow>@fleet.p99`` is computed from the
    union of every member's per-op observations; members without histograms
    (old-wire) fall back to a max-over-members tail bound.
    """
    snaps = list(snaps)
    ops = sum(s.ops for s in snaps)
    nbytes = sum(s.bytes for s in snaps)
    hist: Tuple[int, ...] = ()
    for s in snaps:
        hist = merge_counts(hist, s.wait_hist)
    if any(hist):
        p50, p95, p99 = _hist_percentiles(hist)
    else:
        p50 = max((s.wait_p50_ms for s in snaps), default=0.0)
        p95 = max((s.wait_p95_ms for s in snaps), default=0.0)
        p99 = max((s.wait_p99_ms for s in snaps), default=0.0)
    return StatsSnapshot(
        channel=channel,
        ops=ops,
        bytes=nbytes,
        window_seconds=max((s.window_seconds for s in snaps), default=0.0),
        throughput=sum(s.throughput for s in snaps),
        iops=sum(s.iops for s in snaps),
        cumulative_ops=sum(s.cumulative_ops for s in snaps),
        cumulative_bytes=sum(s.cumulative_bytes for s in snaps),
        inflight=sum(s.inflight for s in snaps),
        wait_seconds=sum(s.wait_seconds for s in snaps),
        wait_p50_ms=p50,
        wait_p95_ms=p95,
        wait_p99_ms=p99,
        wait_hist=hist,
        extras=_sum_extras(s.extras for s in snaps),
        cumulative_wait_seconds=sum(s.cumulative_wait_seconds for s in snaps),
    )


def fleet_view(stats: Mapping[str, "StageStats"]) -> "StageStats":
    """Fold one collect tick's member snapshots into the fleet view: every
    channel name seen on any stage gets one merged snapshot spanning all its
    member instances (``scope: global`` flows instantiate the same channel
    name on every stage, so the fleet channel IS the flow). The control
    plane's policy runtime publishes this under the ``@fleet`` pseudo-stage
    (``paio_fleet_*`` metric families)."""
    by_channel: Dict[str, List[StatsSnapshot]] = {}
    for st in stats.values():
        for name, snap in st.per_channel.items():
            by_channel.setdefault(name, []).append(snap)
    return StageStats(
        per_channel={
            name: (snaps[0] if len(snaps) == 1 else merge_parallel(snaps, name))
            for name, snaps in by_channel.items()
        }
    )


@dataclass
class StageStats:
    """Aggregate view over all channels of a stage."""

    per_channel: Dict[str, StatsSnapshot] = field(default_factory=dict)

    def merged_into(self, acc: "StageStats") -> "StageStats":
        """Fold this (newer) window into accumulator ``acc``."""
        out = dict(acc.per_channel)
        for name, snap in self.per_channel.items():
            prev = out.get(name)
            out[name] = snap if prev is None else merge_snapshots(prev, snap)
        return StageStats(per_channel=out)

    @property
    def total_bytes(self) -> int:
        return sum(s.bytes for s in self.per_channel.values())

    @property
    def total_ops(self) -> int:
        return sum(s.ops for s in self.per_channel.values())

    def throughput_of(self, channel: str) -> float:
        snap = self.per_channel.get(channel)
        return snap.throughput if snap else 0.0
