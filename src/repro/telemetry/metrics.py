"""Telemetry: the shared metric registry.

The registry started as a policy-engine internal (the trigger engine samples
it by dotted name); it is now the process-wide observability surface: stage /
channel / serve statistics publish into it as **gauges**, **counters**,
**windowed summaries** (p50/p95/p99 over a bounded sample window) and
**mergeable histograms** (:mod:`repro.telemetry.histogram` — cumulative
fixed-bucket counts, the fleet metric plane's exchange format), and the
:mod:`repro.telemetry.exporter` renders one coherent ``collect()`` of it in
Prometheus text exposition for scraping from outside the process.

Two naming layers coexist deliberately:

* the *registry name* is a dotted string (``serve.tenant_a.wait_ms``) —
  stable, addressable from policy trigger predicates;
* the *export identity* is an optional descriptor (family + labels, e.g.
  ``paio_channel_wait_ms{stage="serve",channel="tenant_a"}``) attached via
  :meth:`MetricRegistry.describe`; undescribed metrics export under their
  sanitized dotted name prefixed ``paio_``.
"""
from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Mapping, Optional, Sequence, Tuple

from .histogram import Histogram, quantile_from_counts

#: quantiles summaries report, as (label, fraction)
SUMMARY_QUANTILES: Tuple[Tuple[str, float], ...] = (("0.5", 0.5), ("0.95", 0.95), ("0.99", 0.99))
#: registry-name suffix ↔ quantile label for summary sampling
_SUMMARY_KEYS = (("p50", 0.5), ("p95", 0.95), ("p99", 0.99))


def quantile(sorted_values: List[float], q: float) -> float:
    """Nearest-rank quantile over an already-sorted list (0 when empty)."""
    if not sorted_values:
        return 0.0
    k = min(int(q * len(sorted_values)), len(sorted_values) - 1)
    return sorted_values[k]


@dataclass
class MetricSample:
    """One metric in a registry ``collect()``: enough to render any
    exposition format without reaching back into the registry."""

    name: str  #: dotted registry name
    kind: str  #: "gauge" | "counter" | "summary" | "histogram"
    value: float = 0.0  #: gauge/counter value; summaries use the fields below
    family: Optional[str] = None  #: export family name (None → derived)
    labels: Dict[str, str] = field(default_factory=dict)
    quantiles: Dict[str, float] = field(default_factory=dict)  #: summaries only
    count: int = 0  #: summaries/histograms: total observations ever
    sum: float = 0.0  #: summaries/histograms: total of all observations ever
    #: histograms only: ``(le_bound, cumulative_count)`` rows for the finite
    #: bounds (the ``+Inf`` row is ``count``)
    buckets: List[Tuple[float, int]] = field(default_factory=list)


class _Summary:
    """Bounded sliding window of observations + cumulative count/sum.

    Percentiles are computed over the retained window (the last ``window``
    observations); ``count``/``sum`` are cumulative since creation, matching
    Prometheus summary semantics.
    """

    __slots__ = ("buf", "count", "sum")

    def __init__(self, window: int) -> None:
        self.buf: Deque[float] = deque(maxlen=window)
        self.count = 0
        self.sum = 0.0

    def observe(self, value: float) -> None:
        self.buf.append(value)
        self.count += 1
        self.sum += value


class MetricRegistry:
    """Named metrics the control plane samples and the exporter renders.

    Five metric shapes:

    * **source** — a zero-arg callable returning the current value (pull);
    * **gauge** — a pushed point-in-time value (``set_gauge``);
    * **counter** — a pushed monotonically-increasing total (``inc``);
    * **summary** — pushed observations with windowed p50/p95/p99
      (``observe``);
    * **histogram** — cumulative fixed-bucket counts merged in per collect
      tick (``hist_add``), rendered as native Prometheus
      ``_bucket``/``_sum``/``_count`` families and mergeable across
      processes (the fleet metric plane).

    ``sample()`` flattens everything into ``{dotted name: float}`` for the
    trigger engine (summaries and histograms contribute ``<name>.p50/.p95/
    .p99/.mean/.count``); ``collect()`` returns structured
    :class:`MetricSample` rows for the exporter.
    """

    def __init__(self, summary_window: int = 1024) -> None:
        self._sources: Dict[str, Callable[[], float]] = {}
        self._gauges: Dict[str, float] = {}
        self._counters: Dict[str, float] = {}
        self._summaries: Dict[str, _Summary] = {}
        self._hists: Dict[str, Histogram] = {}
        #: export metadata: name → (family, labels)
        self._descriptors: Dict[str, Tuple[str, Dict[str, str]]] = {}
        self._summary_window = int(summary_window)
        self._lock = threading.Lock()

    # -- registration ------------------------------------------------------
    def register(self, name: str, source: Callable[[], float]) -> None:
        with self._lock:
            self._sources[name] = source

    def unregister(self, name: str) -> None:
        with self._lock:
            self._sources.pop(name, None)
            self._gauges.pop(name, None)
            self._counters.pop(name, None)
            self._summaries.pop(name, None)
            self._hists.pop(name, None)
            self._descriptors.pop(name, None)

    def describe(self, name: str, family: str, labels: Optional[Mapping[str, str]] = None) -> None:
        """Attach export identity to ``name``: the Prometheus family and label
        set it renders under. Idempotent; cheap enough to call per publish."""
        with self._lock:
            self._descriptors[name] = (family, dict(labels or {}))

    # -- pushes ------------------------------------------------------------
    def set_gauge(self, name: str, value: float) -> None:
        """Push-style update (used for per-collect stage statistics)."""
        with self._lock:
            self._gauges[name] = float(value)

    def update_gauges(self, values: Mapping[str, float]) -> None:
        """Bulk ``set_gauge``: one lock acquisition for a whole stats tick
        (the control loop publishes O(stages×channels) gauges per tick)."""
        with self._lock:
            self._gauges.update(values)

    def inc(self, name: str, delta: float = 1.0) -> None:
        """Increment counter ``name`` (created at 0 on first use)."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + delta

    def observe(self, name: str, value: float) -> None:
        """Add one observation to summary ``name`` (created on first use)."""
        with self._lock:
            s = self._summaries.get(name)
            if s is None:
                s = self._summaries[name] = _Summary(self._summary_window)
            s.observe(float(value))

    def hist_add(self, name: str, counts: Sequence[int], sum_delta: float = 0.0) -> None:
        """Merge a windowed bucket-count delta into cumulative histogram
        ``name`` (created on first use — an all-zero delta pre-registers the
        family at zero). Counts follow the shared WAIT_BOUNDS_MS layout;
        ``sum_delta`` is the window's total in the same unit (ms)."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            h.add_counts(counts, sum_delta)

    # -- reads -------------------------------------------------------------
    def names(self) -> List[str]:
        with self._lock:
            return sorted(
                set(self._sources)
                | set(self._gauges)
                | set(self._counters)
                | set(self._summaries)
                | set(self._hists)
            )

    def gauge_count(self, prefix: str = "", suffix: str = "") -> int:
        """Count pushed gauges matching ``prefix``/``suffix`` — O(n) with no
        sort/alloc, cheap enough for derived sources sampled every tick."""
        with self._lock:
            return sum(
                1 for n in self._gauges if n.startswith(prefix) and n.endswith(suffix)
            )

    def sample(self) -> Dict[str, float]:
        """One coherent flat sample of every metric (for trigger predicates).

        A source that raises is skipped for this tick (a dead metric must not
        take down the control loop) — its last pushed value, if any, remains.
        """
        with self._lock:
            sources = list(self._sources.items())
            out = dict(self._gauges)
            out.update(self._counters)
            # copy windows under the lock, sort OUTSIDE it: the serve decode
            # hot path observes into these summaries and must not block
            # behind O(n log n) sorts per tick/scrape
            summaries = [(n, list(s.buf), s.count, s.sum) for n, s in self._summaries.items()]
            hists = [(n, tuple(h.counts), h.sum) for n, h in self._hists.items()]
        for name, values, count, total in summaries:
            values.sort()
            for suffix, q in _SUMMARY_KEYS:
                out[f"{name}.{suffix}"] = quantile(values, q)
            out[f"{name}.mean"] = (total / count) if count else 0.0
            out[f"{name}.count"] = float(count)
        for name, counts, total in hists:
            n_obs = sum(counts)
            for suffix, q in _SUMMARY_KEYS:
                out[f"{name}.{suffix}"] = quantile_from_counts(counts, q)
            out[f"{name}.mean"] = (total / n_obs) if n_obs else 0.0
            out[f"{name}.count"] = float(n_obs)
        for name, fn in sources:
            try:
                out[name] = float(fn())
            except Exception:  # noqa: BLE001 — sampling is best-effort
                continue
        return out

    def collect(self) -> List[MetricSample]:
        """Structured snapshot for exposition (exporter endpoint)."""
        with self._lock:
            desc = dict(self._descriptors)
            gauges = list(self._gauges.items())
            counters = list(self._counters.items())
            summaries = [(n, list(s.buf), s.count, s.sum) for n, s in self._summaries.items()]
            # O(buckets) per histogram — cheap enough to flatten under the lock
            hists = [(n, h.cumulative(), h.count, h.sum) for n, h in self._hists.items()]
            sources = list(self._sources.items())
        for _, values, _, _ in summaries:
            values.sort()  # outside the lock — see sample()
        out: List[MetricSample] = []

        def meta(name: str) -> Tuple[Optional[str], Dict[str, str]]:
            fam, labels = desc.get(name, (None, {}))
            return fam, dict(labels)

        for name, value in gauges:
            fam, labels = meta(name)
            out.append(MetricSample(name, "gauge", value, fam, labels))
        for name, value in counters:
            fam, labels = meta(name)
            out.append(MetricSample(name, "counter", value, fam, labels))
        for name, values, count, total in summaries:
            fam, labels = meta(name)
            out.append(
                MetricSample(
                    name,
                    "summary",
                    family=fam,
                    labels=labels,
                    quantiles={ql: quantile(values, q) for ql, q in SUMMARY_QUANTILES},
                    count=count,
                    sum=total,
                )
            )
        for name, bucket_rows, count, total in hists:
            fam, labels = meta(name)
            out.append(
                MetricSample(
                    name,
                    "histogram",
                    family=fam,
                    labels=labels,
                    count=count,
                    sum=total,
                    buckets=bucket_rows,
                )
            )
        for name, fn in sources:
            try:
                value = float(fn())
            except Exception:  # noqa: BLE001 — a dead source is skipped
                continue
            fam, labels = meta(name)
            out.append(MetricSample(name, "gauge", value, fam, labels))
        return out


# --------------------------------------------------------------------------- #
# process-wide registry                                                        #
# --------------------------------------------------------------------------- #
_global_lock = threading.Lock()
_global_registry: Optional[MetricRegistry] = None


def get_registry() -> MetricRegistry:
    """The process-wide shared registry: the default publication target for
    control planes and serve engines, and the default source for the
    exporter — everything that publishes here is visible on one endpoint."""
    global _global_registry
    with _global_lock:
        if _global_registry is None:
            _global_registry = MetricRegistry()
        return _global_registry


def set_registry(registry: MetricRegistry) -> MetricRegistry:
    """Swap the process-wide registry (tests use this for isolation);
    returns the previous one (possibly None on first call)."""
    global _global_registry
    with _global_lock:
        prev, _global_registry = _global_registry, registry
    return prev  # type: ignore[return-value]
