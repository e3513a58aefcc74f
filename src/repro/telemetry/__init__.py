from .histogram import (
    NBUCKETS,
    WAIT_BOUNDS_MS,
    Histogram,
    bucket_index,
    merge_counts,
    quantile_from_counts,
)
from .metrics import (
    MetricRegistry,
    MetricSample,
    get_registry,
    quantile,
    set_registry,
)
from . import spans
from .spans import SpanRecord, span

#: exporter names resolve lazily (module __getattr__): the data plane imports
#: repro.telemetry for the registry; it must not pay for http.server unless
#: something actually starts/renders an exporter
_EXPORTER_NAMES = frozenset(
    {"MetricsExporter", "parse_labels", "parse_prometheus", "render_prometheus", "start_exporter"}
)

__all__ = [
    "Histogram",
    "MetricRegistry",
    "MetricSample",
    "MetricsExporter",
    "NBUCKETS",
    "SpanRecord",
    "WAIT_BOUNDS_MS",
    "bucket_index",
    "get_registry",
    "merge_counts",
    "parse_labels",
    "parse_prometheus",
    "quantile",
    "quantile_from_counts",
    "render_prometheus",
    "set_registry",
    "span",
    "spans",
    "start_exporter",
]


def __getattr__(name: str):
    if name in _EXPORTER_NAMES:
        from . import exporter

        return getattr(exporter, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
