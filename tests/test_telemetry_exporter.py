"""Shared metric registry v2 + Prometheus exporter: counters/gauges/windowed
summaries, export descriptors, text exposition, the HTTP endpoint, and the
stats-to-gauges publication path (wait percentiles, buffer reuse)."""
from __future__ import annotations

import urllib.error
import urllib.request

import pytest

from repro.core import VirtualClock
from repro.core.stats import ChannelStats, StatsSnapshot, merge_snapshots
from repro.policy.engine import PolicyRuntime, stats_to_samples
from repro.telemetry import (
    MetricRegistry,
    MetricsExporter,
    get_registry,
    parse_prometheus,
    render_prometheus,
    set_registry,
)


class TestRegistry:
    def test_gauges_counters_summaries_in_sample(self):
        r = MetricRegistry()
        r.set_gauge("g", 1.5)
        r.inc("c")
        r.inc("c", 2)
        for v in range(1, 101):
            r.observe("s", float(v))
        sample = r.sample()
        assert sample["g"] == 1.5
        assert sample["c"] == 3.0
        # nearest-rank (same convention as SlidingWindow)
        assert sample["s.p50"] == 51.0
        assert sample["s.p95"] == 96.0
        assert sample["s.p99"] == 100.0
        assert sample["s.mean"] == 50.5
        assert sample["s.count"] == 100.0

    def test_summary_window_slides_but_count_is_cumulative(self):
        r = MetricRegistry(summary_window=10)
        for v in range(100):
            r.observe("s", float(v))
        sample = r.sample()
        assert sample["s.count"] == 100.0  # cumulative
        assert sample["s.p50"] >= 90.0  # window holds only the last 10

    def test_update_gauges_bulk(self):
        r = MetricRegistry()
        r.update_gauges({"a": 1.0, "b": 2.0})
        assert r.sample() == {"a": 1.0, "b": 2.0}

    def test_unregister_clears_every_shape(self):
        r = MetricRegistry()
        r.set_gauge("x", 1)
        r.inc("y")
        r.observe("z", 1)
        for name in ("x", "y", "z"):
            r.unregister(name)
        assert r.names() == []

    def test_dead_source_skipped(self):
        r = MetricRegistry()
        r.register("bad", lambda: 1 / 0)
        r.set_gauge("good", 1.0)
        assert r.sample() == {"good": 1.0}
        assert all(s.name != "bad" for s in r.collect())

    def test_global_registry_swap(self):
        first = get_registry()
        assert get_registry() is first
        fresh = MetricRegistry()
        prev = set_registry(fresh)
        assert prev is first
        assert get_registry() is fresh


class TestRendering:
    def test_families_labels_and_types(self):
        r = MetricRegistry()
        r.set_gauge("s.ch.throughput", 12.5)
        r.describe("s.ch.throughput", "paio_channel_throughput", {"stage": "s", "channel": "ch"})
        r.inc("tokens", 7)
        r.observe("lat_ms", 4.0)
        text = render_prometheus(r)
        assert '# TYPE paio_channel_throughput gauge' in text
        assert 'paio_channel_throughput{channel="ch",stage="s"} 12.5' in text
        assert "# TYPE paio_tokens_total counter" in text
        assert "paio_tokens_total 7" in text
        assert "# TYPE paio_lat_ms summary" in text
        assert 'paio_lat_ms{quantile="0.99"} 4' in text
        assert "paio_lat_ms_count 1" in text

    def test_undescribed_names_sanitize(self):
        r = MetricRegistry()
        r.set_gauge("train.step.p99-ms", 3.0)
        assert "paio_train_step_p99_ms 3" in render_prometheus(r)

    def test_label_escaping(self):
        r = MetricRegistry()
        r.set_gauge("g", 1.0)
        r.describe("g", "paio_g", {"who": 'a"b\\c'})
        line = [l for l in render_prometheus(r).splitlines() if l.startswith("paio_g")][0]
        assert '\\"' in line and "\\\\" in line

    def test_parse_round_trip(self):
        r = MetricRegistry()
        r.set_gauge("g", 2.25)
        r.describe("g", "paio_g", {"k": "v"})
        parsed = parse_prometheus(render_prometheus(r))
        assert parsed['paio_g{k="v"}'] == 2.25


class TestExporterHTTP:
    def test_endpoint_serves_and_stops(self):
        r = MetricRegistry()
        r.set_gauge("up", 1.0)
        exp = MetricsExporter(registry=r).start()
        try:
            with urllib.request.urlopen(exp.url, timeout=5.0) as resp:
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
            assert "paio_up 1" in body
            # collect() is the same rendering without HTTP
            assert exp.collect() == body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(exp.url.replace("/metrics", "/nope"), timeout=5.0)
        finally:
            exp.stop()

    def test_default_registry_is_process_wide(self):
        get_registry().set_gauge("shared", 42.0)
        exp = MetricsExporter().start()
        try:
            body = urllib.request.urlopen(exp.url, timeout=5.0).read().decode()
            assert "paio_shared 42" in body
        finally:
            exp.stop()


class TestWaitPercentiles:
    def test_channel_stats_percentiles(self):
        clk = VirtualClock()
        cs = ChannelStats("c", clk)
        for i in range(100):
            cs.record(1, wait=i / 1000.0)  # 0..99 ms
        clk.sleep(1.0)
        snap = cs.collect()
        # histogram-derived: exact counts, bucket-width resolution (≤2.5x)
        assert snap.wait_p50_ms == pytest.approx(50.0)
        assert 95.0 <= snap.wait_p95_ms <= 100.0
        assert 99.0 <= snap.wait_p99_ms <= 100.0
        assert sum(snap.wait_hist) == 100
        # an idle window holds the previous window's percentiles (hold-last):
        # a one-tick traffic gap must not read as a latency collapse
        clk.sleep(1.0)
        idle = cs.collect()
        assert idle.ops == 0 and not any(idle.wait_hist)
        assert idle.wait_p99_ms == snap.wait_p99_ms

    def test_batch_contributes_mean_observation(self):
        clk = VirtualClock()
        cs = ChannelStats("c", clk)
        cs.record_batch(10, 100, wait=0.05)  # 5 ms per op mean
        clk.sleep(1.0)
        assert cs.collect().wait_p99_ms == pytest.approx(5.0)

    def test_batch_per_op_waits_match_sequential(self):
        # the PR-3 caveat, fixed: batched and sequential enforcement of the
        # same latency distribution produce the same histogram + percentiles
        clk = VirtualClock()
        seq, bat = ChannelStats("a", clk), ChannelStats("b", clk)
        waits = [i / 1000.0 for i in range(200)]  # 0..199 ms
        for w in waits:
            seq.record(8, wait=w)
        bat.record_batch(len(waits), 8 * len(waits), waits=waits)
        clk.sleep(1.0)
        s, b = seq.collect(), bat.collect()
        assert s.wait_hist == b.wait_hist
        assert s.wait_p50_ms == b.wait_p50_ms
        assert s.wait_p99_ms == b.wait_p99_ms
        assert s.wait_seconds == pytest.approx(b.wait_seconds)

    def test_snapshot_wire_round_trip_with_new_fields(self):
        from dataclasses import asdict

        snap = StatsSnapshot(
            channel="c", ops=1, bytes=2, window_seconds=1.0, throughput=2.0, iops=1.0,
            wait_p50_ms=1.0, wait_p95_ms=2.0, wait_p99_ms=3.0,
        )
        assert StatsSnapshot(**asdict(snap)) == snap
        # old-wire snapshots (no percentile fields) still deserialize
        d = asdict(snap)
        for k in ("wait_p50_ms", "wait_p95_ms", "wait_p99_ms"):
            d.pop(k)
        assert StatsSnapshot(**d).wait_p99_ms == 0.0

    def test_merge_takes_later_percentiles(self):
        a = StatsSnapshot("c", 1, 1, 1.0, 1.0, 1.0, wait_p99_ms=9.0)
        b = StatsSnapshot("c", 1, 1, 1.0, 1.0, 1.0, wait_p99_ms=4.0)
        assert merge_snapshots(a, b).wait_p99_ms == 4.0


class TestStatsPublication:
    def _stats(self, wait=0.0):
        from repro.core.stats import StageStats

        snap = StatsSnapshot(
            channel="ch", ops=10, bytes=100, window_seconds=1.0, throughput=100.0,
            iops=10.0, wait_seconds=wait, wait_p99_ms=wait * 100,
        )
        return {"s": StageStats(per_channel={"ch": snap})}

    def test_samples_include_percentile_gauges(self):
        out = stats_to_samples(self._stats(wait=0.5))
        assert out["s.ch.wait_p99_ms"] == 50.0
        assert out["s.wait_p99_ms"] == 50.0  # stage aggregate: max over channels
        assert out["s.ch.throughput"] == 100.0

    def test_buffer_and_key_cache_reuse(self):
        buf: dict = {}
        cache: dict = {}
        out1 = stats_to_samples(self._stats(), out=buf, key_cache=cache)
        assert out1 is buf
        keys1 = list(buf)
        out2 = stats_to_samples(self._stats(), out=buf, key_cache=cache)
        assert out2 is buf and list(buf) == keys1
        # key strings are cached objects, not rebuilt per tick
        assert len(cache) == 2  # (stage, channel) + (stage, None)

    def test_runtime_publishes_described_gauges(self):
        reg = MetricRegistry()
        rt = PolicyRuntime(registry=reg)
        rt.on_collect(0.0, self._stats(wait=0.5))
        text = render_prometheus(reg)
        assert 'paio_channel_wait_p99_ms{channel="ch",stage="s"} 50' in text
        assert 'paio_stage_throughput{stage="s"} 100' in text
        # gauges vanish when the channel does (absent, not stale)
        rt.on_collect(1.0, {})
        assert "paio_channel_wait_p99_ms" not in render_prometheus(reg)

    def test_runtime_defaults_to_global_registry(self):
        rt = PolicyRuntime()
        assert rt.registry is get_registry()


class TestAllowlist:
    def _registry(self):
        r = MetricRegistry()
        r.set_gauge("stage.s1.up", 1.0)
        r.describe("stage.s1.up", "paio_stage_up", {"stage": "s1"})
        r.set_gauge("serve.tenant_a.tokens", 9.0)
        r.set_gauge("train.step.p99_ms", 3.0)
        return r

    def test_render_filters_by_family_or_raw_name(self):
        r = self._registry()
        text = render_prometheus(r, allow_prefixes=("paio_stage_",))
        assert 'paio_stage_up{stage="s1"} 1' in text
        assert "tenant_a" not in text and "train" not in text
        # raw dotted registry names match too (undescribed metrics)
        text = render_prometheus(r, allow_prefixes=("train.",))
        assert "paio_train_step_p99_ms 3" in text
        assert "paio_stage_up" not in text

    def test_exporter_serves_only_allowlisted_families(self):
        exp = MetricsExporter(registry=self._registry(), allow_prefixes=("paio_stage_",)).start()
        try:
            body = urllib.request.urlopen(exp.url, timeout=5.0).read().decode()
            assert "paio_stage_up" in body
            assert "tenant_a" not in body
        finally:
            exp.stop()

    def test_non_loopback_bind_requires_allowlist_or_opt_in(self):
        with pytest.raises(ValueError, match="non-loopback"):
            MetricsExporter(registry=MetricRegistry(), host="0.0.0.0")
        # either escape hatch is accepted (constructor-level guard; no bind)
        MetricsExporter(registry=MetricRegistry(), host="0.0.0.0", allow_prefixes=("paio_",))
        MetricsExporter(registry=MetricRegistry(), host="0.0.0.0", allow_all=True)

    def test_loopback_unrestricted_by_default(self):
        exp = MetricsExporter(registry=self._registry()).start()
        try:
            body = urllib.request.urlopen(exp.url, timeout=5.0).read().decode()
            assert "tenant_a" in body and "paio_stage_up" in body
        finally:
            exp.stop()

    def test_control_plane_passthrough(self):
        from repro.core import ControlPlane

        with ControlPlane() as cp:
            get_registry().set_gauge("stage.s1.up", 1.0)
            get_registry().describe("stage.s1.up", "paio_stage_up", {"stage": "s1"})
            get_registry().set_gauge("secret.detail", 7.0)
            exp = cp.serve_metrics(allow_prefixes=("paio_stage_",))
            body = urllib.request.urlopen(exp.url, timeout=5.0).read().decode()
            assert "paio_stage_up" in body and "secret" not in body


# --------------------------------------------------------------------------- #
# histograms: registry shape + native _bucket exposition                       #
# --------------------------------------------------------------------------- #
class TestHistogramExposition:
    def _hist_registry(self):
        from repro.telemetry import NBUCKETS, Histogram

        r = MetricRegistry()
        h = Histogram()
        h.observe_many([0.5, 3.0, 3.0, 40.0, 7000.0])
        r.hist_add("s.ch.wait_hist_ms", h.counts, h.sum)
        r.describe("s.ch.wait_hist_ms", "paio_channel_wait_hist_ms",
                   {"stage": "s", "channel": "ch"})
        return r

    def test_sample_flattens_histogram_percentiles(self):
        r = self._hist_registry()
        sample = r.sample()
        assert sample["s.ch.wait_hist_ms.count"] == 5.0
        assert sample["s.ch.wait_hist_ms.p50"] <= sample["s.ch.wait_hist_ms.p99"]
        assert sample["s.ch.wait_hist_ms.mean"] == pytest.approx(7046.5 / 5)

    def test_renders_native_bucket_family(self):
        text = render_prometheus(self._hist_registry())
        assert "# TYPE paio_channel_wait_hist_ms histogram" in text
        parsed = parse_prometheus(text)
        lbl = 'channel="ch",stage="s"'
        assert parsed[f'paio_channel_wait_hist_ms_count{{{lbl}}}'] == 5.0
        assert parsed[f'paio_channel_wait_hist_ms_sum{{{lbl}}}'] == pytest.approx(7046.5)
        assert parsed[f'paio_channel_wait_hist_ms_bucket{{{lbl},le="+Inf"}}'] == 5.0
        # cumulative and non-decreasing across ascending le bounds
        from repro.telemetry import WAIT_BOUNDS_MS

        cums = [parsed[f'paio_channel_wait_hist_ms_bucket{{{lbl},le="{b:g}"}}'] for b in WAIT_BOUNDS_MS]
        assert cums == sorted(cums)
        assert cums[-1] <= 5.0

    def test_cumulative_across_ticks(self):
        r = self._hist_registry()
        from repro.telemetry import NBUCKETS

        delta = [0] * NBUCKETS
        delta[0] = 3
        r.hist_add("s.ch.wait_hist_ms", delta, 0.003)
        assert r.sample()["s.ch.wait_hist_ms.count"] == 8.0

    def test_unregister_drops_histogram(self):
        r = self._hist_registry()
        r.unregister("s.ch.wait_hist_ms")
        assert "s.ch.wait_hist_ms" not in r.names()
        assert "wait_hist" not in render_prometheus(r)


# --------------------------------------------------------------------------- #
# label escaping: render must not corrupt, parse must round-trip               #
# --------------------------------------------------------------------------- #
class TestLabelEscaping:
    EVIL = 'a"} 9\\n\nback\\slash'

    def _registry(self):
        r = MetricRegistry()
        r.set_gauge("flow.evil.throughput", 7.0)
        r.describe("flow.evil.throughput", "paio_channel_throughput",
                   {"stage": "s", "channel": self.EVIL})
        r.set_gauge("flow.plain.throughput", 3.0)
        r.describe("flow.plain.throughput", "paio_channel_throughput",
                   {"stage": "s", "channel": "plain"})
        return r

    def test_render_escapes_label_values(self):
        text = render_prometheus(self._registry())
        # raw newline must never appear inside a label value
        for line in text.splitlines():
            assert not line.endswith("\\")
        assert '\\"} 9' in text  # the quote is escaped where it appears

    def test_parse_survives_pathological_values(self):
        # the old rpartition(" ") parser silently dropped any series whose
        # label value contained '"} ' — both series must parse now
        parsed = parse_prometheus(render_prometheus(self._registry()))
        assert 3.0 in parsed.values() and 7.0 in parsed.values()
        assert len([k for k in parsed if k.startswith("paio_channel_throughput")]) == 2

    def test_parse_labels_round_trips(self):
        from repro.telemetry import parse_labels

        parsed = parse_prometheus(render_prometheus(self._registry()))
        by_channel = {}
        for series, value in parsed.items():
            fam, labels = parse_labels(series)
            assert fam == "paio_channel_throughput"
            by_channel[labels["channel"]] = value
        assert by_channel == {self.EVIL: 7.0, "plain": 3.0}

    def test_parse_labels_no_labels(self):
        from repro.telemetry import parse_labels

        assert parse_labels("paio_up") == ("paio_up", {})
