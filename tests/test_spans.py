"""Spans inside the program (``repro.telemetry.spans``): the off path, parents
and ids across threads, the buffer's bound, self time, the profiler's copy,
and the spans each instrumented layer opens."""
from __future__ import annotations

import glob
import itertools
import os
import re
import subprocess
import sys
import tempfile
import threading
import tracemalloc

import pytest

from repro.telemetry import spans
from repro.telemetry.spans import SpanRecord

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


@pytest.fixture
def on():
    spans.enable()
    try:
        yield
    finally:
        spans.disable()


def by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def _peak_of(fn) -> int:
    """Peak traced bytes above the start while ``fn`` runs, after a warm run."""
    fn()
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        before, _ = tracemalloc.get_traced_memory()
        fn()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    return peak - before


class TestOff:
    def test_span_returns_one_shared_object_and_records_nothing(self):
        assert not spans.enabled()
        a = spans.span("paio.test.a")
        b = spans.span("paio.test.b", request=3)
        assert a is b
        with a:
            pass
        assert spans.records() == [] and spans.dropped() == 0

    def test_off_path_allocates_nothing(self):
        def calls():
            for _ in itertools.repeat(None, 5000):
                spans.span("paio.test.a")
                spans.span("paio.test.b", request=1)

        def empty_loop():
            for _ in itertools.repeat(None, 5000):
                pass

        assert _peak_of(calls) <= _peak_of(empty_loop)


class TestOn:
    def test_parents_ids_and_threads(self, on):
        inner_open = threading.Event()
        worker_done = threading.Event()

        def worker():
            inner_open.wait(5)
            with spans.span("paio.test.w", request=9):
                with spans.span("paio.test.w.child"):
                    pass
            worker_done.set()

        t = threading.Thread(target=worker, name="span-test-worker")
        t.start()
        with spans.span("paio.test.outer", request=1):
            with spans.span("paio.test.inner", leaf="x"):
                inner_open.set()
                assert worker_done.wait(5)
        t.join(5)
        assert not t.is_alive()
        got = {r.name: r for r in spans.records()}
        outer, inner = got["paio.test.outer"], got["paio.test.inner"]
        w, child = got["paio.test.w"], got["paio.test.w.child"]
        assert outer.parent_id is None and inner.parent_id == outer.span_id
        # the worker's spans ran while the main thread's were open, yet their
        # parents are their own thread's
        assert w.parent_id is None and child.parent_id == w.span_id
        assert outer.ids == {"request": 1} and inner.ids == {"leaf": "x"} and w.ids == {"request": 9}
        assert outer.thread == threading.current_thread().name and w.thread == "span-test-worker"
        assert outer.t0_ns <= inner.t0_ns <= w.t0_ns <= w.t1_ns <= inner.t1_ns <= outer.t1_ns
        assert len({r.span_id for r in got.values()}) == 4

    def test_buffer_keeps_the_newest_within_its_bound(self):
        spans.enable(capacity=5)
        try:
            for i in range(8):
                with spans.span("paio.test.n", i=i):
                    pass
            kept = spans.records()
            assert [r.ids["i"] for r in kept] == [3, 4, 5, 6, 7]
            assert spans.dropped() == 3
            spans.clear()
            assert spans.records() == [] and spans.dropped() == 0
            with spans.span("paio.test.n", i=8):
                pass
            assert len(spans.records()) == 1
        finally:
            spans.disable()

    def test_self_time_is_duration_less_children(self, on):
        with spans.span("paio.test.parent"):
            for _ in range(2):
                with spans.span("paio.test.child"):
                    with spans.span("paio.test.grandchild"):
                        pass
        recs = spans.records()
        own = spans.self_ns(recs)
        g = by_name(recs)
        (parent,) = g["paio.test.parent"]
        kids = g["paio.test.child"]
        assert own[parent.span_id] == parent.duration_ns - sum(k.duration_ns for k in kids)
        for k, gk in zip(kids, g["paio.test.grandchild"]):
            assert own[k.span_id] == k.duration_ns - gk.duration_ns
            assert own[gk.span_id] == gk.duration_ns
        # by hand, on made-up records
        made = [
            SpanRecord(2, 1, "c", "t", 10, 30, {}),
            SpanRecord(3, 1, "c", "t", 40, 45, {}),
            SpanRecord(1, None, "p", "t", 0, 100, {}),
        ]
        assert spans.self_ns(made) == {1: 75, 2: 20, 3: 5}

    def test_each_span_lands_in_the_profiler_trace_with_its_id(self, on):
        import jax
        from jax.profiler import ProfileData

        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            try:
                with spans.span("paio.test.traced"):
                    jax.numpy.ones(4).block_until_ready()
            finally:
                jax.profiler.stop_trace()
            (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
            data = ProfileData.from_file(path)
            found = [
                dict(e.stats).get("span_id")
                for plane in data.planes if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events if e.name == "paio.test.traced"
            ]
        (rec,) = [r for r in spans.records() if r.name == "paio.test.traced"]
        assert found == [rec.span_id]


def test_core_with_spans_on_imports_no_jax():
    code = (
        "import sys\n"
        "from repro.telemetry import spans\n"
        "spans.enable()\n"
        "from repro.core import Stage, HousekeepingRule, RequestType, build_context\n"
        "stage = Stage('s')\n"
        "stage.hsk_rule(HousekeepingRule(op='create_channel', channel='default'))\n"
        "stage.enforce(build_context(RequestType.get, size=4))\n"
        "stage.enforce_batch([build_context(RequestType.get, size=4)] * 3)\n"
        "assert [r.name for r in spans.records()] == ['paio.stage.enforce'] * 2\n"
        "assert 'jax' not in sys.modules, 'repro.core imported jax'\n"
        "print('ok')\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_every_program_span_name_starts_with_paio():
    names = set()
    for path in glob.glob(os.path.join(SRC, "repro", "**", "*.py"), recursive=True):
        with open(path) as f:
            names |= set(re.findall(r"\bspan\(\s*\"([^\"]+)\"", f.read()))
    assert len(names) >= 20
    assert all(n.startswith("paio.") for n in names), sorted(names)


# --------------------------------------------------------------------------- #
# the instrumented layers                                                      #
# --------------------------------------------------------------------------- #
class TestLayers:
    def test_control_tick_and_its_children(self, on):
        from repro.core import ControlPlane, FlowSpec, HousekeepingRule, Stage, TrainIOControl

        stage = Stage("s")
        for ch in ("fetch", "ckpt"):
            stage.hsk_rule(HousekeepingRule(op="create_channel", channel=ch))
        cp = ControlPlane(TrainIOControl(
            fg=FlowSpec("s", "fetch"), background=[FlowSpec("s", "ckpt")], total_bandwidth=1e6
        ))
        cp.register_stage(stage)
        cp.run_once()
        g = by_name(spans.records())
        (tick,) = g["paio.control.tick"]
        for child in ("paio.control.collect", "paio.control.step", "paio.control.ship"):
            (c,) = g[child]
            assert c.parent_id == tick.span_id
        assert "paio.control.policy" not in g  # no policy installed
        cp.close()

    def test_fetch_span_per_read(self, on):
        from repro.core import HousekeepingRule, Stage
        from repro.data import DataPipeline, SyntheticTokenSource

        stage = Stage("s")
        stage.hsk_rule(HousekeepingRule(op="create_channel", channel="default"))
        pipe = DataPipeline(SyntheticTokenSource(vocab=64, batch=2, seq=8, seed=0), stage=stage)
        pipe.read_batch(0)
        pipe.read_batch(1)
        g = by_name(spans.records())
        assert [r.ids for r in g["paio.data.fetch"]] == [{"batch": 0}, {"batch": 1}]
        for r in g["paio.stage.enforce"]:
            assert r.parent_id in {f.span_id for f in g["paio.data.fetch"]}

    def test_checkpoint_snapshot_on_the_caller_and_writes_on_the_worker(self, on, tmp_path):
        import jax
        import jax.numpy as jnp

        from repro.checkpoint import AsyncCheckpointer, CheckpointManager

        state = {"w": jnp.ones((64, 32), jnp.float32), "b": jnp.zeros((32,), jnp.bfloat16)}
        manager = CheckpointManager(str(tmp_path))
        ckpt = AsyncCheckpointer(manager)
        ckpt.save(1, state)
        ckpt.wait()
        g = by_name(spans.records())
        (snap,) = g["paio.ckpt.snapshot"]
        (ready,), (d2h,) = g["paio.ckpt.ready"], g["paio.ckpt.d2h"]
        assert ready.parent_id == snap.span_id and d2h.parent_id == snap.span_id
        assert snap.thread == threading.current_thread().name
        writes = g["paio.ckpt.write"]
        (commit,) = g["paio.ckpt.commit"]
        assert sorted(w.ids["leaf"] for w in writes) == sorted(n.replace("/", "_") for n in (
            jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(state)[0]))
        assert {w.thread for w in writes} == {commit.thread} and commit.thread != snap.thread
        assert commit.ids == {"step": 1}
        # only device arrays are copied, and counted
        assert manager.d2h_bytes == 64 * 32 * 4 + 32 * 2
        manager.save(2, jax.device_get(state))
        assert manager.d2h_bytes == 64 * 32 * 4 + 32 * 2
        assert len(by_name(spans.records())["paio.ckpt.snapshot"]) == 1

    def test_train_loop_spans(self, on, tmp_path):
        import repro.configs as configs
        from repro.launch.train import train

        cfg = configs.get_reduced("llama3_2_1b").replace(name="lm-spans-test", n_layers=1)
        train(cfg, steps=2, batch=2, seq=16, ckpt_dir=str(tmp_path), ckpt_every=2, seed=1)
        g = by_name(spans.records())
        assert [r.ids for r in g["paio.train.fetch"]] == [{"step": 0}, {"step": 1}]
        assert [r.ids for r in g["paio.train.step"]] == [{"step": 0}, {"step": 1}]
        (save,) = g["paio.train.ckpt"]
        assert save.ids == {"step": 2}
        (snap,) = g["paio.ckpt.snapshot"]
        assert snap.parent_id == save.span_id
        fetch_ids = {r.span_id for r in g["paio.train.fetch"]}
        assert all(r.parent_id in fetch_ids for r in g["paio.data.fetch"])
