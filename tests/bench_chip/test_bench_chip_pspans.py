"""The reduction of the program's own spans (``pspans.py``), on made-up
intervals and on a small trace recorded on one TPU v5e chip, against values
worked out by hand."""
import os

import pytest

from benchmarks.chip import pspans, xtrace
from benchmarks.chip.pspans import Span

from conftest import FIXTURES

DEV = "/device:TPU:0"
MAIN, OTHER = ("/host:CPU", 1), ("/host:CPU", 2)


def nested():
    return [Span(0, 100, "a", 1), Span(10, 20, "b", 2), Span(12, 15, "c", 3), Span(50, 60, "d", 4)]


def test_program_spans_never_take_the_benchmarks_names():
    # every program span starts with paio. (tests/test_spans.py), so none can
    # land among the spans xtrace attributes idle time to
    assert not any(n.startswith(pspans.PREFIX) for n in xtrace.SPAN_NAMES)


def test_self_times_by_hand():
    assert pspans.self_times(nested()) == [100 - 10 - 10, 10 - 3, 3, 10]


def test_segments_name_the_innermost_span():
    pieces = [(a, b, s.name) for a, b, s in pspans.segments(nested())]
    assert pieces == [
        (0, 10, "a"), (10, 12, "b"), (12, 15, "c"), (15, 20, "b"), (20, 50, "a"), (50, 60, "d"), (60, 100, "a"),
    ]
    # time under no span is left out
    gaps = [(a, b, s.name) for a, b, s in pspans.segments([Span(0, 10, "x", 1), Span(20, 30, "y", 2)])]
    assert gaps == [(0, 10, "x"), (20, 30, "y")]


def made_up():
    """A window [0, 200] on the dispatching line: a decode step [10, 110]
    with two dispatches and a sync, then a prefill [120, 190]. Another
    thread's tick [0, 200] holds device idle time that must never be
    attributed. The device (offset +5 to the host) runs run 1 (launched in
    the first dispatch) and run 2 (launched in the prefill)."""
    trace = xtrace.Trace(
        device={DEV: [(20, 40, "op1"), (45, 60, "op2"), (140, 170, "op3")]},
        host=[(0, 200, xtrace.WINDOW_SPAN)],
        offset_ns=5,
    )
    sp = pspans.Spans(
        lines={
            MAIN: [
                Span(10, 110, "paio.serve.decode", 1), Span(12, 22, "paio.serve.dispatch", 2),
                Span(22, 80, "paio.serve.sync", 3), Span(80, 90, "paio.serve.dispatch", 4),
                Span(120, 190, "paio.serve.prefill", 5),
            ],
            OTHER: [Span(0, 200, "paio.control.tick", 6), Span(5, 15, "paio.control.collect", 7)],
        },
        dispatch=MAIN,
        launches={1: 15, 2: 125},
        runs={DEV: [(18, 62, 1), (135, 175, 2)]},
    )
    return trace, sp


def test_idle_goes_only_to_the_dispatching_line():
    r = pspans.reduce(*made_up())
    # busy on the host's clock: [25, 45], [50, 65], [145, 175]
    idle_in = {k: v * 1e9 for k, v in r["idle_in"].items()}
    assert idle_in["paio.serve.decode"] == pytest.approx(100 - 35)
    # dispatch [12, 22] holds busy [12, 22] ∩ [25, 45] = 0; [80, 90] none
    assert idle_in["paio.serve.dispatch"] == pytest.approx(20)
    # sync [22, 80]: busy 20 + 15
    assert idle_in["paio.serve.sync"] == pytest.approx(58 - 35)
    assert idle_in["paio.serve.prefill"] == pytest.approx(70 - 30)
    idle_self = {k: v * 1e9 for k, v in r["idle_self"].items()}
    # the decode step's own idle: [10, 12] and [90, 110]
    assert idle_self["paio.serve.decode"] == pytest.approx(2 + 20)
    assert sum(idle_self.values()) == pytest.approx(sum(idle_in[k] for k in ("paio.serve.decode", "paio.serve.prefill")))
    for key in ("idle_in", "idle_self", "busy_by", "in_window"):
        assert not any(k.startswith("paio.control") for k in r[key])
    # the other thread still gives its self time
    assert r["spans"]["paio.control.tick"]["self_s"] == pytest.approx(190e-9)
    assert r["spans"]["paio.control.tick"]["count"] == 1
    assert r["in_window"] == {"paio.serve.decode": 1, "paio.serve.dispatch": 2, "paio.serve.sync": 1, "paio.serve.prefill": 1}


def test_device_time_goes_to_the_span_that_launched_it():
    trace, sp = made_up()
    ops = pspans.attribute_ops(trace, sp)
    assert [(s, e, n, o.span_id) for s, e, n, o in ops] == [
        (25, 45, "op1", 2), (50, 65, "op2", 2), (145, 175, "op3", 5),
    ]
    busy = {k: v * 1e9 for k, v in pspans.reduce(trace, sp)["busy_by"].items()}
    assert busy == {"paio.serve.dispatch": pytest.approx(35), "paio.serve.prefill": pytest.approx(30)}


def test_run_without_a_launch_or_span_goes_to_none():
    trace, sp = made_up()
    sp.launches = {1: 115}  # between the decode step and the prefill; run 2's launch is not in the trace
    busy = pspans.reduce(trace, sp)["busy_by"]
    assert set(busy) == {"none"} and busy["none"] == pytest.approx(65e-9)


def test_no_window_gives_span_totals_only():
    trace, sp = made_up()
    trace.host = []
    r = pspans.reduce(trace, sp)
    assert r["idle_in"] == {} and r["busy_by"] == {}
    assert r["spans"]["paio.serve.dispatch"]["count"] == 2


# --------------------------------------------------------------------------- #
# a trace recorded on the chip                                                 #
# --------------------------------------------------------------------------- #
FIXTURE = os.path.join(FIXTURES, "pspans_v5e.xplane.pb")
MAIN_SPANS = [
    ("paio.serve.admit", 341), ("paio.stage.enforce", 342), ("paio.serve.prefill", 343), ("paio.serve.prefill", 348),
    ("paio.serve.decode", 357), ("paio.stage.enforce", 358), ("paio.serve.dispatch", 359), ("paio.serve.sync", 360),
    ("paio.serve.dispatch", 361), ("paio.serve.sync", 362), ("paio.ckpt.snapshot", 363), ("paio.ckpt.ready", 364),
    ("paio.ckpt.d2h", 369),
]


@pytest.fixture(scope="module")
def recorded():
    """``fixtures/record_pspans_trace.py`` on one TPU v5e chip: a drain of two
    requests (two prefills, then one lockstep decode step of both) while the
    control plane ticks on its own thread, then an asynchronous save whose
    writes run on a third."""
    return xtrace.load(FIXTURE), pspans.load(FIXTURE)


def test_fixture_keeps_each_threads_line(recorded):
    assert os.path.getsize(FIXTURE) <= 50_000
    trace, sp = recorded
    assert [(s.name, s.span_id) for s in sp.lines[sp.dispatch]] == MAIN_SPANS
    others = sorted(sorted({s.name for s in v}) for k, v in sp.lines.items() if k != sp.dispatch)
    assert others == [
        ["paio.ckpt.commit", "paio.ckpt.write", "paio.stage.enforce"],
        ["paio.control.collect", "paio.control.ship", "paio.control.step", "paio.control.tick"],
    ]
    assert len(trace.device["/device:TPU:0"]) == 388


def test_device_ops_go_to_the_call_that_launched_them(recorded):
    trace, sp = recorded
    ops = pspans.attribute_ops(trace, sp)
    by = {}
    for op in ops:
        by.setdefault(op[3].name if op[3] is not None else None, []).append(op)
    # each decode call's program (88 ops) goes to its dispatch span; the
    # device runs it only after the call returned, while the host waits in
    # paio.serve.sync
    dispatch = by["paio.serve.dispatch"]
    assert len(dispatch) == 176 and {o.span_id for *_, o in dispatch} == {359, 361}
    assert all(start >= owner.start for start, _, _, owner in dispatch)
    assert all(start >= owner.end for start, _, _, owner in dispatch)
    assert len(by["paio.serve.prefill"]) == 210
    assert {o.span_id for *_, o in by["paio.serve.prefill"]} == {343, 348}
    # the state saved was made under no span
    assert len(by[None]) == 2
    assert set(by) == {"paio.serve.dispatch", "paio.serve.prefill", None}


def test_idle_and_busy_by_span_by_hand(recorded):
    r = pspans.reduce(*recorded)
    idle_in = {k: round(v * 1e9) for k, v in r["idle_in"].items()}
    # decode step [64,196,172, 68,302,241]: busy 9,581 + 9,645 ns (its two programs)
    assert idle_in["paio.serve.decode"] == 4_106_069 - 19_226
    assert idle_in["paio.serve.dispatch"] == 398_080 + 404_739  # no device time in either call
    assert idle_in["paio.serve.sync"] == 855_970 - 9_581 + 891_030 - 9_645
    assert idle_in["paio.serve.prefill"] == 8_303_929 - 13_057 + 7_221_709 - 12_653
    assert idle_in["paio.ckpt.ready"] == 626_400 - 1_201
    idle_self = {k: round(v * 1e9) for k, v in r["idle_self"].items()}
    assert idle_self["paio.serve.decode"] == idle_in["paio.serve.decode"] - (398_080 + 404_739) - (
        846_389 + 881_385) - 33_420
    busy = {k: round(v * 1e9) for k, v in r["busy_by"].items()}
    assert busy == {"paio.serve.dispatch": 19_226, "paio.serve.prefill": 25_710, "none": 1_201}
    # idle time on the other threads' spans is never attributed, though the
    # device idles under all of them; they give their self time
    for key in ("idle_in", "idle_self", "busy_by", "in_window"):
        assert not any(k.startswith(("paio.control", "paio.ckpt.write", "paio.ckpt.commit")) for k in r[key])
    assert r["spans"]["paio.control.tick"]["count"] == 8
    assert r["spans"]["paio.ckpt.write"]["count"] == 2
    tick = r["spans"]["paio.control.tick"]
    assert tick["self_s"] < tick["total_s"]
    # the window's idle (xtrace) bounds what the spans hold
    x = xtrace.reduce(recorded[0])
    assert sum(r["idle_self"].values()) <= x["window_s"] - x["busy_s"]
