"""``spanrun.py`` at test size on the CPU: a run with the program's spans on is
still correct, its readings come from the window's spans and the program's
counters, and nothing it observed stays patched or on afterwards."""
import pytest

from benchmarks.chip import spanrun, xtrace
from repro.serve.engine import ServeEngine
from repro.telemetry import spans

from benchutil import SERVE, TRAIN, tiny_spec


def test_training_readings():
    r = spanrun.spanrun(tiny_spec(), TRAIN, 2**31 + 11, 2.0, False, require_tpu=False)
    assert r["correct"]
    got = r["spans"]
    for key in ("ckpt_ready_ms", "ckpt_d2h_ms", "ckpt_snapshot_ms", "ckpt_drl_wait_s", "control_share"):
        assert got[key] is not None and got[key] >= 0, key
    # the snapshot is its two children and a little more, and within the stall
    # the benchmark times from outside around the save
    assert got["ckpt_ready_ms"] + got["ckpt_d2h_ms"] <= got["ckpt_snapshot_ms"] <= got["ckpt_stall_ms"]
    assert 0 < got["control_share"] < 100
    assert "MainThread:paio.data.fetch" in r["span_table"]
    assert set(r["metrics"]) == {"setup_s", "train_tokens_per_s"}


def test_serving_readings_traced():
    publish, load = ServeEngine._publish, xtrace.load
    r = spanrun.spanrun(tiny_spec(), SERVE, 2**31 + 12, 2.0, True, require_tpu=False)
    assert r["correct"]
    got = r["spans"]
    assert got["requests"] == r["attempted"]
    assert 0 <= got["queue_wait_ms"] <= got["ttft_ms"] <= got["generate_ms"]
    assert got["serve_decode_ms"] > 0
    assert r["pspans"]["spans"]["paio.serve.decode"]["count"] > 0
    assert not spans.enabled()
    assert ServeEngine._publish is publish and xtrace.load is load


@pytest.mark.parametrize("missing", ["serve", "train"])
def test_readings_of_an_empty_window(missing):
    from repro.telemetry.spans import SpanRecord

    recs = [SpanRecord(1, None, "paio.control.tick", "t", 0, 10, {})]
    got = spanrun.span_readings(recs, (100, 200), {"kind": missing})
    assert got["control_share"] == 0.0 and got["control_ticks"] == 0
    assert all(v is None for k, v in got.items() if k.endswith("_ms"))
