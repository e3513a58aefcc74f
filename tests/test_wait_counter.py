"""``cumulative_wait_seconds``: a channel's imposed wait since it was created,
which no collect resets, carried through merges and both wire codecs."""
from __future__ import annotations

import dataclasses

import pytest

from repro.core import HousekeepingRule, RequestType, Stage, VirtualClock, build_context
from repro.core.stats import ChannelStats, StageStats, StatsSnapshot, merge_parallel, merge_snapshots
from repro.transport import decode_stats, encode_stats, snapshot_from_wire, snapshot_to_wire


def test_survives_collect_resets():
    stats = ChannelStats("c", clock=VirtualClock())
    stats.record(10, wait=0.25)
    first = stats.collect()
    assert first.wait_seconds == pytest.approx(0.25)
    assert first.cumulative_wait_seconds == pytest.approx(0.25)
    stats.record_batch(3, 30, waits=[0.5, 0.0, 0.125])
    stats.record(1)
    second = stats.collect()
    assert second.wait_seconds == pytest.approx(0.625)
    assert second.cumulative_wait_seconds == pytest.approx(0.875)
    idle = stats.collect()
    assert idle.wait_seconds == 0.0 and idle.cumulative_wait_seconds == pytest.approx(0.875)


def test_drl_waits_add_up_across_control_ticks():
    clk = VirtualClock()
    stage = Stage("s", clock=clk)
    stage.hsk_rule(HousekeepingRule(op="create_channel", channel="default"))
    stage.hsk_rule(HousekeepingRule(
        op="create_object", channel="default", object_id="0", object_kind="drl", params={"rate": 100.0},
    ))
    waits = []
    for _ in range(4):
        stage.enforce(build_context(RequestType.write, size=50))
        waits.append(stage.collect().per_channel["default"])  # a control tick
    assert sum(s.wait_seconds for s in waits) > 0
    assert waits[-1].cumulative_wait_seconds == pytest.approx(sum(s.wait_seconds for s in waits))


def _snap(channel: str, wait: float, cum: float) -> StatsSnapshot:
    return StatsSnapshot(
        channel=channel, ops=1, bytes=1, window_seconds=1.0, throughput=1.0, iops=1.0,
        wait_seconds=wait, cumulative_wait_seconds=cum,
    )


def test_sequential_merge_takes_the_later_and_parallel_merge_sums():
    earlier, later = _snap("c", 0.5, 2.0), _snap("c", 0.25, 2.25)
    assert merge_snapshots(earlier, later).cumulative_wait_seconds == 2.25
    assert merge_parallel([_snap("c", 0.5, 2.0), _snap("c", 1.0, 3.0)], "c").cumulative_wait_seconds == 5.0


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_round_trips_through_both_codecs(codec):
    stats = StageStats(per_channel={"a": _snap("a", 0.5, 12.375), "b": _snap("b", 0.0, 0.0)})
    if codec == "binary":
        back = decode_stats(encode_stats(stats))
    else:
        back = StageStats({n: snapshot_from_wire(snapshot_to_wire(s)) for n, s in stats.per_channel.items()})
    assert back == stats
    assert back.per_channel["a"].cumulative_wait_seconds == 12.375


def test_old_wire_peer_reads_zero():
    wire = snapshot_to_wire(_snap("a", 0.5, 7.0))
    del wire["cumulative_wait_seconds"]  # what a peer from before the field sends
    old = snapshot_from_wire(wire)
    assert old.cumulative_wait_seconds == 0.0
    assert dataclasses.replace(old, cumulative_wait_seconds=7.0) == _snap("a", 0.5, 7.0)


def test_codec_coverage_rule_covers_the_field(tmp_path):
    """The ``codec-coverage`` lint fails a codec that drops the field on
    either side of the wire."""
    from pathlib import Path

    from repro.analysis import LintEngine, default_rules

    src = Path(__file__).resolve().parent.parent / "src" / "repro"
    for rel in ("core/stats.py", "core/rules.py", "filters/spec.py"):
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text((src / rel).read_text())
    codec = (src / "transport" / "codec.py").read_text()
    encoded = "            s.cumulative_wait_seconds,\n"
    decoded = "            cumulative_wait_seconds=cumulative_wait_seconds,\n"
    assert codec.count(encoded) == 1 and codec.count(decoded) == 1
    dropped = codec.replace(encoded, "").replace(decoded, "")
    (tmp_path / "transport").mkdir()
    (tmp_path / "transport" / "codec.py").write_text(dropped)
    report = LintEngine(default_rules()).run([str(tmp_path)])
    found = [f.message for f in report.findings if f.rule == "codec-coverage"]
    assert len(found) == 2 and all("cumulative_wait_seconds" in m for m in found)
