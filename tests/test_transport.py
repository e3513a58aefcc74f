"""repro.transport: binary codec round-trips (unit + property), frame layer,
v1↔v2 protocol negotiation/interop matrix, pipelined overlap semantics, and
mixed-version fleets driven through one ControlPlane.
"""
from __future__ import annotations

import io
import math
import os
import tempfile
import threading
import time

import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - exercised on minimal containers
    from _hypothesis_stub import given, settings, st

from repro.telemetry.histogram import NBUCKETS

from repro.core import (
    ControlPlane,
    DifferentiationRule,
    EnforcementRule,
    HousekeepingRule,
    Stage,
    StageServer,
    StageStats,
    StatsSnapshot,
)
from repro.transport import (
    MAX_FRAME_BYTES,
    OP_RULE,
    RemoteStageHandle,
    RuleShipError,
    TransportError,
    decode_rule,
    decode_stats,
    encode_rule,
    encode_stats,
    pack_value,
    read_frame,
    unpack_value,
    write_frame,
)

MiB = float(1 << 20)


# --------------------------------------------------------------------------- #
# value codec                                                                  #
# --------------------------------------------------------------------------- #
class TestValueCodec:
    @pytest.mark.parametrize(
        "value",
        [
            None,
            True,
            False,
            0,
            -1,
            (1 << 63) - 1,
            -(1 << 63),
            1 << 100,          # beyond int64 → bigint path
            -(1 << 100),
            0.0,
            -2.5,
            float("inf"),
            float("-inf"),
            5e-324,            # smallest denormal
            "",
            "héllo wörld ✓",
            "x" * 100_000,     # long token
            b"",
            b"\x00\xff\x7f",
            [],
            {},
            [1, "a", None, [2.5, {"k": b"v"}]],
            {"nested": {"list": [1, 2, 3]}, "f": -0.0},
        ],
    )
    def test_round_trip(self, value):
        assert unpack_value(pack_value(value)) == value

    def test_nan_round_trips(self):
        # JSON cannot represent NaN; the binary codec must
        assert math.isnan(unpack_value(pack_value(float("nan"))))

    def test_trailing_garbage_rejected(self):
        with pytest.raises(TransportError, match="trailing"):
            unpack_value(pack_value(1) + b"\x00")

    def test_truncation_rejected(self):
        with pytest.raises(TransportError):
            unpack_value(pack_value("hello")[:-1])

    def test_unknown_tag_rejected(self):
        with pytest.raises(TransportError, match="unknown value tag"):
            unpack_value(b"\xfe")

    def test_unencodable_type_rejected(self):
        with pytest.raises(TypeError):
            pack_value(object())

    @given(
        st.recursive(
            st.none()
            | st.booleans()
            | st.integers()
            | st.floats(allow_nan=False)
            | st.text(max_size=64)
            | st.binary(max_size=64),
            lambda children: st.lists(children, max_size=4)
            | st.dictionaries(st.text(max_size=8), children, max_size=4),
            max_leaves=20,
        )
    )
    @settings(max_examples=150, deadline=None)
    def test_property_round_trip(self, value):
        assert unpack_value(pack_value(value)) == value


# --------------------------------------------------------------------------- #
# rule codec                                                                   #
# --------------------------------------------------------------------------- #
_hsk = st.builds(
    HousekeepingRule,
    op=st.sampled_from(["create_channel", "remove_channel", "create_object", "remove_object", "remove_route"]),
    channel=st.text(min_size=1, max_size=64),
    object_id=st.none() | st.text(max_size=32),
    object_kind=st.none() | st.sampled_from(["drl", "noop", "priority"]),
    params=st.dictionaries(
        st.text(max_size=16),
        st.none() | st.booleans() | st.integers() | st.floats(allow_nan=False) | st.text(max_size=32),
        max_size=4,
    ),
)
_dif = st.builds(
    DifferentiationRule,
    channel=st.text(min_size=1, max_size=64),
    match=st.dictionaries(
        st.sampled_from(["workflow_id", "request_type", "request_context", "tenant"]),
        st.text(max_size=512),
        max_size=4,
    ),
    object_id=st.none() | st.text(max_size=32),
)
_enf = st.builds(
    EnforcementRule,
    channel=st.text(min_size=1, max_size=64),
    object_id=st.text(min_size=1, max_size=32),
    state=st.dictionaries(
        st.text(max_size=16), st.floats(allow_nan=False) | st.integers(), max_size=4
    ),
)


class TestRuleCodec:
    def test_each_rule_type_round_trips(self):
        rules = [
            HousekeepingRule(op="create_object", channel="io", object_id="0",
                             object_kind="drl", params={"rate": 100 * MiB}),
            HousekeepingRule(op="remove_route", channel="io",
                             params={"match": {"tenant": "a"}}),
            DifferentiationRule(channel="io", match={"tenant": "a" * 4096}, object_id="0"),
            DifferentiationRule(channel="io"),  # empty match (wildcard)
            EnforcementRule(channel="io", object_id="0", state={"rate": 2.5e8}),
            EnforcementRule(channel="io", object_id="0", state={}),
        ]
        for rule in rules:
            assert decode_rule(encode_rule(rule)) == rule

    def test_not_a_rule_rejected(self):
        with pytest.raises(TypeError):
            encode_rule({"rule": "enf"})

    def test_bad_tag_rejected(self):
        with pytest.raises(TransportError, match="unknown rule tag"):
            decode_rule(b"\x7f")

    @given(st.one_of(_hsk, _dif, _enf))
    @settings(max_examples=150, deadline=None)
    def test_property_round_trip(self, rule):
        assert decode_rule(encode_rule(rule)) == rule


# --------------------------------------------------------------------------- #
# stats codec                                                                  #
# --------------------------------------------------------------------------- #
_snap = st.builds(
    StatsSnapshot,
    channel=st.text(max_size=64),
    ops=st.integers(min_value=0, max_value=1 << 50),
    bytes=st.integers(min_value=0, max_value=1 << 50),
    window_seconds=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    throughput=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    iops=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    cumulative_ops=st.integers(min_value=0, max_value=1 << 50),
    cumulative_bytes=st.integers(min_value=0, max_value=1 << 50),
    inflight=st.integers(min_value=0, max_value=1 << 30),
    wait_seconds=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    wait_p50_ms=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    wait_p95_ms=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    wait_p99_ms=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    cumulative_wait_seconds=st.floats(min_value=0, allow_nan=False, allow_infinity=False),
    wait_hist=st.one_of(
        st.just(()),  # old-wire: no histogram
        st.lists(
            st.integers(min_value=0, max_value=1 << 40),
            min_size=NBUCKETS, max_size=NBUCKETS,
        ).map(tuple),
    ),
)


class TestStatsCodec:
    def test_empty_batch(self):
        assert decode_stats(encode_stats(StageStats())).per_channel == {}

    def test_multi_channel_round_trip(self):
        stats = StageStats(per_channel={
            "a": StatsSnapshot(channel="a", ops=10, bytes=1 << 20, window_seconds=0.5,
                               throughput=2e6, iops=20.0, cumulative_ops=100,
                               cumulative_bytes=1 << 30, inflight=3, wait_seconds=0.01,
                               wait_p50_ms=0.1, wait_p95_ms=1.5, wait_p99_ms=9.9),
            "b": StatsSnapshot(channel="b", ops=0, bytes=0, window_seconds=1e-9,
                               throughput=0.0, iops=0.0),
        })
        assert decode_stats(encode_stats(stats)) == stats

    @given(st.dictionaries(st.text(max_size=32), _snap, max_size=5))
    @settings(max_examples=100, deadline=None)
    def test_property_round_trip(self, per_channel):
        stats = StageStats(per_channel=per_channel)
        assert decode_stats(encode_stats(stats)) == stats

    def test_sparse_histogram_round_trip(self):
        # histogram ships as sparse (index, count) pairs; absent (old-wire),
        # all-zero (idle window) and populated hists all round-trip distinct
        hist = [0] * NBUCKETS
        hist[3], hist[17], hist[NBUCKETS - 1] = 5, 1_000_000, 7
        cases = [(), (0,) * NBUCKETS, tuple(hist)]
        for wait_hist in cases:
            stats = StageStats(per_channel={
                "c": StatsSnapshot(channel="c", ops=1, bytes=1, window_seconds=1.0,
                                   throughput=1.0, iops=1.0, wait_hist=wait_hist),
            })
            decoded = decode_stats(encode_stats(stats))
            assert decoded.per_channel["c"].wait_hist == wait_hist

    def test_policy_wire_dict_round_trips(self):
        # the canonical (JSON-native) policy dict is wire-encodable as a value
        from repro.policy import load_policy, policy_to_dict

        policy = policy_to_dict(load_policy(
            "policy p\nfor tenant=a: limit bandwidth 10MiB/s\n"
        ))
        assert unpack_value(pack_value(policy)) == policy


# --------------------------------------------------------------------------- #
# framing                                                                      #
# --------------------------------------------------------------------------- #
class TestFraming:
    def test_frame_round_trip(self):
        buf = io.BytesIO()
        write_frame(buf, OP_RULE, 0, 42, b"payload")
        write_frame(buf, OP_RULE, 1, 43, b"")
        buf.seek(0)
        assert read_frame(buf) == (OP_RULE, 0, 42, b"payload")
        assert read_frame(buf) == (OP_RULE, 1, 43, b"")
        assert read_frame(buf) is None  # clean EOF

    def test_oversized_frame_rejected(self):
        buf = io.BytesIO()
        from repro.transport import HEADER

        buf.write(HEADER.pack(OP_RULE, 0, 1, MAX_FRAME_BYTES + 1))
        buf.seek(0)
        with pytest.raises(TransportError, match="exceeds"):
            read_frame(buf)

    def test_mid_frame_eof_rejected(self):
        buf = io.BytesIO()
        write_frame(buf, OP_RULE, 0, 1, b"payload")
        data = buf.getvalue()
        with pytest.raises(TransportError):
            read_frame(io.BytesIO(data[:-2]))


# --------------------------------------------------------------------------- #
# negotiation / interop matrix                                                 #
# --------------------------------------------------------------------------- #
@pytest.fixture
def stage_dir():
    with tempfile.TemporaryDirectory() as d:
        yield d


def _stage(name: str) -> Stage:
    stage = Stage(name)
    stage.hsk_rule(HousekeepingRule(op="create_channel", channel="io"))
    stage.hsk_rule(HousekeepingRule(
        op="create_object", channel="io", object_id="0", object_kind="drl",
        params={"rate": 100 * MiB},
    ))
    return stage


INTEROP_MATRIX = [
    ("auto", 2, 2),    # v2 × v2 → binary
    ("auto", 1, 1),    # v2 client, v1 server → JSON fallback
    ("json", 2, 1),    # v1 client, v2 server → JSON served
    ("json", 1, 1),    # v1 × v1 → JSON (the seed protocol)
    ("binary", 2, 2),  # forced binary against a v2 server
]


class TestInterop:
    @pytest.mark.parametrize("client_protocol,server_max,expect_proto", INTEROP_MATRIX)
    def test_matrix_same_semantics(self, stage_dir, client_protocol, server_max, expect_proto):
        stage = _stage("s")
        path = os.path.join(stage_dir, "s.sock")
        server = StageServer(stage, path, max_protocol=server_max).start()
        try:
            handle = RemoteStageHandle(path, protocol=client_protocol)
            try:
                assert handle.proto == expect_proto
                info = handle.stage_info()
                assert info["stage"] == "s" and "io" in info["channels"]
                assert handle.enf_rule(
                    EnforcementRule(channel="io", object_id="0", state={"rate": 5 * MiB})
                )
                assert stage.channel("io").get_object("0").rate == pytest.approx(5 * MiB)
                assert handle.hsk_rule(HousekeepingRule(op="create_channel", channel="x"))
                assert handle.dif_rule(DifferentiationRule(channel="x", match={"tenant": "t"}))
                stage.channel("io").stats.record(4096)
                stats = handle.collect()
                assert stats.per_channel["io"].bytes == 4096
                assert stats.per_channel["io"].ops == 1
                # same outcome surface for a failing rule (unknown channel →
                # stage-side False, never a transport error)
                assert handle.enf_rule(
                    EnforcementRule(channel="nope", object_id="0", state={})
                ) is False
            finally:
                handle.close()
        finally:
            server.stop()

    @pytest.mark.parametrize("client_protocol,server_max,expect_proto", INTEROP_MATRIX)
    def test_matrix_carries_the_cumulative_wait(self, stage_dir, client_protocol, server_max, expect_proto):
        # the run's total wait survives the remote collects that reset the
        # window, over every protocol pairing
        stage = _stage("s")
        path = os.path.join(stage_dir, "s.sock")
        server = StageServer(stage, path, max_protocol=server_max).start()
        try:
            handle = RemoteStageHandle(path, protocol=client_protocol)
            try:
                assert handle.proto == expect_proto
                stats = stage.channel("io").stats
                stats.record(1, wait=0.25)
                first = handle.collect().per_channel["io"]
                stats.record(1, wait=0.5)
                second = handle.collect().per_channel["io"]
                assert (first.wait_seconds, first.cumulative_wait_seconds) == (0.25, 0.25)
                assert (second.wait_seconds, second.cumulative_wait_seconds) == (0.5, 0.75)
            finally:
                handle.close()
        finally:
            server.stop()

    def test_ping_both_protocols(self, stage_dir):
        # OP_PING in binary mode; the v1 fallback degrades to stage_info
        path = os.path.join(stage_dir, "s.sock")
        server = StageServer(_stage("s"), path).start()
        try:
            for proto, want in (("binary", 2), ("json", 1)):
                handle = RemoteStageHandle(path, protocol=proto)
                try:
                    assert handle.proto == want
                    handle.ping()  # raises on any transport/protocol fault
                finally:
                    handle.close()
        finally:
            server.stop()

    def test_binary_required_against_v1_server_raises(self, stage_dir):
        path = os.path.join(stage_dir, "s.sock")
        server = StageServer(_stage("s"), path, max_protocol=1).start()
        try:
            with pytest.raises(TransportError, match="does not speak"):
                RemoteStageHandle(path, protocol="binary")
        finally:
            server.stop()

    def test_bad_protocol_name_rejected(self, stage_dir):
        with pytest.raises(ValueError, match="auto\\|binary\\|json"):
            RemoteStageHandle(os.path.join(stage_dir, "x.sock"), protocol="carrier-pigeon")

    def test_apply_rules_ordered_over_both_protocols(self, stage_dir):
        for proto in ("binary", "json"):
            stage = _stage(f"s-{proto}")
            path = os.path.join(stage_dir, f"{proto}.sock")
            server = StageServer(stage, path).start()
            try:
                handle = RemoteStageHandle(path, protocol=proto)
                try:
                    # order-sensitive program: create → route → tune
                    outcomes = handle.apply_rules([
                        HousekeepingRule(op="create_channel", channel="t"),
                        HousekeepingRule(op="create_object", channel="t", object_id="0",
                                         object_kind="drl", params={"rate": MiB}),
                        DifferentiationRule(channel="t", match={"tenant": "z"}),
                        EnforcementRule(channel="t", object_id="0", state={"rate": 7 * MiB}),
                    ])
                    assert outcomes == [True, True, True, True]
                    assert stage.channel("t").get_object("0").rate == pytest.approx(7 * MiB)
                finally:
                    handle.close()
            finally:
                server.stop()

    def test_apply_rules_dead_peer_raises_ship_error(self, stage_dir):
        stage = _stage("s")
        path = os.path.join(stage_dir, "s.sock")
        server = StageServer(stage, path).start()
        handle = RemoteStageHandle(path, timeout=1.0)
        try:
            assert handle.proto == 2
            server.stop()
            import socket as _socket

            handle._sock.shutdown(_socket.SHUT_RDWR)  # kill the live connection
            rules = [
                EnforcementRule(channel="io", object_id="0", state={"rate": float(i)})
                for i in range(4)
            ]
            with pytest.raises(RuleShipError) as err:
                handle.apply_rules(rules)
            assert err.value.applied + err.value.pending == rules
            assert isinstance(err.value, ConnectionError)  # down-markable
        finally:
            handle.close()


# --------------------------------------------------------------------------- #
# pipelining: collect and rules overlap on one connection                      #
# --------------------------------------------------------------------------- #
class TestPipelining:
    def test_slow_collect_does_not_block_rules(self, stage_dir):
        class SlowCollectStage(Stage):
            def collect(self):
                time.sleep(0.4)
                return super().collect()

        stage = SlowCollectStage("slow")
        stage.hsk_rule(HousekeepingRule(op="create_channel", channel="io"))
        stage.hsk_rule(HousekeepingRule(
            op="create_object", channel="io", object_id="0", object_kind="drl",
            params={"rate": MiB},
        ))
        path = os.path.join(stage_dir, "slow.sock")
        server = StageServer(stage, path).start()
        try:
            handle = RemoteStageHandle(path, timeout=5.0)
            try:
                assert handle.proto == 2
                done = threading.Event()
                collector = threading.Thread(target=lambda: (handle.collect(), done.set()))
                collector.start()
                time.sleep(0.05)  # collect is now parked inside the stage
                t0 = time.perf_counter()
                assert handle.enf_rule(
                    EnforcementRule(channel="io", object_id="0", state={"rate": 2 * MiB})
                )
                rule_latency = time.perf_counter() - t0
                # the rule must complete while collect is still in flight —
                # on the v1 protocol it would wait ≥ 0.35s behind the lock
                assert not done.is_set()
                assert rule_latency < 0.2
                assert done.wait(5.0)
                collector.join(5.0)
            finally:
                handle.close()
        finally:
            server.stop()

    def test_concurrent_callers_multiplex_one_connection(self, stage_dir):
        stage = _stage("mux")
        path = os.path.join(stage_dir, "mux.sock")
        server = StageServer(stage, path).start()
        try:
            handle = RemoteStageHandle(path)
            errors = []

            def worker(i: int) -> None:
                try:
                    for j in range(50):
                        ok = handle.enf_rule(EnforcementRule(
                            channel="io", object_id="0", state={"rate": float(i * 1000 + j + 1)}
                        ))
                        assert ok
                        handle.stage_info()
                except Exception as exc:  # noqa: BLE001 — surfaced below
                    errors.append(exc)

            threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            assert errors == []
            handle.close()
        finally:
            server.stop()


# --------------------------------------------------------------------------- #
# mixed-version fleet through one control plane                                #
# --------------------------------------------------------------------------- #
PAIR_POLICY = {
    "policy": "mixed",
    "flows": [
        {"name": "a", "stage": "v1stage", "match": {"tenant": "a"},
         "objects": [{"kind": "drl", "id": "0", "params": {"rate": "60MiB/s"}}]},
        {"name": "b", "stage": "v2stage", "match": {"tenant": "b"},
         "objects": [{"kind": "drl", "id": "0", "params": {"rate": "40MiB/s"}}]},
    ],
}


class TestMixedFleet:
    def test_v1_and_v2_stages_identical_semantics(self, stage_dir):
        s1, s2 = Stage("v1stage"), Stage("v2stage")
        srv1 = StageServer(s1, os.path.join(stage_dir, "v1.sock"), max_protocol=1).start()
        srv2 = StageServer(s2, os.path.join(stage_dir, "v2.sock")).start()
        try:
            with ControlPlane() as cp:
                cp.connect("v1stage", os.path.join(stage_dir, "v1.sock"))
                cp.connect("v2stage", os.path.join(stage_dir, "v2.sock"))
                status = cp.fleet_status()
                assert status["v1stage"]["protocol"] == "jsonl"
                assert status["v2stage"]["protocol"] == "binary"
                assert all(s["up"] and s["transport"] == "uds" for s in status.values())

                cp.install_policy(PAIR_POLICY)
                # the policy landed identically on both wire versions
                assert s1.channel("a").get_object("0").rate == pytest.approx(60 * MiB)
                assert s2.channel("b").get_object("0").rate == pytest.approx(40 * MiB)
                (summary,) = cp.list_policies()
                assert summary["stages"] == ["v1stage", "v2stage"]
                assert summary["down_stages"] == []

                s1.channel("a").stats.record(1 << 20)
                s2.channel("b").stats.record(2 << 20)
                stats = cp._collect_all()
                assert stats["v1stage"].per_channel["a"].bytes == 1 << 20
                assert stats["v2stage"].per_channel["b"].bytes == 2 << 20

                cp.remove_policy("mixed")
                assert s1.channel("a") is None and s2.channel("b") is None
        finally:
            srv1.stop()
            srv2.stop()
