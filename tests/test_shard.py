"""Sharded data plane: rendezvous shard map properties (scalar == batch,
minimal movement on add/remove), the ShardRouter's merged single-stage view
(router-merged collect == one stage over the union of ops), failover
re-homing, the policy ``shards:`` stanza, and the v1/v2 interop matrix.

Property tests run under hypothesis when installed; each carries a seeded
deterministic twin so the invariants stay covered on minimal containers.
"""
from __future__ import annotations

import os
import random
import tempfile
import time

import pytest

try:
    from hypothesis import assume, given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - exercised on minimal containers
    from _hypothesis_stub import assume, given, settings, st

from repro.core import (
    Context,
    DifferentiationRule,
    HousekeepingRule,
    RequestType,
    ShardMap,
    Stage,
    flow_key,
    flow_token,
    logical_stage_name,
    shard_stage_names,
)
from repro.core.shard import placement_moves
from repro.distributed import AllShardsDownError, LocalShardHandle, ShardRouter
from repro.telemetry import get_registry
from repro.transport import RemoteStageHandle, StageServer
from repro.transport.codec import (
    TransportError,
    decode_enforce_batch,
    decode_int,
    encode_enforce_batch,
    pack_value,
)

MiB = float(1 << 20)

_tokens = st.lists(st.integers(min_value=0, max_value=2**32 - 1), min_size=1, max_size=200)
_shard_ids = st.sets(st.text(alphabet="abcdefgh", min_size=1, max_size=6), min_size=2, max_size=8)


def _ctx(tenant: str, size: int = 1024) -> Context:
    return Context(0, RequestType.write, size, tenant=tenant)


# --------------------------------------------------------------------------- #
# naming + flow identity                                                       #
# --------------------------------------------------------------------------- #
class TestNaming:
    def test_shard_stage_names(self):
        assert shard_stage_names("web", 3) == ["web/0", "web/1", "web/2"]
        with pytest.raises(ValueError):
            shard_stage_names("web", 0)

    def test_logical_stage_name_inverts(self):
        for name in shard_stage_names("web", 5):
            assert logical_stage_name(name) == "web"
        # names without a shard ordinal map to themselves
        assert logical_stage_name("web") == "web"
        assert logical_stage_name("a/b/notdigit") == "a/b/notdigit"

    def test_flow_key_is_the_classifier_tuple(self):
        ctx = Context(7, RequestType.read, 512, "bg_flush", "t1")
        assert flow_key(ctx) == (7, RequestType.read, "bg_flush", "t1")

    def test_flow_token_ignores_size(self):
        # size is per-request, not per-flow: both requests are the same flow
        assert flow_token(_ctx("a", size=1)) == flow_token(_ctx("a", size=1 << 20))
        assert flow_token(_ctx("a")) != flow_token(_ctx("b"))


# --------------------------------------------------------------------------- #
# shard map: property tests + seeded twins                                     #
# --------------------------------------------------------------------------- #
class TestShardMapProperties:
    @given(_tokens, st.integers(min_value=1, max_value=9))
    @settings(max_examples=50, deadline=None)
    def test_batch_matches_scalar(self, tokens, n):
        m = ShardMap(shard_stage_names("web", n))
        assert m.shard_of_batch(tokens) == [m.shard_of(t) for t in tokens]

    @given(_tokens, _shard_ids, st.integers(min_value=0))
    @settings(max_examples=50, deadline=None)
    def test_remove_moves_only_the_dead_shards_flows(self, tokens, ids, pick):
        ids = sorted(ids)
        assume(len(ids) >= 2)
        victim = ids[pick % len(ids)]
        before = ShardMap(ids)
        after = ShardMap([s for s in ids if s != victim])
        moves = placement_moves(before, after, tokens)
        for _tok, (old, new) in moves.items():
            assert old == victim and new is not None and new != victim
        for t in tokens:  # completeness: every victim-owned token re-homed
            if before.shard_of(t) == victim:
                assert t in moves

    @given(_tokens, _shard_ids)
    @settings(max_examples=50, deadline=None)
    def test_add_steals_only_for_the_new_shard(self, tokens, ids):
        ids = sorted(ids)  # alphabet a–h: "z-new" can never collide
        before = ShardMap(ids)
        after = ShardMap(ids + ["z-new"])
        for _tok, (old, new) in placement_moves(before, after, tokens).items():
            assert new == "z-new" and old != "z-new"


class TestShardMapSeeded:
    """Deterministic twins of the properties above (always run)."""

    def _tokens(self, n=5000, seed=1234):
        rng = random.Random(seed)
        return [rng.getrandbits(32) for _ in range(n)]

    def test_batch_matches_scalar_seeded(self):
        tokens = self._tokens()
        for n in (1, 2, 3, 5, 8):
            m = ShardMap(shard_stage_names("web", n))
            assert m.shard_of_batch(tokens) == [m.shard_of(t) for t in tokens]

    def test_remove_moves_only_the_dead_shards_flows_seeded(self):
        tokens = self._tokens()
        names = shard_stage_names("web", 4)
        before = ShardMap(names)
        victim = "web/2"
        after = ShardMap([s for s in names if s != victim])
        moves = placement_moves(before, after, tokens)
        owned = [t for t in tokens if before.shard_of(t) == victim]
        assert owned  # the victim owned a healthy slice of the keyspace
        assert sorted(moves) == sorted(set(owned))
        assert all(old == victim for old, _new in moves.values())

    def test_add_steals_only_for_the_new_shard_seeded(self):
        tokens = self._tokens()
        before = ShardMap(shard_stage_names("web", 3))
        after = ShardMap(shard_stage_names("web", 4))
        moves = placement_moves(before, after, tokens)
        assert moves  # the newcomer won something
        assert all(new == "web/3" for _old, new in moves.values())

    def test_placement_is_roughly_balanced(self):
        tokens = self._tokens()
        m = ShardMap(shard_stage_names("web", 4))
        owners = m.shard_of_batch(tokens)
        for sid in m.shards:
            frac = owners.count(sid) / len(tokens)
            assert 0.15 < frac < 0.35, f"{sid} owns {frac:.1%} of the keyspace"

    def test_empty_map_raises_and_empty_batch_is_empty(self):
        m = ShardMap()
        with pytest.raises(LookupError):
            m.shard_of(1)
        with pytest.raises(LookupError):
            m.shard_of_batch([1])
        assert ShardMap(["a"]).shard_of_batch([]) == []

    def test_add_remove_idempotent(self):
        m = ShardMap(["a", "b"])
        m.add("a")
        assert m.shards == ("a", "b")
        m.remove("zzz")
        assert m.shards == ("a", "b")
        m.remove("a")
        m.remove("a")
        assert m.shards == ("b",)


# --------------------------------------------------------------------------- #
# OP_ENFORCE codec                                                             #
# --------------------------------------------------------------------------- #
class TestEnforceCodec:
    def test_round_trip(self):
        groups = [
            (7, int(RequestType.write), 4096, "bg_flush", "tenant_a", 12),
            (0, int(RequestType.read), 0, "", None, 1),
        ]
        assert decode_enforce_batch(encode_enforce_batch("web/1", groups)) == (
            "web/1",
            groups,
        )

    def test_negative_count_rejected(self):
        payload = encode_enforce_batch("s", [(0, 0, 0, "", None, -1)])
        with pytest.raises(TransportError):
            decode_enforce_batch(payload)

    def test_trailing_bytes_rejected(self):
        payload = encode_enforce_batch("s", [(0, 0, 0, "", None, 1)])
        with pytest.raises(TransportError):
            decode_enforce_batch(payload + b"\x00")

    def test_int_reply_rejects_bool(self):
        assert decode_int(pack_value(42)) == 42
        with pytest.raises(TransportError):
            decode_int(pack_value(True))


# --------------------------------------------------------------------------- #
# router over in-process shards: merged view == one stage                      #
# --------------------------------------------------------------------------- #
TENANTS = [f"t{i}" for i in range(5)]


def _provision(target, channel="c", tenants=TENANTS):
    target.hsk_rule(HousekeepingRule(op="create_channel", channel=channel))
    for t in tenants:
        target.dif_rule(DifferentiationRule(channel=channel, match={"tenant": t}))


def _mk_router(n=3):
    stages = [Stage(sid) for sid in shard_stage_names("web", n)]
    router = ShardRouter("web", probe_interval=0.01)
    for stage in stages:
        router.add_shard(stage.name, LocalShardHandle(stage))
    _provision(router)
    return router, stages


class TestRouterMergedView:
    def _drive_and_compare(self, ops):
        """ops: list of (tenant_index, size, count). The router-merged collect
        must equal a single stage serving the union of the same requests."""
        router, stages = _mk_router()
        twin = Stage("solo")
        _provision(twin)
        ctxs = []
        for tenant_idx, size, count in ops:
            ctxs.extend([_ctx(TENANTS[tenant_idx % len(TENANTS)], size)] * count)
        results = router.enforce_batch(ctxs)
        assert len(results) == len(ctxs)
        twin.enforce_batch(ctxs)
        rs = router.collect().per_channel["c"]
        ts = twin.collect().per_channel["c"]
        assert (rs.ops, rs.bytes) == (ts.ops, ts.bytes)
        assert (rs.cumulative_ops, rs.cumulative_bytes) == (
            ts.cumulative_ops,
            ts.cumulative_bytes,
        )
        assert rs.wait_hist == ts.wait_hist  # exact histogram merge
        router.close()

    @pytest.mark.parametrize(
        "ops",
        [
            [(i, 1024 * (i + 1), 10 + i) for i in range(5)],
            # one flow's requests differ in size: each must count its own bytes
            [(1, 0, 1), (1, 1, 1), (3, 4096, 2), (3, 512, 3), (3, 4096, 1)],
        ],
        ids=["one_size_per_flow", "mixed_sizes_per_flow"],
    )
    def test_merged_collect_equals_single_stage(self, ops):
        self._drive_and_compare(ops)
        # and the flows really spread over more than one shard
        router, _ = _mk_router()
        owners = {router.owner_of(_ctx(t)) for t in TENANTS}
        assert len(owners) >= 2
        router.close()

    @given(
        st.lists(
            st.tuples(
                st.integers(min_value=0, max_value=4),
                st.integers(min_value=0, max_value=1 << 20),
                st.integers(min_value=1, max_value=8),
            ),
            min_size=1,
            max_size=40,
        )
    )
    @settings(max_examples=20, deadline=None)
    def test_merged_collect_equals_single_stage_property(self, ops):
        self._drive_and_compare(ops)

    def test_rule_fanout_reaches_every_shard(self):
        router, stages = _mk_router()
        try:
            for stage in stages:
                info = stage.stage_info()
                assert "c" in info["channels"]
            merged = router.stage_info()
            assert merged["sharded"] and merged["shard_count"] == 3
            assert "c" in merged["channels"]
            assert sorted(merged["shards"]) == shard_stage_names("web", 3)
        finally:
            router.close()

    def test_results_echo_request_payloads(self):
        router, _ = _mk_router()
        try:
            reqs = [b"a", b"bb", b"ccc"]
            results = router.enforce_batch([_ctx("t0")] * 3, reqs)
            assert [r.content for r in results] == reqs
        finally:
            router.close()


# --------------------------------------------------------------------------- #
# failover: kill a shard, only its flows move                                  #
# --------------------------------------------------------------------------- #
class _KillableHandle(LocalShardHandle):
    """In-process shard whose transport can be 'killed' (raises like a dead
    socket) and later 'revived' — drives the router's failover/probe path
    without real processes."""

    def __init__(self, stage):
        super().__init__(stage)
        self.dead = False

    def _check(self):
        if self.dead:
            raise ConnectionError(f"shard {self.shard_id} killed")

    def enforce_groups(self, shard_id, groups, timeout=None):
        self._check()
        return super().enforce_groups(shard_id, groups, timeout)

    def collect(self):
        self._check()
        return super().collect()

    def stage_info(self):
        self._check()
        return super().stage_info()

    def ping(self):
        self._check()


def _mk_killable_router(n=3):
    handles = {sid: _KillableHandle(Stage(sid)) for sid in shard_stage_names("web", n)}
    router = ShardRouter("web", probe_interval=0.01)
    for sid, handle in handles.items():
        router.add_shard(sid, handle)
    _provision(router)
    return router, handles


class TestRouterFailover:
    def test_kill_rehomes_only_the_dead_shards_flows(self):
        router, handles = _mk_killable_router()
        try:
            before = {t: router.owner_of(_ctx(t)) for t in TENANTS}
            victim = before[TENANTS[0]]
            handles[victim].dead = True
            results = router.enforce_batch([_ctx(t) for t in TENANTS] * 20)
            assert len(results) == len(TENANTS) * 20  # nobody saw the death
            assert router.failovers == 1
            assert victim not in router.shards
            after = {t: router.owner_of(_ctx(t)) for t in TENANTS}
            for t in TENANTS:
                if before[t] == victim:
                    assert after[t] != victim  # re-homed
                else:
                    assert after[t] == before[t]  # survivors never move
            sample = get_registry().sample()
            assert sample[f"shard.{victim}.up"] == 0.0
            assert sample["shard.web.count"] == 2.0
            assert sample["shard.web.failovers"] == 1.0
        finally:
            router.close()

    def test_probe_readmits_a_revived_shard(self):
        router, handles = _mk_killable_router()
        try:
            victim = router.owner_of(_ctx(TENANTS[0]))
            handles[victim].dead = True
            router.enforce_batch([_ctx(t) for t in TENANTS])
            assert victim not in router.shards
            handles[victim].dead = False
            deadline = time.monotonic() + 5.0
            while victim not in router.shards and time.monotonic() < deadline:
                time.sleep(0.02)
                router.enforce_batch([_ctx(TENANTS[1])])  # probes ride dispatch
            assert victim in router.shards
            assert get_registry().sample()[f"shard.{victim}.up"] == 1.0
        finally:
            router.close()

    def test_readmit_gate_blocks_until_it_passes(self):
        gate_open = []
        router = ShardRouter(
            "web", probe_interval=0.01, readmit_gate=lambda sid: bool(gate_open)
        )
        handles = {}
        try:
            for sid in shard_stage_names("web", 2):
                handles[sid] = _KillableHandle(Stage(sid))
                router.add_shard(sid, handles[sid])
            _provision(router)
            victim = router.owner_of(_ctx(TENANTS[0]))
            handles[victim].dead = True
            router.enforce_batch([_ctx(t) for t in TENANTS])
            handles[victim].dead = False
            time.sleep(0.05)
            router.enforce_batch([_ctx(TENANTS[0])])
            assert victim not in router.shards  # gate closed: still out
            gate_open.append(True)
            time.sleep(0.05)
            router.enforce_batch([_ctx(TENANTS[0])])
            assert victim in router.shards
        finally:
            router.close()

    def test_all_shards_down_raises(self):
        router, handles = _mk_killable_router(2)
        try:
            for handle in handles.values():
                handle.dead = True
            with pytest.raises(AllShardsDownError):
                router.enforce_batch([_ctx("t0")])
            with pytest.raises(AllShardsDownError):
                router.ping()
        finally:
            router.close()

    def test_local_handle_rejects_misaddressed_batch(self):
        handle = LocalShardHandle(Stage("web/0"))
        with pytest.raises(ValueError):
            handle.enforce_groups("web/1", [(0, 0, 0, "", None, 1)])


# --------------------------------------------------------------------------- #
# policy `shards:` stanza                                                      #
# --------------------------------------------------------------------------- #
SHARDED_POLICY = {
    "policy": "fair",
    "stage": "web",
    "shards": 2,
    "flows": [
        {
            "name": "tenant_a",
            "scope": "global",
            "match": {"tenant": "tenant_a"},
            "objects": [{"kind": "drl", "id": "0", "params": {"rate": "60MiB/s"}}],
        }
    ],
    "objective": {
        "kind": "fairshare",
        "capacity": "60MiB/s",
        "demands": {"tenant_a": "60MiB/s"},
    },
}


class TestPolicyShards:
    def test_text_header_and_round_trip(self):
        from repro.policy import load_policy, policy_from_dict, policy_to_dict

        policy = load_policy("policy fair stage web shards 4\nfor tenant=a as A: limit bandwidth 1MiB/s")
        assert policy.shards == 4 and policy.stage == "web"
        assert policy_from_dict(policy_to_dict(policy)).shards == 4

    def test_shards_without_stage_rejected(self):
        from repro.policy import PolicyError, policy_from_dict

        bad = dict(SHARDED_POLICY)
        bad.pop("stage")
        with pytest.raises(PolicyError):
            policy_from_dict(bad)
        with pytest.raises(PolicyError):
            policy_from_dict({**SHARDED_POLICY, "shards": 0})

    def test_offline_compile_binds_real_shard_members(self):
        from repro.policy import compile_policy, load_policy

        compiled = compile_policy(load_policy(SHARDED_POLICY), None)
        assert sorted(compiled.install) == shard_stage_names("web", 2)

    def test_online_compile_requires_every_shard_registered(self):
        from repro.policy import PolicyError, compile_policy, load_policy

        infos = {"web/0": Stage("web/0").stage_info()}
        with pytest.raises(PolicyError, match="web/1"):
            compile_policy(load_policy(SHARDED_POLICY), infos)
        infos["web/1"] = Stage("web/1").stage_info()
        compiled = compile_policy(load_policy(SHARDED_POLICY), infos)
        assert sorted(compiled.install) == shard_stage_names("web", 2)


# --------------------------------------------------------------------------- #
# interop matrix: one router over mixed v1 (JSON) / v2 (binary) shards         #
# --------------------------------------------------------------------------- #
class TestInteropMatrix:
    @pytest.mark.parametrize(
        "protos", [(2, 2, 1), (1, 1, 1)], ids=["mixed-v2-v1", "all-v1"]
    )
    def test_router_over_mixed_protocol_fleet(self, protos):
        with tempfile.TemporaryDirectory() as d:
            servers = []
            router = ShardRouter("web", probe_interval=0.01)
            try:
                names = shard_stage_names("web", len(protos))
                for sid, proto in zip(names, protos):
                    path = os.path.join(d, sid.replace("/", "_") + ".sock")
                    servers.append(
                        StageServer(
                            Stage(sid), path, max_protocol=proto, shard_id=sid
                        ).start()
                    )
                    router.connect(sid, path, timeout=5.0)
                negotiated = sorted(
                    router._states[sid].handle.proto for sid in names
                )
                assert negotiated == sorted(protos)
                _provision(router)
                ctxs = [_ctx(t) for t in TENANTS] * 60
                assert len(router.enforce_batch(ctxs)) == len(ctxs)
                merged = router.collect().per_channel["c"]
                assert merged.ops == len(ctxs)
                assert merged.bytes == sum(c.size for c in ctxs)
            finally:
                router.close()
                for server in servers:
                    server.stop()

    def test_shard_id_mismatch_is_a_loud_transport_error(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "s.sock")
            server = StageServer(Stage("web/0"), path, shard_id="web/0").start()
            handle = RemoteStageHandle(path, timeout=5.0)
            try:
                ok = handle.enforce_groups(
                    "web/0", [(0, int(RequestType.write), 1, "", None, 3)]
                )
                assert ok == 3
                with pytest.raises(ConnectionError):
                    handle.enforce_groups(
                        "web/9", [(0, int(RequestType.write), 1, "", None, 1)]
                    )
            finally:
                handle.close()
                server.stop()
