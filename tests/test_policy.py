"""Policy subsystem: DSL round-trips, compile validation, trigger semantics
(hysteresis/cooldown), runtime lifecycle over local and UDS transports, and
policy-vs-hand-coded control equivalence."""
from __future__ import annotations

import tempfile

import pytest

from repro.core import (
    ControlPlane,
    Context,
    FairShareControl,
    FlowSpec,
    HousekeepingRule,
    RequestType,
    Stage,
    StageServer,
    VirtualClock,
    rules_from_wire,
    rules_to_wire,
)
from repro.policy import (
    CompiledTrigger,
    PolicyError,
    SlidingWindow,
    TriggerEngine,
    compile_policy,
    load_policy,
    parse_duration,
    parse_policy_text,
    parse_quantity,
    policy_from_dict,
    policy_to_dict,
)

MiB = float(1 << 20)

GUARD_TEXT = """
policy serve_guard stage serve
for tenant=analytics: limit bandwidth 100MiB/s
for request_context=bg_compaction_LN as compaction: limit bandwidth 50MiB/s
when p99_latency_ms@analytics > 50 window 2s cooldown 1s release 35: demote compaction
objective fairshare capacity 600MiB/s demands analytics=400MiB/s,compaction=200MiB/s
"""


# --------------------------------------------------------------------------- #
# DSL                                                                          #
# --------------------------------------------------------------------------- #
class TestQuantities:
    def test_parse_quantity(self):
        assert parse_quantity("100MiB/s") == 100 * MiB
        assert parse_quantity("4KiB") == 4096.0
        assert parse_quantity("1GiB/s") == float(1 << 30)
        assert parse_quantity(250) == 250.0
        assert parse_quantity("250") == 250.0
        with pytest.raises(PolicyError):
            parse_quantity("fast")

    def test_parse_duration(self):
        assert parse_duration("500ms") == pytest.approx(0.5)
        assert parse_duration("2s") == 2.0
        assert parse_duration(0.1) == 0.1
        with pytest.raises(PolicyError):
            parse_duration("soon")


class TestDSL:
    def test_text_to_policy(self):
        p = parse_policy_text(GUARD_TEXT, "serve_guard")
        assert p.name == "serve_guard" and p.stage == "serve"
        assert [f.name for f in p.flows] == ["analytics", "compaction"]
        assert p.flow("analytics").match_dict() == {"tenant": "analytics"}
        drl = p.flow("analytics").objects[0]
        assert drl.kind == "drl" and drl.params_dict()["rate"] == 100 * MiB
        (trig,) = p.triggers
        assert trig.when.metric == "latency_ms" and trig.when.agg == "p99"
        assert trig.when.flow == "analytics" and trig.when.window == 2.0
        assert trig.hysteresis == pytest.approx(15.0) and trig.cooldown == 1.0
        assert [a.op for a in trig.do] == ["demote"]
        assert [a.op for a in trig.release] == ["promote"]  # auto-paired
        assert p.objective.kind == "fairshare"

    def test_dict_round_trip(self):
        p1 = parse_policy_text(GUARD_TEXT, "serve_guard")
        p2 = policy_from_dict(policy_to_dict(p1))
        assert policy_to_dict(p2) == policy_to_dict(p1)

    def test_load_policy_accepts_everything(self):
        p = parse_policy_text(GUARD_TEXT, "serve_guard")
        assert load_policy(p) is p
        assert load_policy(policy_to_dict(p)).name == "serve_guard"
        assert load_policy(GUARD_TEXT, name="serve_guard").name == "serve_guard"

    def test_parse_errors(self):
        with pytest.raises(PolicyError, match="unknown classifier"):
            parse_policy_text("for color=red: limit bandwidth 1MiB/s")
        with pytest.raises(PolicyError, match="needs ': <action>'"):
            parse_policy_text("for tenant=a")
        with pytest.raises(PolicyError, match="unknown action verb"):
            parse_policy_text("for tenant=a: explode")
        with pytest.raises(PolicyError, match="unrecognized statement"):
            parse_policy_text("please be fast")
        with pytest.raises(PolicyError, match="bad 'when' head"):
            parse_policy_text("when latency is bad: demote x")

    def test_classifier_aliases(self):
        p = parse_policy_text("for workflow=7 as wf: limit bandwidth 1MiB/s")
        assert p.flow("wf").match_dict() == {"workflow_id": 7}

    def test_symbolic_request_type_resolves_to_int(self):
        """'type=read' must land on the same int code contexts hash, or the
        route would silently never match."""
        p = parse_policy_text("for type=read as rd: limit bandwidth 1MiB/s")
        assert p.flow("rd").match_dict() == {"request_type": int(RequestType.read)}
        with pytest.raises(PolicyError, match="unknown request_type"):
            parse_policy_text("for type=teleport as t: limit bandwidth 1MiB/s")

    def test_symbolic_request_type_routes(self):
        st = Stage("s", clock=VirtualClock())
        cp = ControlPlane()
        cp.register_stage(st)
        cp.install_policy("stage s\nfor type=read as rd: limit bandwidth 1MiB/s")
        assert st.select_channel(Context(1, RequestType.read, 1)) == "rd"
        assert st.select_channel(Context(1, RequestType.write, 1)) == "default"


# --------------------------------------------------------------------------- #
# compile validation                                                           #
# --------------------------------------------------------------------------- #
class TestCompile:
    def _infos(self, *stages):
        return {
            s: {"stage": s, "channels": {"default": {"objects": {"0": {"kind": "noop"}}}}}
            for s in stages
        }

    def test_unknown_stage_fails(self):
        p = parse_policy_text(GUARD_TEXT, "g")
        with pytest.raises(PolicyError, match="unknown stage 'serve'"):
            compile_policy(p, self._infos("other"))

    def test_unknown_object_kind_fails(self):
        p = policy_from_dict(
            {
                "policy": "p",
                "stage": "s",
                "flows": [{"name": "f", "match": {"tenant": "t"}, "objects": [{"kind": "warp_drive"}]}],
            }
        )
        with pytest.raises(PolicyError, match="unknown object kind"):
            compile_policy(p, self._infos("s"))

    def test_unknown_metric_fails(self):
        with pytest.raises(PolicyError, match="unknown metric"):
            compile_policy(
                parse_policy_text(
                    "stage s\nfor tenant=a: limit bandwidth 1MiB/s\nwhen vibes > 3: demote a"
                ),
                self._infos("s"),
            )

    def test_unknown_action_flow_fails(self):
        with pytest.raises(PolicyError, match="unknown flow"):
            compile_policy(
                parse_policy_text(
                    "stage s\nfor tenant=a: limit bandwidth 1MiB/s\nwhen iops@a > 3: demote ghost"
                ),
                self._infos("s"),
            )

    def test_demote_without_drl_fails(self):
        p = policy_from_dict(
            {
                "policy": "p",
                "stage": "s",
                "flows": [{"name": "f", "match": {"tenant": "t"}}],
                "triggers": [
                    {"when": {"metric": "iops", "flow": "f", "op": ">", "value": 1},
                     "do": [{"op": "demote", "flow": "f"}]}
                ],
            }
        )
        with pytest.raises(PolicyError, match="provisions no DRL"):
            compile_policy(p, self._infos("s"))

    def test_objective_demand_for_undeclared_flow_fails(self):
        with pytest.raises(PolicyError, match="undeclared flow"):
            compile_policy(
                parse_policy_text(
                    "stage s\nfor tenant=a: limit bandwidth 1MiB/s\n"
                    "objective fairshare capacity 10MiB/s demands ghost=1MiB/s"
                ),
                self._infos("s"),
            )

    def test_bad_object_params_fail_at_compile(self):
        p = policy_from_dict(
            {
                "policy": "p",
                "stage": "s",
                "flows": [
                    {"name": "f", "match": {"tenant": "t"},
                     "objects": [{"kind": "drl", "params": {"rate": 1e6, "burst": 2}}]}
                ],
            }
        )
        with pytest.raises(PolicyError, match="bad params"):
            compile_policy(p, self._infos("s"))

    def test_offline_compile_skips_stage_existence(self):
        compiled = compile_policy(parse_policy_text(GUARD_TEXT, "g"))
        assert "serve" in compiled.install
        assert compiled.algorithm is not None

    def test_match_resolution_in_actions(self):
        p = parse_policy_text(
            "stage s\nfor tenant=batch: limit bandwidth 8MiB/s\n"
            "when iops > 100: demote tenant=batch"
        )
        compiled = compile_policy(p, self._infos("s"))
        (trig,) = compiled.triggers
        (rule,) = trig.fire_rules["s"]
        assert rule.channel == "batch"
        assert rule.state["rate"] == pytest.approx(8 * MiB / 10)  # demote floor


# --------------------------------------------------------------------------- #
# rule wire round-trip                                                         #
# --------------------------------------------------------------------------- #
class TestWireRoundTrip:
    def test_compiled_rules_survive_wire(self):
        compiled = compile_policy(parse_policy_text(GUARD_TEXT, "g"))
        for rules in (*compiled.install.values(), *compiled.teardown.values()):
            assert rules_from_wire(rules_to_wire(rules)) == rules

    def test_remove_route_round_trip(self):
        r = HousekeepingRule(op="remove_route", channel="c", params={"match": {"tenant": "x"}})
        (back,) = rules_from_wire(rules_to_wire([r]))
        assert back == r


# --------------------------------------------------------------------------- #
# install → stage state → remove, over both transports                         #
# --------------------------------------------------------------------------- #
def _assert_guard_installed(st: Stage) -> None:
    assert set(st.channels()) >= {"analytics", "compaction"}
    assert st.channel("analytics").get_object("0").rate == 100 * MiB
    assert st.channel("compaction").get_object("0").rate == 50 * MiB
    assert st.select_channel(Context(1, RequestType.read, 1, "", tenant="analytics")) == "analytics"
    assert st.select_channel(Context(1, RequestType.read, 1, "bg_compaction_LN")) == "compaction"


def _assert_guard_removed(st: Stage) -> None:
    assert set(st.channels()) == {"default"}
    assert st.select_channel(Context(1, RequestType.read, 1, "", tenant="analytics")) == "default"
    assert st.select_channel(Context(1, RequestType.read, 1, "bg_compaction_LN")) == "default"


class TestLifecycle:
    def test_local_install_remove(self):
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        name = cp.install_policy(GUARD_TEXT)
        assert name == "serve_guard"
        _assert_guard_installed(st)
        (summary,) = cp.list_policies()
        assert summary["policy"] == "serve_guard"
        assert summary["objective"] == "fairshare"
        cp.remove_policy(name)
        assert cp.list_policies() == []
        _assert_guard_removed(st)

    def test_uds_install_remove(self):
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        with tempfile.TemporaryDirectory() as d:
            server = StageServer(st, f"{d}/paio.sock").start()
            try:
                cp = ControlPlane(clock=clk)
                cp.connect("serve", f"{d}/paio.sock")
                name = cp.install_policy(GUARD_TEXT)
                _assert_guard_installed(st)
                cp.remove_policy(name)
                _assert_guard_removed(st)
            finally:
                server.stop()

    def test_duplicate_install_rejected(self):
        st = Stage("serve", clock=VirtualClock())
        cp = ControlPlane()
        cp.register_stage(st)
        cp.install_policy(GUARD_TEXT)
        with pytest.raises(ValueError, match="already installed"):
            cp.install_policy(GUARD_TEXT)

    def test_install_validates_against_live_stage_info(self):
        st = Stage("other_stage", clock=VirtualClock())
        cp = ControlPlane()
        cp.register_stage(st)
        with pytest.raises(PolicyError, match="unknown stage 'serve'"):
            cp.install_policy(GUARD_TEXT)
        assert cp.list_policies() == []  # nothing half-installed

    def test_remove_while_fired_applies_release_rules(self):
        """A trigger fired against a pre-existing (non-policy-owned) object
        must not leave its enforcement state behind on uninstall."""
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        st.hsk_rule(HousekeepingRule(op="create_channel", channel="pre"))
        st.hsk_rule(
            HousekeepingRule(
                op="create_object", channel="pre", object_id="0", object_kind="drl",
                params={"rate": 100 * MiB},
            )
        )
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        name = cp.install_policy(
            {
                "policy": "guard",
                "stage": "s",
                "flows": [{"name": "victim", "match": {"tenant": "x"}, "channel": "pre"}],
                "triggers": [
                    {
                        "when": {"metric": "iops", "flow": "victim", "op": ">", "value": 10},
                        "do": [{"op": "set", "flow": "victim", "state": {"rate": 1.0}}],
                        "release": [{"op": "set", "flow": "victim", "state": {"rate": 100 * MiB}}],
                    }
                ],
            }
        )
        for _ in range(20):
            st.channel("pre").stats.record(1)
        clk.sleep(0.1)
        cp.run_once()
        assert st.channel("pre").get_object("0").rate == 1.0  # fired
        cp.remove_policy(name)
        # release rule ran on uninstall: the pre-existing DRL is restored
        assert st.channel("pre").get_object("0").rate == 100 * MiB
        assert "pre" in st.channels()  # pre-existing channel untouched

    def test_teardown_on_preexisting_channel_restores_default_noop(self):
        """A policy that provisioned a DRL at the default object id on a
        pre-existing channel must leave the channel enforceable on removal
        (default slot resets to Noop, it never becomes a hole)."""
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        st.hsk_rule(HousekeepingRule(op="create_channel", channel="shared"))
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        name = cp.install_policy(
            {
                "policy": "p",
                "stage": "s",
                "flows": [
                    {"name": "f", "match": {"tenant": "t"}, "channel": "shared",
                     "objects": [{"kind": "drl", "params": {"rate": 1e6}}]}
                ],
            }
        )
        assert st.channel("shared").get_object("0").kind == "drl"
        cp.remove_policy(name)
        assert "shared" in st.channels()  # pre-existing channel survives
        ctx = Context(1, RequestType.read, 8)
        r = st.channel("shared").enforce(ctx, b"x")  # must not raise
        assert r.content == b"x"
        assert st.channel("shared").enforce_batch([ctx] * 2)[0].wait_seconds == 0.0

    def test_slow_algorithm_cadence_honored(self):
        """The plane must not silently speed up an algorithm's loop: with no
        explicit plane interval the algorithm's own cadence governs."""
        algo = FairShareControl(flows={}, demands={}, loop_interval=1.0)
        assert ControlPlane(algo).effective_loop_interval() == 1.0
        assert ControlPlane(algo, loop_interval=0.05).effective_loop_interval() == 0.05
        assert ControlPlane().effective_loop_interval() == ControlPlane.DEFAULT_LOOP_INTERVAL

    def test_triggers_keep_tick_fast_despite_slow_objective(self):
        """A slow objective must not starve its own policy's triggers: any
        installed trigger floors the tick at the default interval."""
        st = Stage("s", clock=VirtualClock())
        cp = ControlPlane(clock=VirtualClock())
        cp.register_stage(st)
        cp.install_policy(
            "stage s\nfor tenant=a: limit bandwidth 10MiB/s\n"
            "when iops@a > 100: demote a\n"
            "objective fairshare capacity 10MiB/s loop_interval 5s demands a=10MiB/s"
        )
        assert cp.effective_loop_interval() == ControlPlane.DEFAULT_LOOP_INTERVAL

    def test_demote_rate_accepts_quantity_strings(self):
        p = policy_from_dict(
            {
                "policy": "p",
                "stage": "s",
                "flows": [
                    {"name": "f", "match": {"tenant": "t"},
                     "objects": [{"kind": "drl",
                                  "params": {"rate": "100MiB/s", "demote_rate": "10MiB/s"}}]}
                ],
                "triggers": [
                    {"when": {"metric": "iops", "flow": "f", "op": ">", "value": 1},
                     "do": [{"op": "demote", "flow": "f"}]}
                ],
            }
        )
        compiled = compile_policy(p)
        (rule,) = compiled.triggers[0].fire_rules["s"]
        assert rule.state["rate"] == 10 * MiB

    def test_removed_channel_gauges_go_absent_not_stale(self):
        """Gauges of a torn-down channel must disappear so triggers freeze
        (absent metric) instead of reacting to a stale constant."""
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        name = cp.install_policy("stage s\nfor tenant=a: limit bandwidth 1MiB/s")
        st.channel("a").stats.record(4096)
        clk.sleep(0.1)
        cp.run_once()
        assert "s.a.throughput" in cp.policy_runtime.registry.sample()
        cp.remove_policy(name)
        clk.sleep(0.1)
        cp.run_once()
        assert "s.a.throughput" not in cp.policy_runtime.registry.sample()

    def test_failed_install_rolls_back(self):
        """install_policy must not leave partial stage state when a rule
        fails mid-apply (e.g. a UDS stage rejecting a rule)."""
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        policy = {
            "policy": "p",
            "stage": "s",
            "flows": [
                {"name": "a", "match": {"tenant": "a"},
                 "objects": [{"kind": "drl", "params": {"rate": 1e6}}]},
                {"name": "b", "match": {"tenant": "b"},
                 "objects": [{"kind": "drl", "params": {"rate": 1e6}}]},
            ],
        }
        handle = cp._handles["s"]
        original = handle.hsk_rule
        calls = {"n": 0}

        def flaky(rule):
            calls["n"] += 1
            if calls["n"] == 4:  # fail midway through the second flow
                raise RuntimeError("stage rejected rule")
            return original(rule)

        handle.hsk_rule = flaky
        with pytest.raises(RuntimeError):
            cp.install_policy(policy)
        handle.hsk_rule = original
        assert cp.list_policies() == []
        assert set(st.channels()) == {"default"}  # rollback removed channel 'a'

    def test_tail_latency_objective_from_policy(self):
        from repro.core import TailLatencyControl

        compiled = compile_policy(load_policy("examples/policies/tail_latency.pol"))
        algo = compiled.algorithm
        assert isinstance(algo, TailLatencyControl)
        assert algo.kvs_b == 200 * MiB and algo.min_b == 10 * MiB
        assert algo.fg == FlowSpec("kvs", "fg")
        assert [s.channel for s in algo.ln] == ["ln"]
        # thin-wrapper round trip: to_policy carries the same parameters
        spec = algo.to_policy()
        again = TailLatencyControl.from_policy(spec)
        assert (again.kvs_b, again.min_b, again.fg) == (algo.kvs_b, algo.min_b, algo.fg)

    def test_objective_drives_rates_from_policy_file_alone(self):
        """FairShareControl behavior reproducible from the policy alone: the
        compiled objective's allocations match a hand-constructed Algorithm 2."""
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        cp.install_policy(GUARD_TEXT)
        clk.sleep(0.1)
        cp.run_once()
        hand = FairShareControl(
            flows={
                "analytics": FlowSpec("serve", "analytics"),
                "compaction": FlowSpec("serve", "compaction"),
            },
            demands={"analytics": 400 * MiB, "compaction": 200 * MiB},
            max_bandwidth=600 * MiB,
        )
        expect = hand.step({})  # demand-driven: stats-independent
        for rule in expect["serve"]:
            got = st.channel(rule.channel).get_object(rule.object_id).rate
            assert got == pytest.approx(rule.state["rate"])


# --------------------------------------------------------------------------- #
# windows + trigger semantics                                                  #
# --------------------------------------------------------------------------- #
class TestSlidingWindow:
    def test_aggregations(self):
        w = SlidingWindow(10.0)
        for i, v in enumerate([5.0, 1.0, 9.0, 3.0]):
            w.push(float(i), v)
        assert w.aggregate("last") == 3.0
        assert w.aggregate("mean") == pytest.approx(4.5)
        assert w.aggregate("min") == 1.0 and w.aggregate("max") == 9.0
        # nearest-rank percentiles (same scheme as telemetry.quantile)
        assert w.aggregate("p50") == 5.0 and w.aggregate("p99") == 9.0

    def test_pruning(self):
        w = SlidingWindow(1.0)
        w.push(0.0, 100.0)
        w.push(2.0, 1.0)
        assert len(w) == 1 and w.aggregate("max") == 1.0

    def test_rate(self):
        w = SlidingWindow(10.0)
        w.push(0.0, 0.0)
        w.push(4.0, 100.0)
        assert w.aggregate("rate") == pytest.approx(25.0)

    def test_empty(self):
        assert SlidingWindow(1.0).aggregate("mean") is None


def _mk_trigger(**kw) -> CompiledTrigger:
    base = dict(
        policy="p",
        name="t",
        metric_key="m",
        agg="last",
        op=">",
        value=50.0,
        window=10.0,
        hysteresis=0.0,
        cooldown=0.0,
        fire_rules={"s": ["FIRE"]},
        release_rules={"s": ["RELEASE"]},
    )
    base.update(kw)
    return CompiledTrigger(**base)


class TestTriggerEngine:
    def test_fire_and_release(self):
        eng = TriggerEngine()
        eng.add(_mk_trigger())
        assert eng.observe(0.0, {"m": 10.0}) == []
        (ev,) = eng.observe(1.0, {"m": 99.0})
        assert ev.kind == "fire" and ev.rules == {"s": ["FIRE"]}
        assert eng.observe(2.0, {"m": 99.0}) == []  # stays fired, no re-fire
        (ev,) = eng.observe(3.0, {"m": 10.0})
        assert ev.kind == "release" and ev.rules == {"s": ["RELEASE"]}

    def test_missing_metric_keeps_state(self):
        eng = TriggerEngine()
        eng.add(_mk_trigger())
        eng.observe(0.0, {"m": 99.0})
        assert eng.observe(1.0, {}) == []  # metric vanished: no release
        assert eng.states()["p/t"] == "fired"

    def test_hysteresis_no_flapping_under_oscillation(self):
        """A metric oscillating inside the hysteresis band must produce exactly
        one fire — and release only once it leaves the widened band."""
        eng = TriggerEngine()
        eng.add(_mk_trigger(hysteresis=20.0, window=0.5))
        transitions = []
        t = 0.0
        # oscillate between 45 and 60 around the threshold 50 (band: 30..50)
        for i in range(40):
            t += 0.25
            value = 60.0 if i % 2 == 0 else 45.0
            for ev in eng.observe(t, {"m": value}):
                transitions.append((ev.kind, value))
        assert transitions == [("fire", 60.0)]  # one fire, zero releases
        # leaving the band releases exactly once
        t += 0.25
        evs = eng.observe(t, {"m": 25.0})
        assert [e.kind for e in evs] == ["release"]

    def test_without_hysteresis_flapping_happens(self):
        """Sanity inverse: hysteresis=0 flaps on the same oscillation (this is
        the failure mode the hysteresis band exists to prevent)."""
        eng = TriggerEngine()
        eng.add(_mk_trigger(hysteresis=0.0, window=0.4))
        kinds = []
        t = 0.0
        for i in range(10):
            t += 0.25
            for ev in eng.observe(t, {"m": 60.0 if i % 2 == 0 else 45.0}):
                kinds.append(ev.kind)
        assert kinds.count("fire") > 1

    def test_cooldown_blocks_refire(self):
        eng = TriggerEngine()
        eng.add(_mk_trigger(cooldown=5.0, window=0.5))
        (ev,) = eng.observe(0.0, {"m": 99.0})
        assert ev.kind == "fire"
        eng.observe(1.0, {"m": 10.0})  # release
        assert eng.observe(2.0, {"m": 99.0}) == []  # within cooldown
        (ev,) = eng.observe(6.0, {"m": 99.0})  # cooldown elapsed
        assert ev.kind == "fire"

    def test_less_than_trigger_hysteresis(self):
        eng = TriggerEngine()
        eng.add(_mk_trigger(op="<", value=10.0, hysteresis=5.0, window=0.5))
        (ev,) = eng.observe(0.0, {"m": 3.0})
        assert ev.kind == "fire"
        assert eng.observe(1.0, {"m": 12.0}) == []  # inside band (release at 15)
        (ev,) = eng.observe(2.0, {"m": 16.0})
        assert ev.kind == "release"


# --------------------------------------------------------------------------- #
# end-to-end trigger reaction + pinning                                        #
# --------------------------------------------------------------------------- #
class TestTriggeredControl:
    def test_trigger_fires_within_one_tick_and_pins(self):
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        cp.install_policy(GUARD_TEXT)
        clk.sleep(0.1)
        cp.run_once()  # objective sets fair-share rates
        assert st.channel("compaction").get_object("0").rate == pytest.approx(200 * MiB)
        # drive p99 wait over 50 ms on the analytics channel, one collect tick
        st.channel("analytics").stats.record(100, wait=0.2)
        clk.sleep(0.1)
        cp.run_once()
        demoted = st.channel("compaction").get_object("0").rate
        assert demoted == pytest.approx(50 * MiB / 10)  # demote floor
        # fired trigger pins the DRL: the objective must not re-raise it
        clk.sleep(0.1)
        cp.run_once()
        assert st.channel("compaction").get_object("0").rate == pytest.approx(demoted)
        # quiet metric ages out of the 2 s window → release → objective resumes
        for _ in range(25):
            st.channel("analytics").stats.record(100, wait=0.0)
            clk.sleep(0.1)
            cp.run_once()
        assert st.channel("compaction").get_object("0").rate == pytest.approx(200 * MiB)

    def test_custom_registry_metric_drives_trigger(self):
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        cp.install_policy(
            "stage serve\nfor tenant=a: limit bandwidth 10MiB/s\n"
            "when gpu.queue_depth > 8: set rate=1MiB/s on a"
        )
        depth = {"v": 0.0}
        cp.policy_runtime.registry.register("gpu.queue_depth", lambda: depth["v"])
        clk.sleep(0.1)
        cp.run_once()
        assert st.channel("a").get_object("0").rate == 10 * MiB
        depth["v"] = 32.0
        clk.sleep(0.1)
        cp.run_once()
        assert st.channel("a").get_object("0").rate == 1 * MiB


# --------------------------------------------------------------------------- #
# control loop cadence gating                                                  #
# --------------------------------------------------------------------------- #
class TestCadenceGating:
    def _counting_algo(self, interval: float):
        from repro.core import ControlAlgorithm

        class Counting(ControlAlgorithm):
            loop_interval = interval

            def __init__(self):
                self.windows = []

            def step(self, stats):
                self.windows.append(
                    {n: s.per_channel.get("io") for n, s in stats.items()}
                )
                return {}

        return Counting()

    def test_slow_algorithm_steps_at_own_cadence_with_accumulated_windows(self):
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        st.hsk_rule(HousekeepingRule(op="create_channel", channel="io"))
        slow = self._counting_algo(1.0)
        cp = ControlPlane(slow, clock=clk, loop_interval=0.1)
        cp.register_stage(st)
        # 10 gated ticks at 0.1s: slow algorithm steps on the first tick and
        # once more after >= 1.0s, with the skipped windows folded together
        for _ in range(11):
            st.channel("io").stats.record(100)
            clk.sleep(0.1)
            cp.run_once(gated=True)
        assert len(slow.windows) == 2
        merged = slow.windows[1]["s"]
        assert merged.ops == 10  # ten accumulated ticks, not one sliver
        assert merged.window_seconds == pytest.approx(1.0)
        assert merged.throughput == pytest.approx(1000.0)

    def test_ungated_run_once_always_steps(self):
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        slow = self._counting_algo(10.0)
        cp = ControlPlane(slow, clock=clk)
        cp.run_once()
        cp.run_once()
        assert len(slow.windows) == 2  # synchronous API: every call steps

    def test_merge_snapshots(self):
        from repro.core.stats import StatsSnapshot, merge_snapshots

        a = StatsSnapshot("c", ops=2, bytes=100, window_seconds=1.0, throughput=100.0,
                          iops=2.0, cumulative_ops=2, cumulative_bytes=100, wait_seconds=0.1)
        b = StatsSnapshot("c", ops=4, bytes=300, window_seconds=3.0, throughput=100.0,
                          iops=4 / 3, cumulative_ops=6, cumulative_bytes=400,
                          inflight=1, wait_seconds=0.3)
        m = merge_snapshots(a, b)
        assert (m.ops, m.bytes, m.window_seconds) == (6, 400, 4.0)
        assert m.throughput == pytest.approx(100.0)
        assert m.iops == pytest.approx(1.5)
        assert (m.cumulative_ops, m.cumulative_bytes, m.inflight) == (6, 400, 1)
        assert m.wait_seconds == pytest.approx(0.4)


# --------------------------------------------------------------------------- #
# stats wait recording (the latency metric source)                             #
# --------------------------------------------------------------------------- #
class TestWaitStats:
    def test_wait_recorded_and_windowed(self):
        from repro.core.stats import ChannelStats

        clk = VirtualClock()
        cs = ChannelStats("c", clk)
        cs.record(100, wait=0.05)
        cs.record(100, wait=0.15)
        snap = cs.collect()
        assert snap.wait_seconds == pytest.approx(0.2)
        assert snap.mean_wait_ms == pytest.approx(100.0)
        assert cs.collect().wait_seconds == 0.0  # window reset

    def test_batch_wait_matches_sequential(self):
        clk = VirtualClock()
        a, b = Stage("a", clock=clk), Stage("b", clock=clk)
        for st in (a, b):
            st.hsk_rule(HousekeepingRule(op="create_channel", channel="io"))
            st.hsk_rule(
                HousekeepingRule(
                    op="create_object", channel="io", object_id="0", object_kind="drl",
                    params={"rate": 100.0},
                )
            )
            st.dif_rule(
                __import__("repro.core", fromlist=["DifferentiationRule"]).DifferentiationRule(
                    channel="io", match={"request_type": int(RequestType.read)}
                )
            )
        ctxs = [Context(1, RequestType.read, 30) for _ in range(4)]
        for c in ctxs:
            a.enforce(c)
        b.enforce_batch(ctxs)
        wa = a.collect().per_channel["io"].wait_seconds
        wb = b.collect().per_channel["io"].wait_seconds
        assert wa == pytest.approx(wb)
        assert wa > 0.0

    def test_custom_blocking_object_wait_recorded_in_batch(self):
        """Wait telemetry must be batch ≡ sequential for ANY blocking object,
        not just the kinds that track inflight (drl/priority_gate)."""
        from repro.core import EnforcementObject, Result

        class Sleepy(EnforcementObject):
            kind = "sleepy"

            def obj_enf(self, ctx, request=None):
                return Result(content=request, wait_seconds=0.01)

            def obj_config(self, state):
                pass

        clk = VirtualClock()
        st = Stage("s", clock=clk)
        st.install("slow", "0", Sleepy())
        st.dif_rule(
            __import__("repro.core", fromlist=["DifferentiationRule"]).DifferentiationRule(
                channel="slow", match={"tenant": "z"}
            )
        )
        ctxs = [Context(1, RequestType.read, 1, "", tenant="z") for _ in range(5)]
        st.enforce_batch(ctxs)
        assert st.collect().per_channel["slow"].wait_seconds == pytest.approx(0.05)

    def test_digit_string_classifier_aliases_int(self):
        """Wire clients sending workflow_id as a digit string must route the
        same as int contexts (the pre-packing str(p) behavior)."""
        from repro.core import DifferentiationRule, token_for

        assert token_for(("7",)) == token_for((7,))
        assert token_for(("-3",)) == token_for((-3,))
        # only canonical spellings alias: leading zeros keep string identity
        assert token_for(("01",)) != token_for(("1",))
        assert token_for(("007",)) != token_for((7,))
        assert token_for(("-0",)) != token_for((0,))
        st = Stage("s", clock=VirtualClock())
        st.hsk_rule(HousekeepingRule(op="create_channel", channel="w"))
        st.dif_rule(DifferentiationRule(channel="w", match={"workflow_id": "7"}))
        assert st.select_channel(Context(7, RequestType.read, 1)) == "w"


# --------------------------------------------------------------------------- #
# atomic versioned replace (install_policy(..., replace=True))                 #
# --------------------------------------------------------------------------- #
REPLACE_V1 = """
policy guard stage serve
for tenant=a as fa: limit bandwidth 100MiB/s
for tenant=b as fb: limit bandwidth 50MiB/s
"""

REPLACE_V2 = """
policy guard stage serve
for tenant=a as fa: limit bandwidth 200MiB/s
for tenant=c as fc: limit bandwidth 10MiB/s
"""


class TestAtomicReplace:
    def _plane(self):
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        return st, cp

    def test_replace_retunes_in_place_and_bumps_version(self):
        st, cp = self._plane()
        cp.install_policy(REPLACE_V1)
        (p,) = cp.list_policies()
        assert p["version"] == 1
        drl_before = st.channel("fa").get_object("0")
        assert drl_before.rate == 100 * MiB

        cp.install_policy(REPLACE_V2, replace=True)
        (p,) = cp.list_policies()
        assert p["version"] == 2
        assert sorted(p["flows"]) == ["fa", "fc"]
        # the surviving flow's live object was retuned, not recreated — the
        # zero-gap mechanism for carried-over entities
        drl_after = st.channel("fa").get_object("0")
        assert drl_after is drl_before
        assert drl_after.rate == 200 * MiB
        # dropped flow torn down, new flow provisioned
        assert st.channel("fb") is None
        assert st.channel("fc").get_object("0").rate == 10 * MiB
        ctx_c = Context(1, RequestType.read, 1, "", tenant="c")
        assert st.select_channel(ctx_c) == "fc"
        assert st.select_channel(Context(1, RequestType.read, 1, "", tenant="b")) == "default"

    def test_replace_without_flag_still_rejected(self):
        st, cp = self._plane()
        cp.install_policy(REPLACE_V1)
        with pytest.raises(ValueError, match="replace=True"):
            cp.install_policy(REPLACE_V2)

    def test_replace_acts_as_install_when_absent(self):
        st, cp = self._plane()
        cp.install_policy(REPLACE_V1, replace=True)
        assert cp.list_policies()[0]["version"] == 1
        assert st.channel("fa").get_object("0").rate == 100 * MiB

    def test_zero_enforcement_gap_under_traffic(self):
        """Traffic flowing through the stage during repeated replaces must be
        governed by exactly the old or the new rule set at every instant:
        the flow's route always resolves, its object slot always holds a DRL,
        and the observed rate is always one of the two versions'."""
        import threading as _threading

        st = Stage("serve")  # real clock: huge rates, so nothing blocks
        cp = ControlPlane()
        cp.register_stage(st)
        cp.install_policy(REPLACE_V1)
        allowed = {100 * MiB, 200 * MiB}
        ctx = Context(1, RequestType.read, 64, "", tenant="a")
        stop = _threading.Event()
        violations: list = []
        observed: set = set()

        def driver() -> None:
            # any exception IS a violation (e.g. channel momentarily absent):
            # record it rather than dying silently and vacuously passing
            try:
                while not stop.is_set():
                    chan_name = st.select_channel(ctx)
                    if chan_name != "fa":
                        violations.append(("route", chan_name))
                        continue
                    chan = st.channel("fa")
                    obj = chan.get_object("0") if chan is not None else None
                    if obj is None or obj.kind != "drl":
                        violations.append(("object", obj))
                        continue
                    rate = obj.rate
                    if rate not in allowed:
                        violations.append(("rate", rate))
                    observed.add(rate)
                    st.enforce(ctx)
            except Exception as exc:  # noqa: BLE001
                violations.append(("crash", repr(exc)))

        threads = [_threading.Thread(target=driver) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            import time as _time

            deadline = _time.monotonic() + 5.0
            while not observed and _time.monotonic() < deadline:
                _time.sleep(0.001)  # drivers demonstrably running before flips
            for i in range(30):
                cp.install_policy(REPLACE_V2 if i % 2 == 0 else REPLACE_V1, replace=True)
                _time.sleep(0.001)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
        assert violations == []
        assert observed == allowed  # traffic really saw both versions
        assert cp.list_policies()[0]["version"] == 31

    def test_replace_over_uds_transport(self):
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        with tempfile.TemporaryDirectory() as d:
            server = StageServer(st, f"{d}/paio.sock").start()
            try:
                cp = ControlPlane(clock=clk)
                cp.connect("serve", f"{d}/paio.sock")
                cp.install_policy(REPLACE_V1)
                drl_before = st.channel("fa").get_object("0")
                cp.install_policy(REPLACE_V2, replace=True)
                (p,) = cp.list_policies()
                assert p["version"] == 2
                assert sorted(p["flows"]) == ["fa", "fc"]
                # same in-place semantics as the local transport
                assert st.channel("fa").get_object("0") is drl_before
                assert st.channel("fa").get_object("0").rate == 200 * MiB
                assert st.channel("fb") is None
                assert st.channel("fc") is not None
            finally:
                server.stop()

    def test_replace_failure_restores_old_version(self):
        st, cp = self._plane()
        cp.install_policy(REPLACE_V1)
        handle = cp._handles["serve"]
        original = handle.hsk_rule

        def flaky(rule):
            if getattr(rule, "channel", None) == "fc":
                raise RuntimeError("stage rejected rule")
            return original(rule)

        handle.hsk_rule = flaky
        with pytest.raises(RuntimeError, match="stage rejected rule"):
            cp.install_policy(REPLACE_V2, replace=True)
        handle.hsk_rule = original
        # the old version is still the installed one and still governs —
        # at its ORIGINAL version (a failed replace must not advance what
        # an external monitor watches)
        (p,) = cp.list_policies()
        assert sorted(p["flows"]) == ["fa", "fb"]
        assert p["version"] == 1
        assert st.channel("fa").get_object("0").rate == 100 * MiB
        assert st.channel("fb").get_object("0").rate == 50 * MiB

    def test_failed_replace_restores_fired_trigger_clamp(self):
        """A fired trigger's protective clamp is released during replace (new
        triggers start armed); if the delta then fails, rollback must put the
        clamp BACK — not leave the flow running unprotected under the
        'restored' old policy."""
        clk = VirtualClock()
        st = Stage("serve", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        guarded = {
            "policy": "g", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [{"kind": "drl", "params": {"rate": 100 * MiB}}]}],
            "triggers": [{
                "when": {"metric": "iops", "flow": "f", "op": ">", "value": 10},
                "do": [{"op": "set", "flow": "f", "state": {"rate": 1.0}}],
                "release": [{"op": "set", "flow": "f", "state": {"rate": 100 * MiB}}],
            }],
        }
        cp.install_policy(guarded)
        for _ in range(20):
            st.channel("f").stats.record(1)
        clk.sleep(0.1)
        cp.run_once()
        assert st.channel("f").get_object("0").rate == 1.0  # clamped

        v2 = dict(guarded)
        v2["flows"] = guarded["flows"] + [
            {"name": "extra", "match": {"tenant": "b"},
             "objects": [{"kind": "drl", "params": {"rate": 1e6}}]},
        ]
        handle = cp._handles["serve"]
        original = handle.hsk_rule

        def flaky(rule):
            if getattr(rule, "channel", None) == "extra":
                raise RuntimeError("stage rejected rule")
            return original(rule)

        handle.hsk_rule = flaky
        with pytest.raises(RuntimeError):
            cp.install_policy(v2, replace=True)
        handle.hsk_rule = original
        # old policy restored at its version, the clamp is back on, AND the
        # restored trigger owns it (FIRED) — so it can still release
        (p,) = cp.list_policies()
        assert p["version"] == 1
        assert st.channel("f").get_object("0").rate == 1.0
        assert list(p["trigger_states"].values()) == ["fired"]
        # traffic stops → the restored-fired trigger releases the clamp
        clk.sleep(0.5)
        cp.run_once()
        assert st.channel("f").get_object("0").rate == 100 * MiB

    def test_replace_non_configurable_param_swaps_slot(self):
        """A changed param obj_config cannot apply faithfully (drl min_rate)
        must swap the object slot atomically, not silently no-op a retune."""
        st, cp = self._plane()
        base = {
            "policy": "p", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [{"kind": "drl", "params": {"rate": 1e6, "min_rate": 1.0}}]}],
        }
        cp.install_policy(base)
        before = st.channel("f").get_object("0")
        v2 = {
            "policy": "p", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [{"kind": "drl", "params": {"rate": 1e6, "min_rate": 500.0}}]}],
        }
        cp.install_policy(v2, replace=True)
        after = st.channel("f").get_object("0")
        assert after is not before  # slot swap, not a dropped retune
        assert after.min_rate == 500.0
        # rate-only change on the same policy DOES retune in place
        v3 = {
            "policy": "p", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [{"kind": "drl", "params": {"rate": 2e6, "min_rate": 500.0}}]}],
        }
        cp.install_policy(v3, replace=True)
        assert st.channel("f").get_object("0") is after
        assert st.channel("f").get_object("0").rate == 2e6

    def test_version_exported_as_metric(self):
        from repro.telemetry import render_prometheus

        st, cp = self._plane()
        cp.install_policy(REPLACE_V1)
        cp.install_policy(REPLACE_V2, replace=True)
        text = render_prometheus(cp.policy_runtime.registry)
        assert 'paio_policy_version{policy="guard"} 2' in text
        assert text.count("paio_policies_installed 1") == 1
        cp.remove_policy("guard")
        text = render_prometheus(cp.policy_runtime.registry)
        assert "paio_policy_version" not in text
        # exactly ONE installed-count row (a duplicate sample would make
        # Prometheus reject the whole scrape)
        installed_rows = [l for l in text.splitlines() if l.startswith("paio_policies_installed")]
        assert installed_rows == ["paio_policies_installed 0"]

    def test_failed_removal_rollback_restores_channel_with_objects(self):
        """A rollback that re-creates a dropped flow's channel must restore
        its enforcement objects too — a route pointing at a bare Noop channel
        would be exactly the unenforced window replace=True forbids."""
        st, cp = self._plane()
        cp.install_policy(REPLACE_V1)  # flows fa (100MiB/s) + fb (50MiB/s)
        only_fa = "policy guard stage serve\nfor tenant=a as fa: limit bandwidth 100MiB/s\n"
        handle = cp._handles["serve"]
        original = handle.hsk_rule

        def flaky(rule):
            # fail AFTER fb's route removal so its channel teardown (and the
            # rollback of it) is exercised
            if rule.op == "remove_channel" and rule.channel == "fb":
                raise RuntimeError("stage rejected rule")
            return original(rule)

        handle.hsk_rule = flaky
        with pytest.raises(RuntimeError):
            cp.install_policy(only_fa, replace=True)
        handle.hsk_rule = original
        (p,) = cp.list_policies()
        assert p["version"] == 1 and sorted(p["flows"]) == ["fa", "fb"]
        # fb is fully restored: channel, its DRL, and its route
        obj = st.channel("fb").get_object("0")
        assert obj is not None and obj.kind == "drl" and obj.rate == 50 * MiB
        assert st.select_channel(Context(1, RequestType.read, 1, "", tenant="b")) == "fb"

    def test_object_dropped_from_surviving_channel_is_removed(self):
        """An object the new version drops from a channel that survives the
        replace must actually be removed — owned channels have no per-object
        teardown to reuse, so the delta synthesizes it."""
        st, cp = self._plane()
        v1 = {
            "policy": "p", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [
                           {"kind": "drl", "id": "0", "params": {"rate": 1e6}},
                           {"kind": "checksum", "id": "1", "params": {}},
                       ]}],
        }
        cp.install_policy(v1)
        assert sorted(st.channel("f").object_ids()) == ["0", "1"]
        v2 = {
            "policy": "p", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [{"kind": "drl", "id": "0", "params": {"rate": 1e6}}]}],
        }
        cp.install_policy(v2, replace=True)
        # same channel + untouched DRL, but the checksum object is gone —
        # identical end state to a fresh install of v2
        assert st.channel("f").object_ids() == ["0"]

    def test_rehomed_flow_keeps_its_route(self):
        """Stage routing is channel-blind (keyed by match): moving a flow to
        a new channel in a replace is an overwrite of the same entry — the
        old version's remove_route must NOT delete it afterwards, and a
        failed replace must re-point it back, not leave it deleted."""
        st, cp = self._plane()
        cp.install_policy("policy g stage serve\nfor tenant=a as fa: limit bandwidth 100MiB/s\n")
        v2 = "policy g stage serve\nfor tenant=a as fx: limit bandwidth 200MiB/s\n"
        cp.install_policy(v2, replace=True)
        ctx = Context(1, RequestType.read, 1, "", tenant="a")
        assert st.select_channel(ctx) == "fx"  # still enforced, new home
        assert st.channel("fa") is None
        assert st.channel("fx").get_object("0").rate == 200 * MiB

        # failure mid-replace: the route must re-point to the CURRENT channel
        v3 = (
            "policy g stage serve\n"
            "for tenant=a as fy: limit bandwidth 300MiB/s\n"
            "for tenant=b as extra: limit bandwidth 1MiB/s\n"
        )
        handle = cp._handles["serve"]
        original = handle.hsk_rule

        def flaky(rule):
            if getattr(rule, "channel", None) == "extra":
                raise RuntimeError("stage rejected rule")
            return original(rule)

        handle.hsk_rule = flaky
        with pytest.raises(RuntimeError):
            cp.install_policy(v3, replace=True)
        handle.hsk_rule = original
        ctx2 = Context(2, RequestType.read, 1, "", tenant="a")
        assert st.select_channel(ctx2) == "fx"  # restored, not unrouted

    def test_added_param_forces_slot_swap(self):
        """A param ADDED by the new version is not retunable either — its
        rollback would need to unset it, which obj_config cannot express."""
        st, cp = self._plane()
        v1 = {
            "policy": "p", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [{"kind": "drl", "params": {"rate": 1e6}}]}],
        }
        cp.install_policy(v1)
        before = st.channel("f").get_object("0")
        v2 = {
            "policy": "p", "stage": "serve",
            "flows": [{"name": "f", "match": {"tenant": "a"},
                       "objects": [{"kind": "drl", "params": {"rate": 1e6, "refill_period": 10.0}}]}],
        }
        cp.install_policy(v2, replace=True)
        after = st.channel("f").get_object("0")
        assert after is not before
        assert after.refill_period == 10.0


# --------------------------------------------------------------------------- #
# trigger edge cases (satellite)                                               #
# --------------------------------------------------------------------------- #
class TestTriggerEdgeCases:
    def test_exact_threshold_strict_vs_inclusive(self):
        """An aggregate landing exactly on the threshold must NOT fire a ``>``
        trigger (strictly greater, as the DSL op reads) and MUST fire ``>=``;
        mirrored for ``<`` / ``<=``."""
        eng = TriggerEngine()
        eng.add(_mk_trigger(name="gt", op=">", value=50.0))
        eng.add(_mk_trigger(name="ge", op=">=", value=50.0))
        events = eng.observe(0.0, {"m": 50.0})
        assert [e.trigger.name for e in events] == ["ge"]

        eng = TriggerEngine()
        eng.add(_mk_trigger(name="lt", op="<", value=50.0))
        eng.add(_mk_trigger(name="le", op="<=", value=50.0))
        events = eng.observe(0.0, {"m": 50.0})
        assert [e.trigger.name for e in events] == ["le"]

    def test_fired_release_on_remove_over_uds(self):
        """remove_policy of a FIRED trigger must apply its release rules over
        the UDS transport exactly as it does locally."""
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        st.hsk_rule(HousekeepingRule(op="create_channel", channel="pre"))
        st.hsk_rule(
            HousekeepingRule(
                op="create_object", channel="pre", object_id="0", object_kind="drl",
                params={"rate": 100 * MiB},
            )
        )
        with tempfile.TemporaryDirectory() as d:
            server = StageServer(st, f"{d}/paio.sock").start()
            try:
                cp = ControlPlane(clock=clk)
                cp.connect("s", f"{d}/paio.sock")
                name = cp.install_policy(
                    {
                        "policy": "guard",
                        "stage": "s",
                        "flows": [{"name": "victim", "match": {"tenant": "x"}, "channel": "pre"}],
                        "triggers": [
                            {
                                "when": {"metric": "iops", "flow": "victim", "op": ">", "value": 10},
                                "do": [{"op": "set", "flow": "victim", "state": {"rate": 1.0}}],
                                "release": [
                                    {"op": "set", "flow": "victim", "state": {"rate": 100 * MiB}}
                                ],
                            }
                        ],
                    }
                )
                for _ in range(20):
                    st.channel("pre").stats.record(1)
                clk.sleep(0.1)
                cp.run_once()
                assert st.channel("pre").get_object("0").rate == 1.0  # fired
                assert cp.list_policies()[0]["trigger_states"] == {"guard/trigger0": "fired"}
                cp.remove_policy(name)
                assert st.channel("pre").get_object("0").rate == 100 * MiB
            finally:
                server.stop()

    def test_clock_jump_immunity_with_injected_clock(self):
        """All interval math runs on the injected clock: window eviction and
        cooldown follow it exactly, so a wall-clock step (NTP/suspend) that
        never touches the monotonic clock cannot corrupt windows or pin a
        cooldown. Simulated by driving the engine purely off a fake clock
        while wall time is irrelevant."""
        clk = VirtualClock(start=1000.0)
        eng = TriggerEngine(clock=clk)
        eng.add(_mk_trigger(agg="last", window=5.0, cooldown=60.0, hysteresis=0.0))

        # warm the window below threshold, then cross it — observe(None, ...)
        # timestamps samples off the injected clock
        assert eng.observe(None, {"m": 10.0}) == []
        clk.sleep(1.0)
        (ev,) = eng.observe(None, {"m": 1000.0})
        assert ev.kind == "fire" and ev.at == pytest.approx(1001.0)

        # release, then verify the cooldown pins re-fire on the fake clock
        clk.sleep(1.0)
        (ev,) = eng.observe(None, {"m": 0.0})
        assert ev.kind == "release"
        clk.sleep(10.0)  # old samples (< t+5s) evicted: window holds only new
        assert eng.observe(None, {"m": 99.0}) == []  # within cooldown: pinned
        clk.sleep(60.0)  # cooldown elapsed on the *injected* clock
        (ev,) = eng.observe(None, {"m": 99.0})
        assert ev.kind == "fire"  # not pinned for hours: monotonic interval math

    def test_window_eviction_follows_injected_clock(self):
        clk = VirtualClock()
        eng = TriggerEngine(clock=clk)
        eng.add(_mk_trigger(agg="max", window=2.0, value=50.0))
        eng.observe(None, {"m": 100.0})  # would fire on max; it does
        clk.sleep(5.0)
        # the old 100.0 sample is beyond the 2 s window: max is now 10.0, so
        # the fired trigger releases instead of staying latched on stale data
        (ev,) = eng.observe(None, {"m": 10.0})
        assert ev.kind == "release"


class TestRollbackErrorChaining:
    def test_failed_undo_attaches_context(self):
        """A failing rollback must not mask the install error: the original
        exception propagates with the undo failure chained as __context__,
        remaining undo rules still run, and list_policies stays empty."""
        clk = VirtualClock()
        st = Stage("s", clock=clk)
        cp = ControlPlane(clock=clk)
        cp.register_stage(st)
        policy = {
            "policy": "p",
            "stage": "s",
            "flows": [
                {"name": "a", "match": {"tenant": "a"},
                 "objects": [{"kind": "drl", "params": {"rate": 1e6}}]},
                {"name": "b", "match": {"tenant": "b"},
                 "objects": [{"kind": "drl", "params": {"rate": 1e6}}]},
            ],
        }
        handle = cp._handles["s"]
        original = handle.hsk_rule
        undo_failures = {"n": 0}

        def flaky(rule):
            if rule.op == "create_object" and rule.channel == "b":
                raise RuntimeError("install failed")
            if rule.op == "remove_route" and undo_failures["n"] == 0:
                undo_failures["n"] += 1
                raise OSError("undo also failed")
            return original(rule)

        handle.hsk_rule = flaky
        with pytest.raises(RuntimeError, match="install failed") as excinfo:
            cp.install_policy(policy)
        handle.hsk_rule = original
        assert isinstance(excinfo.value.__context__, OSError)
        assert cp.list_policies() == []
        # undo continued past the failing rule: both channels removed
        assert set(st.channels()) == {"default"}
