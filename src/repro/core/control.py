"""SDS control plane (paper §3.2, §4.1, §4.3).

The control plane is a logically-centralized entity that orchestrates stages
through the five-call control interface. Communication is over UNIX Domain
Sockets (paper §4.3) through the :mod:`repro.transport` subsystem — binary
pipelined frames when both ends speak v2, the newline-delimited JSON protocol
against older peers; an in-process transport with identical semantics is
provided for embedded deployments and deterministic tests.

Control algorithms (paper §5) are pluggable ``ControlAlgorithm`` objects run in
a feedback loop: ``collect → compute → enf_rules → sleep(loop_interval)``.
"""
from __future__ import annotations

import functools
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor, wait as futures_wait
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.ft.monitor import HeartbeatMonitor, StragglerReport
from repro.telemetry.spans import span

# submodule imports (not the repro.transport package) so that importing
# repro.transport first doesn't hit a partially-initialized package cycle
from repro.transport.handle import (
    TRANSPORT_ERRORS,
    CircuitBreaker,
    RemoteStageHandle,
    RetryPolicy,
    RuleShipError,
)
from repro.transport.server import StageServer

from .clock import Clock, DEFAULT_CLOCK
from .rules import DifferentiationRule, EnforcementRule, HousekeepingRule
from .shard import shard_stage_names
from .stage import Stage
from .stats import StageStats, fleet_view


# --------------------------------------------------------------------------- #
# transports                                                                   #
# --------------------------------------------------------------------------- #
class StageHandle:
    """Control-plane-side view of one data plane stage (Table 2 calls)."""

    def stage_info(self) -> Dict[str, Any]:
        raise NotImplementedError

    def hsk_rule(self, rule: HousekeepingRule) -> bool:
        raise NotImplementedError

    def dif_rule(self, rule: DifferentiationRule) -> bool:
        raise NotImplementedError

    def enf_rule(self, rule: EnforcementRule) -> bool:
        raise NotImplementedError

    def collect(self) -> StageStats:
        raise NotImplementedError


class LocalStageHandle(StageHandle):
    """In-process transport: direct calls into the stage object."""

    def __init__(self, stage: Stage) -> None:
        self._stage = stage

    def stage_info(self) -> Dict[str, Any]:
        return self._stage.stage_info()

    def hsk_rule(self, rule: HousekeepingRule) -> bool:
        return self._stage.hsk_rule(rule)

    def dif_rule(self, rule: DifferentiationRule) -> bool:
        return self._stage.dif_rule(rule)

    def enf_rule(self, rule: EnforcementRule) -> bool:
        return self._stage.enf_rule(rule)

    def collect(self) -> StageStats:
        return self._stage.collect()


# StageServer and RemoteStageHandle now live in repro.transport (binary
# pipelined v2 protocol + JSON-line v1 fallback); re-exported here — and from
# repro.core — so existing imports keep working.
#
# TRANSPORT_ERRORS (also from repro.transport): exception types treated as
# "the transport/stage died" (stage marked down) rather than control-plane
# bugs (propagated).


# --------------------------------------------------------------------------- #
# fleet state (liveness tracking per registered stage)                         #
# --------------------------------------------------------------------------- #


@dataclass
class StageState:
    """Liveness + bookkeeping for one registered stage (control-plane side).

    ``deferred`` holds rules destined for the stage while it is DOWN, keyed so
    that repeated enforcement retunes of the same (channel, object) collapse
    to the latest one; they are replayed in order on re-admission, so a
    recovered stage converges to the rules it missed instead of silently
    dropping them.
    """

    up: bool = True
    failures: int = 0  #: up→down transitions observed
    recoveries: int = 0  #: down→up transitions observed
    down_since: float = 0.0  #: plane-clock time of the last up→down transition
    last_error: str = ""
    #: UDS path to reconnect on recovery probes (None → probe the live handle)
    socket_path: Optional[str] = None
    timeout: float = 5.0
    #: protocol preference to reconnect with ("auto" renegotiates, so a stage
    #: that restarted on a different version is re-admitted either way)
    protocol: str = "auto"
    last_probe: float = -float("inf")
    deferred: Dict[Tuple, Any] = field(default_factory=dict)
    _defer_seq: int = 0
    #: snapshot version the stage reported at its last (re)admission — >0
    #: means the stage restored enforcement from its config journal before
    #: the plane reached it (see repro.core.snapshot)
    snapshot_version: int = 0

    def defer(self, rule: Any) -> None:
        if isinstance(rule, EnforcementRule):
            # latest state per target wins (dict insert keeps first position,
            # so replay order still reflects first-submission order)
            self.deferred[("enf", rule.channel, rule.object_id)] = rule
        else:
            self._defer_seq += 1
            self.deferred[("seq", self._defer_seq)] = rule


# --------------------------------------------------------------------------- #
# control plane                                                                #
# --------------------------------------------------------------------------- #
class ControlAlgorithm:
    """One feedback-loop iteration over the registered stages.

    ``step`` receives {stage_name: StageStats} and returns the enforcement
    rules to submit, keyed by stage name.
    """

    loop_interval: float = 0.1

    def setup(self, handles: Dict[str, StageHandle]) -> None:
        """Install housekeeping/differentiation rules (startup phase)."""

    def step(self, stats: Dict[str, StageStats]) -> Dict[str, List[EnforcementRule]]:
        raise NotImplementedError


class ControlPlane:
    """Runs the monitor→rule feedback loop (paper §4.2) over registered stages.

    Two sources of control co-exist on the same loop:

    * a programmatic :class:`ControlAlgorithm` (optional, the paper's §5 path),
    * installed *policies* (:mod:`repro.policy`): declarative flow
      provisioning, metrics-driven triggers, and policy objectives that lower
      to ControlAlgorithms. The lifecycle — ``install_policy`` /
      ``remove_policy`` / ``list_policies`` — goes through the same
      StageHandle interface as everything else, so it has identical semantics
      for embedded stages and stages reached over the UDS transport.
    """

    #: loop cadence when neither an algorithm nor the constructor names one
    DEFAULT_LOOP_INTERVAL = 0.1
    #: fan-out worker cap (fleet sizes beyond this queue, still correct)
    MAX_FANOUT_WORKERS = 32

    def __init__(
        self,
        algorithm: Optional[ControlAlgorithm] = None,
        clock: Clock = DEFAULT_CLOCK,
        loop_interval: Optional[float] = None,
        registry=None,
        concurrent: bool = True,
        stage_deadline: float = 1.0,
        probe_interval: float = 0.5,
        retry: Any = "default",
        breaker: bool = True,
        heartbeats: Optional[HeartbeatMonitor] = None,
    ) -> None:
        self.algorithm = algorithm
        self._clock = clock
        #: metric registry the policy runtime publishes into; None → the
        #: process-wide shared registry (repro.telemetry.get_registry)
        self._registry = registry
        #: explicit plane-level tick cadence; None defers to the algorithms'
        #: own intervals. The loop *ticks* (collect + triggers) at the fastest
        #: requested cadence; each algorithm *steps* at its own loop_interval
        #: with skipped ticks' stat windows accumulated (see _algorithm_stats)
        self.loop_interval = loop_interval
        #: fan collect + rule shipping out over a thread pool (loop latency is
        #: max(stage), not sum(stage)); False forces the sequential path —
        #: useful for benchmarking and single-threaded determinism
        self.concurrent = concurrent
        #: per-stage budget (wall seconds) for one collect/ship round; a stage
        #: exceeding it is marked DOWN for this tick and skipped
        self.stage_deadline = stage_deadline
        #: minimum plane-clock seconds between recovery probes of a DOWN stage
        self.probe_interval = probe_interval
        #: retry policy handed to connect()-created handles for their
        #: idempotent calls: "default" → a seeded exponential-backoff policy,
        #: None → one attempt per call (pre-resilience behavior), or any
        #: RetryPolicy. One shared policy is fine — it is thread-safe and
        #: per-call state is local to the handle.
        self._retry: Optional[RetryPolicy] = (
            RetryPolicy(seed=0) if retry == "default" else retry
        )
        #: per-stage circuit breakers (created on connect, survive handle
        #: swaps across down/probe/recover cycles so breaker history is a
        #: property of the stage, not of one socket)
        self._breaker_enabled = breaker
        self._breakers: Dict[str, CircuitBreaker] = {}
        #: fleet heartbeat/straggler monitor: every successful collect beats
        #: it with the stage's collect latency as the "step time", so
        #: fleet_status() carries dead/straggler verdicts and
        #: squeeze_stragglers() can act on them — one liveness mechanism,
        #: not two disconnected ones
        self.heartbeats = (
            heartbeats if heartbeats is not None else HeartbeatMonitor(clock=clock)
        )
        self._handles: Dict[str, StageHandle] = {}
        #: per-stage liveness + deferred-rule state; guarded by _fleet_lock
        self._stage_states: Dict[str, StageState] = {}
        self._fleet_lock = threading.Lock()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._exporters: List[Any] = []  # exporters started via serve_metrics
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._policy_lock = threading.Lock()
        self._policy_runtime = None  # lazy: created on first install_policy
        #: per-algorithm loop state (last step time + accumulated stats) for
        #: cadence gating; weak keys so removed policies' algorithms drop out
        self._algo_states: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self.iterations = 0
        self.history: List[Dict[str, StageStats]] = []
        self.keep_history = False

    def register(self, name: str, handle: StageHandle) -> None:
        """Register (or re-register) a stage. Re-registering a DOWN stage is
        a *manual recovery*: the old handle is closed, the stage comes back
        UP, and the rules it missed while down are replayed — same contract
        as probe-driven re-admission."""
        with self._fleet_lock:
            old_handle = self._handles.get(name)
            self._handles[name] = handle
            state = self._stage_states.get(name)
            if state is None:
                state = self._stage_states[name] = StageState()
            if not state.up:
                state.recoveries += 1
            state.up = True
            state.socket_path = None
            if isinstance(handle, RemoteStageHandle):
                state.socket_path = handle.socket_path
                state.timeout = handle.timeout
                state.protocol = handle.protocol
            deferred = list(state.deferred.values())
            state.deferred.clear()
        if old_handle is not None and old_handle is not handle and hasattr(old_handle, "close"):
            try:
                old_handle.close()
            except Exception:  # noqa: BLE001 — replaced handle may be dead
                pass
        self._publish_stage_up(name, True)
        deferred = self._squash_deferred(name, deferred)
        if deferred:
            self._ship_rules(name, deferred)

    def register_stage(self, stage: Stage) -> None:
        self.register(stage.name, LocalStageHandle(stage))

    def connect(
        self, name: str, socket_path: str, timeout: float = 5.0, protocol: str = "auto"
    ) -> None:
        """Register a stage reached over UDS. ``protocol`` is the transport
        preference (``auto`` negotiates binary v2 and falls back to the v1
        JSON-line protocol, ``binary``/``json`` force one end of that) — a
        fleet can mix v1 and v2 stages on one plane with identical
        semantics.

        Handles created here get the plane's resilience defaults: idempotent
        calls retry with backoff (``retry=None`` in the constructor disables
        this), and the stage's circuit breaker — shared across reconnects —
        fails fast once the stage keeps dying (``paio_stage_breaker_state``).
        """
        self.register(
            name,
            RemoteStageHandle(
                socket_path,
                timeout=timeout,
                protocol=protocol,
                retry=self._retry,
                breaker=self._breaker_for(name),
                name=name,
                registry=self._registry,
            ),
        )

    def connect_sharded(
        self,
        logical: str,
        socket_paths: Sequence[str],
        timeout: float = 5.0,
        protocol: str = "auto",
    ) -> List[str]:
        """Register the N shard stages of logical stage ``logical`` (shard
        router deployment: one stage process per socket path). Each shard
        registers as ``<logical>/<i>`` — an ordinary stage to everything
        downstream, so liveness, deferred-rule replay, and ``scope: global``
        grant splitting apply per shard with no special casing; a policy's
        ``shards: N`` stanza binds its global flows to exactly these names.
        Returns the shard stage names."""
        names = shard_stage_names(logical, len(socket_paths))
        for name, path in zip(names, socket_paths):
            self.connect(name, path, timeout=timeout, protocol=protocol)
        return names

    def _breaker_for(self, name: str) -> Optional[CircuitBreaker]:
        if not self._breaker_enabled:
            return None
        with self._fleet_lock:
            br = self._breakers.get(name)
            if br is None:
                br = self._breakers[name] = CircuitBreaker(
                    name=name, registry=self._registry
                )
            return br

    # -- fleet liveness ------------------------------------------------------
    def _metric_registry(self):
        if self._registry is not None:
            return self._registry
        from repro.telemetry import get_registry  # local: avoid import cycle

        return get_registry()

    def _publish_stage_up(self, name: str, up: bool) -> None:
        registry = self._metric_registry()
        key = f"stage.{name}.up"
        registry.set_gauge(key, 1.0 if up else 0.0)
        registry.describe(key, "paio_stage_up", {"stage": name})

    def _mark_down(
        self, name: str, exc: BaseException, handle: Optional[StageHandle] = None
    ) -> None:
        with self._fleet_lock:
            state = self._stage_states.get(name)
            if state is None or not state.up:
                return  # already down (or unregistered): one transition only
            if handle is not None and self._handles.get(name) is not handle:
                # a STALE worker (blocked on a handle that has since been
                # swapped by recovery) must not take the recovered stage down
                return
            state.up = False
            state.failures += 1
            state.down_since = self._clock.now()
            state.last_probe = state.down_since
            state.last_error = repr(exc)
        registry = self._metric_registry()
        self._publish_stage_up(name, False)
        key = f"stage.{name}.down"
        registry.inc(key)
        registry.describe(key, "paio_stage_down", {"stage": name})

    def _recover(
        self,
        name: str,
        fresh_handle: Optional[StageHandle],
        info: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Re-admit a DOWN stage: swap in the reconnected handle (UDS) and
        replay the rules deferred while it was away, in submission order with
        same-target enforcement retunes collapsed to the latest.

        When the probe's ``stage_info`` is passed in, recovery also
        **reconciles** the stage against the installed policy set: a stage
        that restored its configuration from a snapshot (``snapshot_version``
        in the info) gets nothing re-shipped unless an entity is actually
        missing; a stage that came back empty gets the full install programs
        of the policies that own it. See
        :func:`repro.policy.engine.missing_install_rules`."""
        with self._fleet_lock:
            state = self._stage_states.get(name)
            if state is None:
                return
            old_handle = self._handles.get(name)
            if fresh_handle is not None:
                self._handles[name] = fresh_handle
            state.up = True
            state.recoveries += 1
            if info is not None:
                state.snapshot_version = int(info.get("snapshot_version") or 0)
            deferred = list(state.deferred.values())
            state.deferred.clear()
        if fresh_handle is not None and old_handle is not None and hasattr(old_handle, "close"):
            try:
                old_handle.close()
            except Exception:  # noqa: BLE001 — the socket is already dead
                pass
        breaker = self._breakers.get(name)
        if breaker is not None:
            breaker.success()  # re-admission closes the circuit
        self._publish_stage_up(name, True)
        # reconcile BEFORE deferred replay: missing install programs restore
        # the entities (channels/objects/routes) the deferred retunes target
        if info is not None:
            reconcile = self._reconcile_rules(name, info)
            if reconcile:
                self._ship_rules(name, reconcile)
        deferred = self._squash_deferred(name, deferred)
        if deferred:
            self._ship_rules(name, deferred)

    def _reconcile_rules(self, name: str, info: Dict[str, Any]) -> List[Any]:
        """Install rules a recovered stage is missing relative to the
        installed policy set (empty when no policies are installed or the
        stage's snapshot restore already covers them)."""
        if self._policy_runtime is None:
            return []
        from repro.policy.engine import missing_install_rules

        return missing_install_rules(self._policy_runtime.installed(), name, info)

    def _squash_deferred(self, name: str, deferred: List[Any]) -> List[Any]:
        """Reconcile a recovering stage's deferred rules with the *currently*
        installed policy set before replay.

        A DOWN window can span policy changes: a policy removed while the
        stage was away left its teardown (remove channel/object/route) in the
        deferred queue, and a successor policy may since have (re)claimed the
        same entities. Replaying those housekeeping ops verbatim would tear
        down live policy state the moment the stage recovers. Any deferred
        remove op whose target an installed policy's install program creates
        on this stage is obsolete — the entity must exist — and is dropped;
        everything else (enforcement retunes, removes of genuinely unclaimed
        entities, creates) replays in order as before.

        Entity identity uses the policy compiler's own keying
        (``_install_key``/``_teardown_key``), including its channel-BLIND
        route identity: stage routing tables are keyed by classifier match,
        not target channel, so a stale ``remove_route`` would delete a
        successor policy's route even when the flow was re-homed to a
        different channel.
        """
        if not deferred or self._policy_runtime is None:
            return deferred
        # lazy: the policy subsystem stays an optional import for planes
        # that never install policies (and then there is nothing to squash)
        from repro.policy.compile import _install_key, _teardown_key

        owned: set = set()
        for compiled in self._policy_runtime.installed():
            for rule in compiled.install.get(name, ()):
                key = _install_key(rule)
                if key is not None:
                    owned.add(key)
        if not owned:
            return deferred
        owned_routes = {(k[2], k[3]) for k in owned if k[0] == "route"}
        kept: List[Any] = []
        for rule in deferred:
            key = _teardown_key(rule) if isinstance(rule, HousekeepingRule) else None
            if key is not None:
                if key in owned:
                    continue  # obsolete: a live policy owns this entity now
                if key[0] == "route" and (key[2], key[3]) in owned_routes:
                    continue  # channel-blind: the match is claimed elsewhere
            kept.append(rule)
        return kept

    def _probe_down_stages(self) -> None:
        """Attempt re-admission of DOWN stages (rate-limited per stage by
        ``probe_interval`` on the plane clock). UDS stages reconnect on a
        fresh socket — the old handle may hold a desynchronized stream —
        and must answer ``stage_info`` before being re-admitted."""
        now = self._clock.now()
        probes: List[Tuple[str, StageState, Optional[StageHandle]]] = []
        with self._fleet_lock:
            for name, state in self._stage_states.items():
                if state.up or (now - state.last_probe) < self.probe_interval:
                    continue
                state.last_probe = now
                probes.append((name, state, self._handles.get(name)))
        for name, state, handle in probes:
            fresh: Optional[RemoteStageHandle] = None
            try:
                if state.socket_path is not None:
                    # the probe handle is built bare — no retry (the probe IS
                    # the rate-limited retry) and no breaker (a probe is the
                    # half-open trial; the plane's probe_interval already
                    # paces it). Resilience is attached once the stage
                    # answers, so the recovered handle has it.
                    fresh = RemoteStageHandle(
                        state.socket_path,
                        timeout=state.timeout,
                        protocol=state.protocol,
                        name=name,
                        registry=self._registry,
                    )
                    info = fresh.stage_info()
                    fresh.retry = self._retry
                    fresh.breaker = self._breaker_for(name)
                    self._recover(name, fresh, info)
                elif handle is not None:
                    info = handle.stage_info()
                    self._recover(name, None, info)
            except TRANSPORT_ERRORS as exc:
                state.last_error = repr(exc)
                if fresh is not None:
                    fresh.close()

    def stage_up(self, name: str) -> bool:
        with self._fleet_lock:
            state = self._stage_states.get(name)
            return bool(state is not None and state.up)

    def fleet_status(self) -> Dict[str, Dict[str, Any]]:
        """Per-stage liveness snapshot: ``up``, transition counters, the last
        transport error, how many rules are deferred awaiting recovery, the
        stage's last-reported snapshot version, the heartbeat monitor's
        verdict (``ok`` / ``straggler`` / ``dead`` / None before any beat),
        and the circuit-breaker state (0 closed / 1 open / 2 half-open)."""
        hb = self.heartbeats.report()
        breakers = dict(self._breakers)
        with self._fleet_lock:
            return {
                name: {
                    "up": state.up,
                    "failures": state.failures,
                    "recoveries": state.recoveries,
                    "down_since": state.down_since if not state.up else None,
                    "last_error": state.last_error or None,
                    "deferred_rules": len(state.deferred),
                    "snapshot_version": state.snapshot_version,
                    "heartbeat": (
                        "dead"
                        if name in hb.dead
                        else "straggler"
                        if name in hb.stragglers
                        else "ok"
                        if name in hb.per_host_step
                        else None
                    ),
                    "breaker": (
                        breakers[name].state if name in breakers else None
                    ),
                    "transport": "uds" if state.socket_path else "local",
                    # negotiated wire protocol (None for local handles):
                    # "binary" = v2 pipelined frames, "jsonl" = v1 fallback
                    "protocol": (
                        ("binary" if getattr(self._handles.get(name), "proto", 1) == 2 else "jsonl")
                        if state.socket_path
                        else None
                    ),
                }
                for name, state in self._stage_states.items()
            }

    def squeeze_stragglers(
        self, rules_for: Callable[[str, StragglerReport], List[Any]]
    ) -> Dict[str, List[Any]]:
        """Act on the heartbeat monitor's straggler verdicts: ``rules_for``
        maps each flagged stage (plus the full report, for context like the
        fleet median step) to the squeeze rules to apply — typically
        enforcement rules dropping the stage's background DRL rates to
        ``min_b``, the paper's Algorithm 1 philosophy applied to fleet
        health. Rules ship through :meth:`_ship_rules` like everything else,
        so a straggler that dies mid-squeeze gets its rules deferred and
        replayed on recovery, not dropped. Returns {stage: applied rules}."""
        report = self.heartbeats.report()
        to_ship: Dict[str, List[Any]] = {}
        for name in report.stragglers:
            rules = rules_for(name, report)
            if rules:
                to_ship[name] = list(rules)
        return self._ship_fanout(to_ship)

    def _fanout_pool(self) -> ThreadPoolExecutor:
        """The (lazily created, fixed-size) fan-out pool. A fixed worker cap
        with on-demand thread spawning means the pool is never replaced, so
        concurrent callers (the loop thread + an admin install) can never
        race a shutdown-and-swap into a dead executor."""
        with self._fleet_lock:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=self.MAX_FANOUT_WORKERS, thread_name_prefix="paio-cp-fanout"
                )
            return self._executor

    def _note_stale_failure(self, name: str):
        """Done-callback for futures abandoned by a deadline: a worker that
        later dies with a NON-transport error (a control-plane bug —
        _ship_rules swallows transport errors itself) must leave a trace, not
        vanish into a dropped Future."""

        def callback(fut) -> None:
            if fut.cancelled():
                return
            exc = fut.exception()
            if exc is not None:
                with self._fleet_lock:
                    state = self._stage_states.get(name)
                    if state is not None:
                        state.last_error = repr(exc)

        return callback

    def _fanout(self, tasks, op_name: str) -> Dict[str, Any]:
        """Run ``tasks`` — ``(name, handle_or_None, thunk)`` triples — one
        worker per stage, each wave of ``MAX_FANOUT_WORKERS`` given a
        ``stage_deadline`` budget (stages beyond the cap queue behind the
        first wave and must not be blamed for its latency). Returns
        {name: thunk result}; a task that raises a transport error or blows
        the deadline gets its stage marked DOWN (scoped to ``handle`` when
        given, so stale workers cannot take down a recovered stage).
        ``concurrent=False`` (or a single task) runs inline, in order."""
        out: Dict[str, Any] = {}
        if not self.concurrent or len(tasks) <= 1:
            for name, handle, thunk in tasks:
                try:
                    out[name] = thunk()
                except TRANSPORT_ERRORS as exc:
                    self._mark_down(name, exc, handle)
            return out
        pool = self._fanout_pool()
        futures = {pool.submit(thunk): (name, handle) for name, handle, thunk in tasks}
        waves = -(-len(tasks) // self.MAX_FANOUT_WORKERS)
        done, pending = futures_wait(futures, timeout=self.stage_deadline * waves)
        for fut in done:
            name, handle = futures[fut]
            try:
                out[name] = fut.result()
            except TRANSPORT_ERRORS as exc:
                self._mark_down(name, exc, handle)
        for fut in pending:
            fut.cancel()
            name, handle = futures[fut]
            self._mark_down(
                name,
                TimeoutError(f"{op_name} exceeded the {self.stage_deadline}s stage deadline"),
                handle,
            )
            fut.add_done_callback(self._note_stale_failure(name))
        return out

    def _live_handles(self) -> List[Tuple[str, StageHandle]]:
        with self._fleet_lock:
            return [
                (name, h)
                for name, h in self._handles.items()
                if self._stage_states[name].up
            ]

    def _collect_all(self) -> Dict[str, StageStats]:
        """Collect stats from every UP stage. A stage that errors or blows
        the ``stage_deadline`` budget is marked DOWN and skipped; its metrics
        vanish from this tick (trigger windows freeze rather than see a stale
        constant), and the loop keeps controlling the rest. Every successful
        collect beats the heartbeat monitor with the stage's collect latency
        as its step time, feeding the dead/straggler verdicts.

        Stages on the pipelined binary transport are collected **from the
        loop thread**: all collect frames are issued back-to-back (the
        per-stage :meth:`~repro.transport.handle.RemoteStageHandle.
        collect_begin` request is microseconds of enqueue work), then the
        replies are drained against a shared deadline measured from issue
        time — no fan-out worker is parked per stage, so the pool is only
        touched for handles that genuinely block (v1 JSON peers, local
        handles), and for a typical small fleet it is never touched at all.
        ``concurrent=False`` keeps the strict sequential path."""
        self._probe_down_stages()
        waits: List[Tuple[str, StageHandle, Any]] = []
        sync_tasks: List[Tuple[str, Optional[StageHandle], Callable[[], Any]]] = []
        t0 = time.perf_counter()
        for name, h in self._live_handles():
            begin = getattr(h, "collect_begin", None) if self.concurrent else None
            if begin is not None:
                try:
                    waiter = begin()
                except TRANSPORT_ERRORS as exc:
                    self._mark_down(name, exc, h)
                    continue
                if waiter is not None:
                    waits.append((name, h, waiter))
                    continue
            sync_tasks.append((name, h, self._timed_collect(name, h)))
        out: Dict[str, StageStats] = self._fanout(sync_tasks, "collect")
        for name, h, waiter in waits:
            remaining = self.stage_deadline - (time.perf_counter() - t0)
            try:
                out[name] = waiter.result(max(remaining, 0.001))
            except TRANSPORT_ERRORS as exc:
                self._mark_down(name, exc, h)
            else:
                self.heartbeats.beat(name, time.perf_counter() - t0)
        return out

    def collect_fleet(self) -> StageStats:
        """One collect tick folded into the fleet view: every channel name
        merged across its member stages (Σ throughput/iops, exactly-merged
        wait histograms, so fleet percentiles are computed over the union of
        every member's per-op observations). This is the same fold the policy
        runtime publishes as ``paio_fleet_*`` / ``@fleet.*`` every loop tick;
        this method exposes it for ad-hoc inspection and benchmarks."""
        return fleet_view(self._collect_all())

    def _timed_collect(self, name: str, handle: StageHandle) -> Callable[[], StageStats]:
        """A collect thunk (for the blocking fan-out path) that beats the
        heartbeat monitor on success with the observed collect latency."""

        def thunk() -> StageStats:
            start = time.perf_counter()
            stats = handle.collect()
            self.heartbeats.beat(name, time.perf_counter() - start)
            return stats

        return thunk

    def _defer(self, name: str, rule: Any) -> None:
        with self._fleet_lock:
            state = self._stage_states.get(name)
            if state is not None:
                state.defer(rule)

    def _ship_rules(self, name: str, rules: List[Any]) -> List[Any]:
        """Apply ``rules`` to one stage in order; returns the applied subset.
        Rules for a DOWN stage are deferred (not dropped); a transport error
        mid-ship marks the stage down and defers the remainder.

        Handles exposing ``apply_rules`` (the remote transport) get the whole
        program as one pipelined batch — per-rule cost is one frame encode,
        not one round trip; a :class:`RuleShipError` carries the
        applied/pending split so deferral semantics are identical to the
        sequential path."""
        applied: List[Any] = []
        idx = 0
        while idx < len(rules):
            # lock-free reads (GIL-atomic dict gets): a stale view at worst
            # tries a dead handle (raises → down-mark) or defers one rule
            # early — both converge on the next probe/replay
            handle = self._handles.get(name)
            state = self._stage_states.get(name)
            if handle is None:
                return applied  # unknown stage: nothing will ever apply this
            if state is not None and not state.up:
                for rule in rules[idx:]:
                    self._defer(name, rule)
                return applied
            batch = rules[idx:]
            ship = getattr(handle, "apply_rules", None)
            if ship is not None:
                try:
                    ship(batch)
                    applied.extend(batch)
                except RuleShipError as exc:
                    applied.extend(exc.applied)
                    self._mark_down(name, exc.cause, handle)
                    for rule in exc.pending:
                        self._defer(name, rule)
                except TRANSPORT_ERRORS as exc:  # pragma: no cover — defensive
                    self._mark_down(name, exc, handle)
                    for rule in batch:
                        self._defer(name, rule)
                return applied
            rule = rules[idx]
            idx += 1
            try:
                self._apply_rule(handle, rule)
                applied.append(rule)
            except TRANSPORT_ERRORS as exc:
                self._mark_down(name, exc, handle)
                self._defer(name, rule)
        return applied

    def _ship_fanout(self, rules_by_stage: Dict[str, List[Any]]) -> Dict[str, List[Any]]:
        """Ship each stage's rule list — stages in parallel, rules within one
        stage in order. Returns {stage: applied rules}. A stage blowing the
        deadline is marked down; its worker keeps draining (deferring once
        the down-mark lands) — this tick just stops waiting for it."""
        items = [(n, rs) for n, rs in rules_by_stage.items() if rs]
        if not items:
            return {}
        out = self._fanout(
            [
                (name, None, functools.partial(self._ship_rules, name, rules))
                for name, rules in items
            ],
            "rule ship",
        )
        for name, _ in items:
            out.setdefault(name, [])
        return out

    # -- policy lifecycle ---------------------------------------------------
    @property
    def policy_runtime(self):
        """The policy runtime (created on demand); exposes ``registry`` for
        registering custom metrics addressable from trigger predicates."""
        if self._policy_runtime is None:
            from repro.policy.engine import PolicyRuntime  # local: optional subsystem

            with self._policy_lock:
                if self._policy_runtime is None:
                    self._policy_runtime = PolicyRuntime(
                        registry=self._registry, clock=self._clock
                    )
        return self._policy_runtime

    def install_policy(
        self, source, stage: Optional[str] = None, replace: bool = False
    ) -> str:
        """Parse, compile and install a policy; returns its name.

        ``source`` is anything :func:`repro.policy.load_policy` accepts — a
        Policy, a canonical dict, DSL text, or a ``.json``/``.pol`` path.
        Compilation validates against live ``stage_info()`` from every
        registered handle, so a policy naming unknown stages/channels/objects
        fails here, before any rule is applied.

        With ``replace=True`` an already-installed policy of the same name is
        updated **atomically**: the new version is compiled, diffed against
        the installed one, and the delta applied as a single swap under the
        policy lock — entities in both versions are retuned in place
        (``obj_config`` / object-slot swap), never removed and recreated, so
        there is no instant at which a surviving flow is unenforced. The
        policy's version (monotonic per control plane) bumps and is surfaced
        in :meth:`list_policies` and as the exported
        ``paio_policy_version`` metric. Semantics are identical for embedded
        stages and stages reached over the UDS transport — the delta ships
        through the same StageHandle interface as everything else.
        """
        from repro.policy import compile_policy, infos_without_policy, load_policy

        policy = load_policy(source)
        runtime = self.policy_runtime
        # fast-fail duplicate check (friendly error before compile touches the
        # channel layout); the authoritative check is under the lock below
        if not replace and runtime.get(policy.name) is not None:
            raise ValueError(
                f"policy {policy.name!r} already installed (use replace=True to update atomically)"
            )
        infos = self._stage_infos()
        if any(f.is_global() for f in policy.flows):
            # a global flow binds to the stages visible NOW; compiling while
            # part of the fleet is DOWN would silently exclude those stages
            # from the flow (and from its aggregate SLO) forever — fail
            # loudly instead, like a named-stage flow would
            with self._fleet_lock:
                down = sorted(
                    n for n, st in self._stage_states.items() if not st.up
                )
            if down:
                from repro.policy import PolicyError

                raise PolicyError(
                    f"policy {policy.name!r} has 'scope: global' flows but stages "
                    f"{down} are DOWN — installing now would silently exclude them "
                    "from the fleet; wait for re-admission or remove the stages"
                )
        current = runtime.get(policy.name) if replace else None
        if current is not None:
            # compile against the stages as they'd look without the old
            # version: the new one re-claims (and takes ownership of) the
            # entities the old version created
            infos = infos_without_policy(infos, current)
        compiled = compile_policy(policy, infos, default_stage=stage)
        with self._policy_lock:
            current = runtime.get(policy.name)
            if current is not None and not replace:
                raise ValueError(
                    f"policy {policy.name!r} already installed (use replace=True to update atomically)"
                )
            if current is None:
                self._install_fresh(runtime, compiled)
            else:
                self._replace_installed(runtime, current, compiled)
        if compiled.algorithm is not None:
            compiled.algorithm.setup(self._handles)
        return policy.name

    def _stage_infos(self) -> Dict[str, Dict[str, Any]]:
        """``stage_info()`` from every UP stage, fanned out. A stage that
        errors here is marked down and excluded — compiling a policy that
        names it then fails with an unknown-stage error (install is an
        explicit admin action; it must not block on a dead socket)."""
        return self._fanout(
            [(name, h, h.stage_info) for name, h in self._live_handles()], "stage_info"
        )

    def _install_fresh(self, runtime, compiled) -> None:
        """First-time install: apply the full install program, rolling back
        on failure. Callers hold ``_policy_lock``."""
        try:
            for stage_name, rules in compiled.install.items():
                handle = self._handles[stage_name]
                for rule in rules:
                    self._apply_rule(handle, rule)
        except Exception as install_exc:
            # roll back the partial install: teardown rules are safe to
            # apply to whatever subset actually landed (remove ops on
            # things never created are no-ops). A failing undo must not
            # mask the install error — it is chained as __context__ and the
            # remaining undo rules still run, so ``list_policies`` (which
            # never saw this policy) stays consistent with the stages.
            undo_error: Optional[Exception] = None
            for stage_name, rules in compiled.teardown.items():
                handle = self._handles.get(stage_name)
                if handle is None:
                    continue
                for rule in rules:
                    try:
                        self._apply_rule(handle, rule)
                    except Exception as exc:  # noqa: BLE001 — best-effort undo
                        if undo_error is None:
                            undo_error = exc
            if undo_error is not None:
                install_exc.__context__ = undo_error
            raise
        runtime.install(compiled)

    def _replace_installed(self, runtime, current, compiled) -> None:
        """Atomic in-place update: release trigger-held state, apply the
        install-set delta, and only THEN swap the runtime entry (version
        bump, old triggers out / new triggers in armed). Callers hold
        ``_policy_lock``.

        Rules-before-swap mirrors the fresh-install ordering: the new
        version's triggers cannot arm (and fire from the loop thread) before
        the entities their rules target exist. It also makes failure cheap:
        a mid-delta error undoes the applied prefix in reverse and re-raises
        with any undo failure chained as ``__context__`` — the runtime was
        never touched, so ``list_policies`` still shows the old version at
        its original version number, and still-fired old triggers still own
        the clamps the rollback re-applied.
        """
        from repro.policy import diff_policies

        delta = diff_policies(current, compiled)
        fired = runtime.trigger_engine.fired_for(compiled.name)
        applied: List = []
        try:
            # fired old triggers first release what they pushed (exactly as
            # remove_policy would), so trigger-held enforcement state cannot
            # leak into the new version — whose triggers start armed — and a
            # release can never overwrite a rate the delta sets next. Undo of
            # a release is the trigger's fire rules: a failed replace must
            # put the protective clamp back, not leave it lifted. Re-clamp
            # undos are registered BEFORE the release applies, so a failure
            # mid-release still rolls back to the clamped state.
            for t in fired:
                for stage_name, rules in t.fire_rules.items():
                    for rule in rules:
                        applied.append((stage_name, rule))
                for stage_name, rules in t.release_rules.items():
                    handle = self._handles.get(stage_name)
                    if handle is None:
                        continue
                    for rule in rules:
                        self._apply_rule(handle, rule)
            for stage_name, rule, undo in delta.ops:
                handle = self._handles.get(stage_name)
                if handle is None:
                    continue
                self._apply_rule(handle, rule)
                applied.append((stage_name, undo))
        except Exception as replace_exc:
            undo_error: Optional[Exception] = None
            for stage_name, undo in reversed(applied):
                handle = self._handles.get(stage_name)
                if handle is None:
                    continue
                undo_rules = undo if isinstance(undo, (list, tuple)) else (undo,)
                for u in undo_rules:
                    if u is None:
                        continue
                    try:
                        self._apply_rule(handle, u)
                    except Exception as exc:  # noqa: BLE001 — best-effort undo
                        if undo_error is None:
                            undo_error = exc
            if undo_error is not None:
                replace_exc.__context__ = undo_error
            raise
        runtime.replace(compiled)

    def remove_policy(self, name: str) -> None:
        """Uninstall a policy: its triggers stop evaluating, its objective
        algorithm leaves the loop, and its teardown rules (remove routes /
        objects / channels it created) are applied best-effort. Triggers that
        are FIRED at removal first apply their release rules, so enforcement
        state pushed onto pre-existing (non-policy-owned) objects does not
        outlive the policy."""
        runtime = self.policy_runtime
        with self._policy_lock:
            compiled, fired = runtime.remove(name)
            merged: Dict[str, List[Any]] = {}
            for rules_by_stage in [t.release_rules for t in fired] + [compiled.teardown]:
                for stage_name, rules in rules_by_stage.items():
                    merged.setdefault(stage_name, []).extend(rules)
            # down stages get their teardown DEFERRED (replayed on recovery),
            # not dropped — a recovered stage must not keep enforcing a
            # policy that no longer exists
            self._ship_fanout(merged)

    def list_policies(self) -> List[Dict[str, Any]]:
        """Installed-policy summaries, including each policy's monotonic
        ``version`` (bumped by every install or atomic replace) and live
        trigger states — identical over both transports. Each summary also
        carries fleet accounting: ``down_stages`` (stages the policy touches
        that are currently DOWN) and ``deferred_rules`` (rules destined for
        those stages, queued for replay on recovery) — rules a down stage
        missed are visible here, never silently dropped."""
        if self._policy_runtime is None:
            return []
        out = self._policy_runtime.list()
        with self._fleet_lock:
            down = {
                name: len(state.deferred)
                for name, state in self._stage_states.items()
                if not state.up
            }
        for summary in out:
            down_stages = sorted(set(summary.get("stages", ())) & set(down))
            summary["down_stages"] = down_stages
            summary["deferred_rules"] = sum(down[name] for name in down_stages)
        return out

    # -- observability ------------------------------------------------------
    def serve_metrics(
        self,
        port: int = 0,
        host: str = "127.0.0.1",
        allow_prefixes: Optional[Tuple[str, ...]] = None,
        allow_all: bool = False,
    ):
        """Start a Prometheus-text exporter over this plane's metric registry
        (by default the process-wide shared one — stage/channel gauges,
        policy versions, trigger states, serve-engine counters). Returns the
        started :class:`~repro.telemetry.exporter.MetricsExporter`; read the
        bound port off ``.port`` (``port=0`` binds an ephemeral one).
        Non-loopback ``host`` binds require ``allow_prefixes`` (serve only
        matching metric families) or an explicit ``allow_all=True``."""
        from repro.telemetry.exporter import MetricsExporter

        exporter = MetricsExporter(
            registry=self.policy_runtime.registry, host=host, port=port,
            allow_prefixes=allow_prefixes, allow_all=allow_all,
        ).start()
        self._exporters.append(exporter)  # torn down by close()
        return exporter

    # -- single iteration (usable synchronously from tests/benchmarks) -----
    def _algorithms(self) -> List[ControlAlgorithm]:
        algos = [self.algorithm] if self.algorithm is not None else []
        if self._policy_runtime is not None:
            algos.extend(self._policy_runtime.algorithms())
        return algos

    @staticmethod
    def _apply_rule(handle: StageHandle, rule) -> bool:
        if isinstance(rule, HousekeepingRule):
            return handle.hsk_rule(rule)
        if isinstance(rule, DifferentiationRule):
            return handle.dif_rule(rule)
        return handle.enf_rule(rule)

    def _algorithm_stats(
        self, algorithm: ControlAlgorithm, stats: Dict[str, StageStats], now: float, gated: bool
    ) -> Optional[Dict[str, StageStats]]:
        """Cadence gating for the background loop: each algorithm steps at its
        own ``loop_interval`` even when the loop ticks faster (the tick rate
        is the min across algorithms + triggers). ``now`` is the plane
        clock's time — monotonic by default, so a wall-clock step can neither
        starve nor double-step a gated algorithm. Skipped ticks are not lost —
        their windows accumulate, so a slow algorithm sees one combined window
        spanning its whole interval, not just the last tick's sliver. Returns
        the stats to step with, or None when this tick is skipped. Ungated
        (synchronous ``run_once()``) always steps with the tick's stats.
        """
        if not gated:
            return stats
        state = self._algo_states.get(algorithm)
        if state is None:
            state = {"last": None, "per_stage": {}}
            self._algo_states[algorithm] = state
        # fold this tick into the accumulator
        merged_acc: Dict[str, StageStats] = state["per_stage"]
        for name, st in stats.items():
            prev = merged_acc.get(name)
            merged_acc[name] = st if prev is None else st.merged_into(prev)
        # small relative epsilon so accumulated float tick times (10 × 0.1s)
        # cannot slip an extra tick past the cadence boundary
        due = algorithm.loop_interval * (1.0 - 1e-6)
        if state["last"] is not None and (now - state["last"]) < due:
            return None
        state["last"] = now
        state["per_stage"] = {}
        return merged_acc

    def run_once(self, gated: bool = False) -> Dict[str, List[EnforcementRule]]:
        with span("paio.control.tick"):
            return self._tick(gated)

    def _tick(self, gated: bool) -> Dict[str, List[EnforcementRule]]:
        now = self._clock.now()
        with span("paio.control.collect"):
            stats = self._collect_all()
        if self.keep_history:
            self.history.append(stats)
        # objects held by FIRED policy triggers: algorithm tuning is suppressed
        # there until the trigger releases, so protective actions stick
        pinned = (
            self._policy_runtime.pinned_targets() if self._policy_runtime is not None else ()
        )
        # all algorithms' rules are gathered per stage first, then shipped in
        # one fan-out (stages in parallel, per-stage order preserved), so the
        # tick's rule latency is max(stage), not sum over algorithms × stages
        to_ship: Dict[str, List[EnforcementRule]] = {}
        with span("paio.control.step"):
            for algorithm in self._algorithms():
                step_stats = self._algorithm_stats(algorithm, stats, now, gated)
                if step_stats is None:
                    continue
                for stage_name, stage_rules in algorithm.step(step_stats).items():
                    for rule in stage_rules:
                        if pinned and (stage_name, rule.channel, rule.object_id) in pinned:
                            continue
                        to_ship.setdefault(stage_name, []).append(rule)
        with span("paio.control.ship"):
            merged = self._ship_fanout(to_ship)
        if self._policy_runtime is not None:
            # trigger evaluation + rule application run under the policy
            # lock: a concurrent install_policy(replace=True) must not
            # interleave with an old trigger firing/releasing, or its rules
            # could land AFTER the delta and override the new version
            with self._policy_lock, span("paio.control.policy"):
                trigger_rules: Dict[str, List[Any]] = {}
                for event in self._policy_runtime.on_collect(self._clock.now(), stats):
                    for stage_name, stage_rules in event.rules.items():
                        trigger_rules.setdefault(stage_name, []).extend(stage_rules)
                self._ship_fanout(trigger_rules)
                # gauges publish only after the events' rules landed: a
                # scraped paio_trigger_fired 1 means enforced, not just latched
                self._policy_runtime.publish_trigger_states()
        self.iterations += 1
        return merged

    # -- background loop ----------------------------------------------------
    def effective_loop_interval(self) -> float:
        """Tick cadence of the background loop: the fastest cadence anyone
        asked for (installed algorithms, the explicit plane interval, or —
        whenever any trigger is installed — the default tick, so a slow
        objective cannot starve its own policy's trigger windows).
        Algorithms slower than the tick rate are cadence-gated per step."""
        intervals = [a.loop_interval for a in self._algorithms()]
        if self.loop_interval is not None:
            intervals.append(self.loop_interval)
        if self._policy_runtime is not None and self._policy_runtime.trigger_engine.triggers():
            intervals.append(self.DEFAULT_LOOP_INTERVAL)
        return min(intervals) if intervals else self.DEFAULT_LOOP_INTERVAL

    def start(self) -> "ControlPlane":
        for algorithm in self._algorithms():
            algorithm.setup(self._handles)
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True, name="paio-control-plane")
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.run_once(gated=True)
            except TRANSPORT_ERRORS:
                # per-stage errors are contained inside run_once (the failing
                # stage is marked down); this guards races like a handle
                # swapped mid-tick — the loop itself must never wedge
                pass
            self._stop.wait(self.effective_loop_interval())

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def close(self) -> None:
        """Tear the plane down for good: stop the loop, shut the fan-out
        pool and any exporters started via :meth:`serve_metrics`, close
        remote handles, and release every name this plane published into the
        (possibly shared, process-wide) metric registry — a discarded plane
        must not leave its stage gauges, liveness state, policy versions and
        trigger states on the exporter forever. Also usable as a context
        manager: ``with ControlPlane() as cp: ...``."""
        self.stop()
        # swap the pool out under _fleet_lock (the lock _fanout_pool creates
        # it under), then shut it down outside: a concurrent fan-out either
        # got the old pool before the swap or will lazily build a fresh one
        with self._fleet_lock:
            executor, self._executor = self._executor, None
        if executor is not None:
            executor.shutdown(wait=False, cancel_futures=True)
        for exporter in self._exporters:
            try:
                exporter.stop()
            except Exception:  # noqa: BLE001 — teardown is best-effort
                pass
        self._exporters = []
        if self._policy_runtime is not None:
            self._policy_runtime.close()
        with self._fleet_lock:
            handles = list(self._handles.values())
            names = list(self._stage_states)
        registry = self._metric_registry()
        for name in names:
            registry.unregister(f"stage.{name}.up")
            registry.unregister(f"stage.{name}.down")
            registry.unregister(f"stage.{name}.breaker")
            registry.unregister(f"rpc.{name}.retries")
        for handle in handles:
            if hasattr(handle, "close"):
                try:
                    handle.close()
                except Exception:  # noqa: BLE001 — socket may already be dead
                    pass

    def __enter__(self) -> "ControlPlane":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
