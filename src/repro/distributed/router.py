"""ShardRouter: one logical stage handle over N local stage processes.

ROADMAP item 1 ("escape the GIL"): a single Python stage process tops out
around one core, so a logical stage is spread over N ``StageServer`` shard
processes and this router presents them as one stage again. Placement is
per-*flow* rendezvous hashing (:mod:`repro.core.shard`): every request's
classifier tuple hashes to a flow token, the token's HRW argmax picks the
shard, and a shard death re-homes exactly that shard's flows onto the
survivors — the surviving flows never move, so their enforcement objects
(token buckets mid-refill, priority windows) keep their state.

The router implements the same five-call control interface a
:class:`~repro.core.stage.Stage` does, plus ``enforce_batch``:

* ``enforce_batch`` — group the batch by flow, place each flow, and ship one
  :data:`~repro.transport.framing.OP_ENFORCE` frame per shard over the
  pipelined binary transport; waits on all shards overlap, so aggregate
  admitted throughput scales with shard count even though each shard serves
  its frame serially. v1 (JSON-line) shards degrade to a blocking call on the
  router's dispatch pool — mixed-version fleets route fine, just slower.
* ``collect`` — every live shard's ``StatsSnapshot``s merged per channel with
  :func:`~repro.core.stats.merge_parallel` (exact histogram merge), so the
  merged view is indistinguishable from one stage having served the union of
  the ops (the property tests assert this).
* rules (``hsk`` / ``dif`` / ``enf``) — fanned out to every live shard:
  a logical stage's configuration is whatever every shard enforces.

Failover: a transport failure while dispatching to a shard marks it down
(``paio_shard_up{shard}`` → 0, ``paio_shard_failovers_total`` + 1), drops it
from the shard map, and re-dispatches the failed groups to their new HRW
owners in the same call — callers never see the death. Down shards are
re-probed every ``probe_interval`` seconds (monotonic clock); a probe that
answers is re-admitted only after the optional ``readmit_gate`` approves —
the sharded-fleet wiring passes a gate that waits for the control plane to
finish deferred-rule replay, which is what closes the enforcement gap on
shard *restart* (on shard *death* there is no gap at all: surviving shards
already carry every ``scope: global`` flow's channels).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.core.context import Context
from repro.core.shard import ShardMap, flow_key, flow_token, shard_stage_names
from repro.core.stage import Stage
from repro.core.stats import StageStats, StatsSnapshot, merge_parallel
from repro.core.objects import Result
from repro.transport.handle import TRANSPORT_ERRORS, RemoteStageHandle, RetryPolicy

__all__ = ["LocalShardHandle", "ShardRouter", "AllShardsDownError"]


class AllShardsDownError(ConnectionError):
    """Every shard of the logical stage is down — nothing left to re-home to."""


class LocalShardHandle:
    """In-process shard handle: the same calls ``RemoteStageHandle`` offers,
    served by a :class:`Stage` in this process. Lets the property tests (and
    single-process deployments) run the full router path — grouping,
    placement, merged collect — with no sockets involved."""

    def __init__(self, stage: Stage, shard_id: Optional[str] = None) -> None:
        self.stage = stage
        self.shard_id = shard_id if shard_id is not None else stage.name
        self.proto = 0  #: not a wire protocol at all

    def enforce_groups_begin(self, shard_id: str, groups: Sequence[Any]):
        return None  # no pipelining in-process; router uses the blocking path

    def enforce_groups(
        self, shard_id: str, groups: Sequence[Any], timeout: Optional[float] = None
    ) -> int:
        if shard_id != self.shard_id:
            raise ValueError(
                f"enforce batch addressed to shard {shard_id!r}, this is {self.shard_id!r}"
            )
        total = 0
        for workflow_id, request_type, size, request_context, tenant, count in groups:
            if count <= 0:
                continue
            ctx = Context(workflow_id, request_type, size, request_context, tenant)
            self.stage.enforce_batch([ctx] * count)
            total += count
        return total

    def stage_info(self) -> Dict[str, Any]:
        return self.stage.stage_info()

    def hsk_rule(self, rule) -> bool:
        return self.stage.hsk_rule(rule)

    def dif_rule(self, rule) -> bool:
        return self.stage.dif_rule(rule)

    def enf_rule(self, rule) -> bool:
        return self.stage.enf_rule(rule)

    def collect(self) -> StageStats:
        return self.stage.collect()

    def collect_begin(self):
        return None

    def ping(self) -> None:
        pass

    def close(self) -> None:
        pass


class _ShardState:
    """Router-side view of one shard (liveness + how to re-dial it)."""

    __slots__ = ("handle", "up", "socket_path", "timeout", "protocol", "last_probe")

    def __init__(self, handle, socket_path: Optional[str], timeout: float, protocol: str) -> None:
        self.handle = handle
        self.up = True
        self.socket_path = socket_path
        self.timeout = timeout
        self.protocol = protocol
        self.last_probe = 0.0


class ShardRouter:
    """Flow-hash router presenting N shard stage processes as one stage.

    Shards are added with :meth:`add_shard` (any handle implementing the
    shard calls) or :meth:`connect` (a ``RemoteStageHandle`` over UDS).
    Thread-safe: drivers may call :meth:`enforce_batch` concurrently; map
    mutations are copy-on-write under one lock.
    """

    def __init__(
        self,
        logical: str,
        probe_interval: float = 0.5,
        readmit_gate: Optional[Callable[[str], bool]] = None,
        registry=None,
    ) -> None:
        self.logical = logical
        self.probe_interval = float(probe_interval)
        self.readmit_gate = readmit_gate
        self._registry = registry
        self._map = ShardMap()
        self._states: Dict[str, _ShardState] = {}
        self._lock = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        self.failovers = 0  #: shards marked down by failed dispatch
        self._publish_count()

    # -- membership ----------------------------------------------------------
    def add_shard(self, shard_id: str, handle) -> None:
        with self._lock:
            old = self._states.get(shard_id)
            self._states[shard_id] = _ShardState(
                handle,
                getattr(handle, "socket_path", None),
                getattr(handle, "timeout", 5.0),
                getattr(handle, "protocol", "auto"),
            )
            self._map.add(shard_id)
        if old is not None and old.handle is not handle:
            try:
                old.handle.close()
            except Exception:  # noqa: BLE001 — replaced handle may be dead
                pass
        self._publish_up(shard_id, True)
        self._publish_count()

    def connect(
        self,
        shard_id: str,
        socket_path: str,
        timeout: float = 5.0,
        protocol: str = "auto",
        retry: Optional[RetryPolicy] = None,
    ) -> None:
        self.add_shard(
            shard_id,
            RemoteStageHandle(
                socket_path,
                timeout=timeout,
                protocol=protocol,
                # the initial dial races the shard's bind→listen at startup;
                # a couple of dial retries absorb it (idempotent-call retries
                # stay off: the router owns failover, not the handle)
                retry=retry if retry is not None else RetryPolicy(attempts=5, seed=0),
                registry=self._registry,
            ),
        )

    @classmethod
    def connect_all(
        cls,
        logical: str,
        socket_paths: Sequence[str],
        timeout: float = 5.0,
        protocol: str = "auto",
        **kwargs: Any,
    ) -> "ShardRouter":
        """Stand up a router over the shards of ``logical`` listening at
        ``socket_paths`` (shard ids follow the ``logical/i`` convention)."""
        router = cls(logical, **kwargs)
        for sid, path in zip(shard_stage_names(logical, len(socket_paths)), socket_paths):
            router.connect(sid, path, timeout=timeout, protocol=protocol)
        return router

    @property
    def shards(self) -> Tuple[str, ...]:
        """Live shard ids (the current rendezvous member set)."""
        return self._map.shards

    @property
    def known_shards(self) -> Tuple[str, ...]:
        """Every shard ever added, up or down."""
        with self._lock:
            return tuple(sorted(self._states))

    def owner_of(self, ctx: Context) -> str:
        """Which live shard owns this request's flow right now."""
        return self._map.shard_of(flow_token(ctx))

    # -- telemetry -----------------------------------------------------------
    def _metric_registry(self):
        if self._registry is not None:
            return self._registry
        from repro.telemetry import get_registry  # local: avoid import cycle

        return get_registry()

    def _publish_up(self, shard_id: str, up: bool) -> None:
        registry = self._metric_registry()
        key = f"shard.{shard_id}.up"
        registry.set_gauge(key, 1.0 if up else 0.0)
        registry.describe(key, "paio_shard_up", {"stage": self.logical, "shard": shard_id})

    def _publish_count(self) -> None:
        registry = self._metric_registry()
        key = f"shard.{self.logical}.count"
        registry.set_gauge(key, float(len(self._map)))
        registry.describe(key, "paio_shard_count", {"stage": self.logical})

    def _count_failover(self) -> None:
        registry = self._metric_registry()
        key = f"shard.{self.logical}.failovers"
        registry.inc(key)
        registry.describe(key, "paio_shard_failovers", {"stage": self.logical})

    # -- liveness ------------------------------------------------------------
    def _mark_down(self, shard_id: str, exc: BaseException) -> None:
        with self._lock:
            state = self._states.get(shard_id)
            if state is None or not state.up:
                return  # one transition only
            state.up = False
            state.last_probe = time.monotonic()
            self._map.remove(shard_id)
        self.failovers += 1
        self._count_failover()
        self._publish_up(shard_id, False)
        self._publish_count()

    def _maybe_probe(self) -> None:
        """Re-dial down shards whose probe cooldown elapsed; re-admit on a
        successful ping (and a passing ``readmit_gate``)."""
        now = time.monotonic()
        with self._lock:
            due = [
                (sid, state)
                for sid, state in self._states.items()
                if not state.up and (now - state.last_probe) >= self.probe_interval
            ]
            for _, state in due:
                state.last_probe = now
        for sid, state in due:
            if state.socket_path is None:
                # in-process shard: the handle never really dies, just ping it
                try:
                    state.handle.ping()
                except TRANSPORT_ERRORS:
                    continue
                handle = state.handle
            else:
                try:
                    handle = RemoteStageHandle(
                        state.socket_path,
                        timeout=state.timeout,
                        protocol=state.protocol,
                        registry=self._registry,
                    )
                except TRANSPORT_ERRORS:
                    continue
            if self.readmit_gate is not None and not self.readmit_gate(sid):
                if handle is not state.handle:
                    handle.close()
                continue
            with self._lock:
                old = state.handle
                state.handle = handle
                state.up = True
                self._map.add(sid)
            if old is not handle:
                try:
                    old.close()
                except Exception:  # noqa: BLE001 — dead handle
                    pass
            self._publish_up(sid, True)
            self._publish_count()

    # -- enforce dispatch ----------------------------------------------------
    def _dispatch_pool(self) -> ThreadPoolExecutor:
        pool = self._pool
        if pool is None:
            pool = self._pool = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix=f"paio-router-{self.logical}"
            )
        return pool

    def enforce_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        """Split-by-shard enforce: group by flow and size, place each group by
        its flow, one frame per shard.

        Returns one :class:`Result` per request, echoing the request payload —
        payload bytes never cross the socket; the wire carries only the
        group records (ROADMAP: "only control frames need the
        socket"). Admission waits happen shard-side; this call returns when
        every shard has admitted its groups. On a shard failure mid-dispatch
        the failed groups re-home to their new HRW owners within this call.
        """
        n = len(ctxs)
        if n == 0:
            return []
        self._maybe_probe()
        # group the batch by flow and size (one wire record per group, not per
        # request): a record carries one size for all of its ``count`` requests
        counts: Dict[Tuple, int] = {}
        exemplar: Dict[Tuple, Context] = {}
        for ctx in ctxs:
            key = (flow_key(ctx), ctx.size)
            if key in counts:
                counts[key] += 1
            else:
                counts[key] = 1
                exemplar[key] = ctx
        flows = list(counts)
        tokens = {key: flow_token(exemplar[key]) for key in flows}
        # pending: group key → group record; re-homed groups re-enter here
        pending: Dict[Tuple, Tuple] = {}
        for key in flows:
            c = exemplar[key]
            pending[key] = (
                c.workflow_id,
                int(c.request_type),
                c.size,
                c.request_context,
                c.tenant,
                counts[key],
            )
        while pending:
            shard_map = self._map  # snapshot not needed: map is copy-on-write
            if len(shard_map) == 0:
                raise AllShardsDownError(
                    f"logical stage {self.logical!r}: no live shards left"
                )
            keys = list(pending)
            owners = shard_map.shard_of_batch([tokens[k] for k in keys])
            by_shard: Dict[str, List[Tuple]] = {}
            for key, owner in zip(keys, owners):
                by_shard.setdefault(owner, []).append(pending[key])
            groups_of: Dict[str, List[Tuple]] = by_shard
            waiters: List[Tuple[str, Any]] = []
            futures: List[Tuple[str, Any]] = []
            failed: List[str] = []
            for sid, groups in groups_of.items():
                state = self._states.get(sid)
                handle = state.handle if state is not None else None
                if handle is None:
                    failed.append(sid)
                    continue
                try:
                    waiter = handle.enforce_groups_begin(sid, groups)
                except TRANSPORT_ERRORS as exc:
                    self._mark_down(sid, exc)
                    failed.append(sid)
                    continue
                if waiter is not None:
                    waiters.append((sid, waiter))
                else:
                    # v1 / in-process shard: blocking call on the pool so it
                    # still overlaps with the other shards' waits
                    futures.append(
                        (sid, self._dispatch_pool().submit(handle.enforce_groups, sid, groups))
                    )
            for sid, waiter in waiters:
                state = self._states.get(sid)
                timeout = state.timeout if state is not None else 5.0
                try:
                    waiter.result(timeout)
                except TRANSPORT_ERRORS as exc:
                    self._mark_down(sid, exc)
                    failed.append(sid)
            for sid, fut in futures:
                try:
                    fut.result()
                except TRANSPORT_ERRORS as exc:
                    self._mark_down(sid, exc)
                    failed.append(sid)
            if not failed:
                break
            # re-home: only the failed shards' flows re-enter the loop; the
            # updated map (failed shards removed) re-places them
            survivors = {
                key
                for key, owner in zip(keys, owners)
                if owner not in failed
            }
            pending = {key: pending[key] for key in keys if key not in survivors}
        if requests is None:
            return [Result(content=None) for _ in range(n)]
        return [Result(content=r) for r in requests]

    # -- five-call control interface (merged view) ---------------------------
    def _live_items(self) -> List[Tuple[str, Any]]:
        with self._lock:
            return [(sid, s.handle) for sid, s in self._states.items() if s.up]

    def stage_info(self) -> Dict[str, Any]:
        """One logical info dict: the shard infos keyed by shard id, plus the
        union channel map (a channel exists logically if any shard has it)."""
        self._maybe_probe()
        shard_infos: Dict[str, Any] = {}
        channels: Dict[str, Any] = {}
        filters: Dict[str, Any] = {}
        for sid, handle in self._live_items():
            try:
                info = handle.stage_info()
            except TRANSPORT_ERRORS as exc:
                self._mark_down(sid, exc)
                continue
            shard_infos[sid] = info
            for name, desc in (info.get("channels") or {}).items():
                channels.setdefault(name, desc)
            # filter registry advertisement: shards run the same code, so a
            # union is a formality — but a mid-upgrade fleet advertises only
            # what some shard can actually instantiate
            for name, desc in (info.get("filters") or {}).items():
                filters.setdefault(name, desc)
        return {
            "stage": self.logical,
            "sharded": True,
            "shard_count": len(shard_infos),
            "shards": shard_infos,
            "channels": channels,
            "filters": filters,
        }

    def _fanout_rule(self, call: str, rule) -> bool:
        """Apply one rule on every live shard; True iff every live shard took
        it (a logical stage is configured when all its shards are)."""
        ok = True
        applied_any = False
        for sid, handle in self._live_items():
            try:
                ok = bool(getattr(handle, call)(rule)) and ok
                applied_any = True
            except TRANSPORT_ERRORS as exc:
                self._mark_down(sid, exc)
                ok = False
        if not applied_any:
            raise AllShardsDownError(
                f"logical stage {self.logical!r}: no live shard accepted the rule"
            )
        return ok

    def hsk_rule(self, rule) -> bool:
        return self._fanout_rule("hsk_rule", rule)

    def dif_rule(self, rule) -> bool:
        return self._fanout_rule("dif_rule", rule)

    def enf_rule(self, rule) -> bool:
        return self._fanout_rule("enf_rule", rule)

    def collect(self) -> StageStats:
        """Merged stats: per channel name, the parallel merge of every live
        shard's snapshot — extensive fields sum, histograms merge exactly, so
        percentiles are computed over the union of per-op observations."""
        self._maybe_probe()
        per_shard: List[StageStats] = []
        waiters: List[Tuple[str, Any]] = []
        blocking: List[Tuple[str, Any]] = []
        for sid, handle in self._live_items():
            try:
                waiter = handle.collect_begin()
            except TRANSPORT_ERRORS as exc:
                self._mark_down(sid, exc)
                continue
            if waiter is not None:
                waiters.append((sid, waiter))
            else:
                blocking.append((sid, handle))
        for sid, waiter in waiters:
            state = self._states.get(sid)
            timeout = state.timeout if state is not None else 5.0
            try:
                per_shard.append(waiter.result(timeout))
            except TRANSPORT_ERRORS as exc:
                self._mark_down(sid, exc)
        for sid, handle in blocking:
            try:
                per_shard.append(handle.collect())
            except TRANSPORT_ERRORS as exc:
                self._mark_down(sid, exc)
        by_channel: Dict[str, List[StatsSnapshot]] = {}
        for stats in per_shard:
            for name, snap in stats.per_channel.items():
                by_channel.setdefault(name, []).append(snap)
        return StageStats(
            per_channel={
                name: (snaps[0] if len(snaps) == 1 else merge_parallel(snaps, name))
                for name, snaps in by_channel.items()
            }
        )

    def ping(self) -> None:
        """Liveness of the *logical* stage: up iff any shard answers."""
        self._maybe_probe()
        last: Optional[BaseException] = None
        for sid, handle in self._live_items():
            try:
                handle.ping()
                return
            except TRANSPORT_ERRORS as exc:
                self._mark_down(sid, exc)
                last = exc
        raise AllShardsDownError(f"logical stage {self.logical!r}: no shard answers") from last

    # -- teardown ------------------------------------------------------------
    def close(self) -> None:
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)
        with self._lock:
            states = list(self._states.values())
            self._states.clear()
            for sid in list(self._map.shards):
                self._map.remove(sid)
        for state in states:
            try:
                state.handle.close()
            except Exception:  # noqa: BLE001 — dead handle
                pass
