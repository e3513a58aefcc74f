"""Data plane stage: differentiation + enforcement modules (paper §3.2–§3.4).

A ``Stage`` is embedded in an I/O layer. It holds channels, the request→channel
differentiation tables, and exposes the five-call control interface of Table 2
(``stage_info``, ``hsk_rule``, ``dif_rule``, ``enf_rule``, ``collect``).

Differentiation follows the paper's two-phase scheme:
  * phase 1 (setup): differentiation rules define which classifier combinations
    ("masks") are considered and install token→channel mappings;
  * phase 2 (runtime): ``select_channel`` hashes the request's classifiers
    under each installed mask (most-specific first) and dispatches to the first
    match, falling back to a default channel.

The hot path (enforce) is lock-free over read-mostly routing tables.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from functools import lru_cache
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.telemetry.spans import span

from .channel import DEFAULT_OBJECT_ID, Channel, group_dispatch, routing_without
from .clock import Clock, DEFAULT_CLOCK
from .context import Context
from .hashing import token_for, token_for_batch
from .objects import OBJECT_KINDS, EnforcementObject, Result
from .rules import CLASSIFIERS, DifferentiationRule, EnforcementRule, HousekeepingRule
from .stats import StageStats

DEFAULT_CHANNEL = "default"

#: position of each routable classifier inside the resolved-route cache key
_CLASSIFIER_POS = {name: i for i, name in enumerate(CLASSIFIERS)}

#: resolved-route memo capacity; past it the oldest entry is evicted (FIFO ≈
#: LRU for routing workloads, where hot flows re-insert rarely) so
#: high-cardinality classifier spaces keep benefiting instead of freezing
#: the cache at its first 64Ki keys. The memo is an OrderedDict purely for
#: ``popitem(last=False)`` — O(1) true FIFO; ``dict.pop(next(iter(d)))``
#: degrades to an O(cap) tombstone scan between internal resizes
_ROUTE_CACHE_CAP = 65536


@lru_cache(maxsize=8192)
def _mask_token(parts: Tuple[Any, ...]) -> int:
    """Bounded memo of the classifier-subtuple → murmur token map (§Perf
    satellite, PR 10): the token is a pure function of the parts, and route-
    cache misses re-hash the same few hundred distinct subtuples over and
    over — an LRU probe is ~6x cheaper than re-running murmur3 in Python."""
    return token_for(parts)


class Stage:
    def __init__(
        self,
        name: str,
        clock: Clock = DEFAULT_CLOCK,
        create_default_channel: bool = True,
    ) -> None:
        self.name = name
        self.pid = os.getpid()
        self._clock = clock
        self._channels: Dict[str, Channel] = {}
        # ordered (mask, {token: channel_name}) — most specific first
        self._routing: List[Tuple[Tuple[str, ...], Dict[int, str]]] = []
        #: classifier-tuple → resolved channel (pure function of _routing)
        self._route_cache: "OrderedDict[tuple, str]" = OrderedDict()
        self._mutate = threading.Lock()
        if create_default_channel:
            self._channels[DEFAULT_CHANNEL] = Channel(DEFAULT_CHANNEL, clock)

    # ------------------------------------------------------------------ #
    # housekeeping                                                       #
    # ------------------------------------------------------------------ #
    def create_channel(self, name: str) -> Channel:
        with self._mutate:
            if name not in self._channels:
                channels = dict(self._channels)
                channels[name] = Channel(name, self._clock)
                self._channels = channels
        return self._channels[name]

    def remove_channel(self, name: str) -> None:
        with self._mutate:
            channels = dict(self._channels)
            channels.pop(name, None)
            self._channels = channels

    def channel(self, name: str) -> Optional[Channel]:
        return self._channels.get(name)

    def channels(self) -> List[str]:
        return list(self._channels.keys())

    # ------------------------------------------------------------------ #
    # differentiation                                                    #
    # ------------------------------------------------------------------ #
    def add_channel_route(self, mask: Tuple[str, ...], key: Tuple[Any, ...], channel: str) -> None:
        with self._mutate:
            routing = [(m, dict(t)) for m, t in self._routing]
            for m, table in routing:
                if m == mask:
                    table[token_for(key)] = channel
                    break
            else:
                routing.append((mask, {token_for(key): channel}))
            routing.sort(key=lambda e: -len(e[0]))
            self._routing = routing
            self._route_cache = OrderedDict()  # routing changed: resolved routes stale

    def remove_channel_route(self, mask: Tuple[str, ...], key: Tuple[Any, ...]) -> bool:
        """Uninstall one request→channel mapping (policy teardown path)."""
        with self._mutate:
            self._routing, removed = routing_without(self._routing, mask, token_for(key))
            self._route_cache = OrderedDict()
        return removed

    def select_channel(self, ctx: Context) -> str:
        # resolved-route memo: murmur hashing of classifier strings is the
        # Python hot-path bottleneck (§Perf iteration 1); the mapping
        # classifiers→channel is pure, so cache the resolution per exact
        # classifier tuple (cleared on any dif_rule change).
        key = (ctx.workflow_id, ctx.request_type, ctx.request_context, ctx.tenant)
        cached = self._route_cache.get(key)
        if cached is not None:
            return cached
        name = DEFAULT_CHANNEL
        for mask, table in self._routing:
            token = _mask_token(tuple(getattr(ctx, c) for c in mask))
            hit = table.get(token)
            if hit is not None:
                name = hit
                break
        cache = self._route_cache
        if len(cache) >= _ROUTE_CACHE_CAP:
            try:  # evict the oldest resolution; tolerate concurrent clears
                cache.popitem(last=False)
            except KeyError:
                pass
        cache[key] = name
        return name

    def select_channels_batch(self, ctxs: Sequence[Context]) -> List[str]:
        """Resolve routes for a whole batch in one pass.

        Cache hits cost one dict probe each; the distinct cache misses are
        tokenized together — one vectorized murmur pass per mask level
        (``token_for_batch``) — instead of hashing request-by-request.
        """
        names: List[Optional[str]] = [None] * len(ctxs)
        cache = self._route_cache
        misses: Dict[tuple, List[int]] = {}
        for i, ctx in enumerate(ctxs):
            key = (ctx.workflow_id, ctx.request_type, ctx.request_context, ctx.tenant)
            hit = cache.get(key)
            if hit is not None:
                names[i] = hit
            else:
                misses.setdefault(key, []).append(i)
        if misses:
            resolved = {key: DEFAULT_CHANNEL for key in misses}
            unresolved = list(misses)
            for mask, table in self._routing:
                if not unresolved:
                    break
                pos = [_CLASSIFIER_POS[c] for c in mask]
                tokens = token_for_batch([tuple(k[p] for p in pos) for k in unresolved])
                still = []
                for key, tok in zip(unresolved, tokens):
                    hit = table.get(tok)
                    if hit is not None:
                        resolved[key] = hit
                    else:
                        still.append(key)
                unresolved = still
            for key, name in resolved.items():
                if len(cache) >= _ROUTE_CACHE_CAP:
                    try:
                        cache.popitem(last=False)
                    except KeyError:
                        pass
                cache[key] = name
                for i in misses[key]:
                    names[i] = name
        return names  # type: ignore[return-value]

    # ------------------------------------------------------------------ #
    # enforcement (Instance API: ``enforce``)                            #
    # ------------------------------------------------------------------ #
    def enforce(self, ctx: Context, request: Any = None) -> Result:
        with span("paio.stage.enforce"):
            name = self.select_channel(ctx)
            chan = self._channels.get(name)
            if chan is None:
                chan = self._channels.get(DEFAULT_CHANNEL)
                if chan is None:  # stage with no channels: pass through
                    return Result(content=request)
            return chan.enforce(ctx, request)

    def enforce_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]] = None
    ) -> List[Result]:
        """Batched ``enforce``: route the whole batch in one pass, group by
        channel, and dispatch one ``Channel.enforce_batch`` call per group.
        Elementwise equivalent to calling ``enforce`` per request, but pays
        routing, lock and dispatch cost per *batch*.
        """
        if len(ctxs) == 0:
            return []
        with span("paio.stage.enforce"):
            return self._enforce_batch(ctxs, requests)

    def _enforce_batch(
        self, ctxs: Sequence[Context], requests: Optional[Sequence[Any]]
    ) -> List[Result]:
        n = len(ctxs)
        c0 = ctxs[0]
        if all(c is c0 for c in ctxs):  # homogeneous submit loop fast path
            chan = self._channels.get(self.select_channel(c0)) or self._channels.get(
                DEFAULT_CHANNEL
            )
            if chan is None:
                reqs = [None] * n if requests is None else requests
                return [Result(content=r) for r in reqs]
            return chan.enforce_batch(ctxs, requests, _homogeneous=True)
        names = self.select_channels_batch(ctxs)
        groups: Dict[str, List[int]] = {}
        for i, name in enumerate(names):
            groups.setdefault(name, []).append(i)
        if len(groups) == 1:
            name = next(iter(groups))
            chan = self._channels.get(name) or self._channels.get(DEFAULT_CHANNEL)
            if chan is None:
                reqs = [None] * n if requests is None else requests
                return [Result(content=r) for r in reqs]
            return chan.enforce_batch(ctxs, requests)
        def call(name: str, sub_ctx, sub_req):
            chan = self._channels.get(name) or self._channels.get(DEFAULT_CHANNEL)
            if chan is None:  # stage with no such channel: pass through
                reqs = [None] * len(sub_ctx) if sub_req is None else sub_req
                return [Result(content=r) for r in reqs]
            return chan.enforce_batch(sub_ctx, sub_req)

        return group_dispatch(n, groups, ctxs, requests, call)

    # ------------------------------------------------------------------ #
    # control interface (Table 2)                                        #
    # ------------------------------------------------------------------ #
    def stage_info(self) -> Dict[str, Any]:
        from repro.filters.registry import FILTER_REGISTRY  # local: no core cycle

        return {
            "pid": self.pid,
            "stage": self.name,
            "channels": {n: c.describe() for n, c in self._channels.items()},
            # advertised filter registry: names → versions/param schema, so
            # the policy compiler validates a filters: stanza against what
            # THIS stage process can actually instantiate
            "filters": FILTER_REGISTRY.advertise(),
        }

    def hsk_rule(self, rule: HousekeepingRule) -> bool:
        if rule.op == "create_channel":
            self.create_channel(rule.channel)
            return True
        if rule.op == "remove_channel":
            self.remove_channel(rule.channel)
            return True
        if rule.op == "create_object":
            chan = self._channels.get(rule.channel)
            if chan is None or rule.object_kind not in OBJECT_KINDS:
                return False
            params = dict(rule.params)
            cls = OBJECT_KINDS[rule.object_kind]
            if rule.object_kind in ("drl", "priority_gate"):
                params.setdefault("clock", self._clock)
            chan.add_object(rule.object_id or DEFAULT_OBJECT_ID, cls(**params))
            return True
        if rule.op == "remove_object":
            chan = self._channels.get(rule.channel)
            if chan is None:
                return False
            chan.remove_object(rule.object_id or DEFAULT_OBJECT_ID)
            return True
        if rule.op == "install_filter":
            chan = self._channels.get(rule.channel)
            if chan is None or not rule.object_kind:
                return False
            from repro.filters import FILTER_REGISTRY, FilterError, FilterSpec

            spec = FilterSpec.from_rule(rule)
            try:
                flt = FILTER_REGISTRY.create(
                    spec.name, spec.version, spec.params, clock=self._clock
                )
            except FilterError:
                return False
            chan.install_filter(spec.filter_id, flt)
            return True
        if rule.op == "remove_filter":
            chan = self._channels.get(rule.channel)
            if chan is None:
                return False
            return chan.remove_filter(rule.object_id or (rule.object_kind or ""))
        if rule.op == "remove_route":
            # inverse of dif_rule: params carries the original match
            dr = DifferentiationRule(
                channel=rule.channel, match=rule.params.get("match") or {}, object_id=rule.object_id
            )
            if rule.object_id is None:
                return self.remove_channel_route(dr.mask(), dr.key())
            chan = self._channels.get(rule.channel)
            if chan is None:
                return False
            return chan.remove_object_route(dr.mask(), dr.key())
        return False

    def dif_rule(self, rule: DifferentiationRule) -> bool:
        if rule.channel not in self._channels:
            return False
        if rule.object_id is None:
            self.add_channel_route(rule.mask(), rule.key(), rule.channel)
        else:
            self._channels[rule.channel].add_object_route(rule.mask(), rule.key(), rule.object_id)
        return True

    def enf_rule(self, rule: EnforcementRule) -> bool:
        chan = self._channels.get(rule.channel)
        if chan is None:
            return False
        return chan.configure_object(rule.object_id, rule.state)

    def collect(self) -> StageStats:
        return StageStats(per_channel={n: c.collect() for n, c in self._channels.items()})

    # convenience: attach a raw EnforcementObject (programmatic setup path;
    # the paper allows configuring stages directly as well as via rules §3.3)
    def install(self, channel: str, object_id: str, obj: EnforcementObject) -> None:
        self.create_channel(channel).add_object(object_id, obj)
