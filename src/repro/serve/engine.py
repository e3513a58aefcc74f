"""Serving engine: batched prefill + decode with a PAIO stage on the
request path.

Every admitted request flows through the stage with its tenant classifier, so
an SDS control plane can enforce per-tenant token-rate policies (the paper's
§5.2 fair-share scenario applied to serving): each tenant's channel holds a
DRL object whose rate is the tenant's *token budget per second*; Algorithm 2
redistributes leftover budget when tenants go idle.

Each request records when it was submitted, began its prefill, had its first
token on the host and had its last (:class:`RequestTimes`); once done, it
publishes its own ``serve.generate_ms``, ``serve.ttft_ms`` and
``serve.queue_wait_ms``. Spans (:mod:`repro.telemetry.spans`): ``paio.serve.admit``
(a drain's admission through the stage), ``paio.serve.prefill`` (one
request's cache init, ``paio.serve.cache_init``, then its prefill and first
token pull), ``paio.serve.decode``
(one lockstep step: its enforcement, then per live request its decode call,
``paio.serve.dispatch``, and its token pull, ``paio.serve.sync``) and
``paio.serve.cast_params`` (the weights' one cast to the compute dtype).
Gauges and counters: ``serve.params_precast_bytes``, ``serve.cache_bytes``
(one request's caches as allocated), ``serve.moe_dropped_choices`` (the
expert choices that the capacity bound dropped in prefills; a decode call of
one token never drops) and ``serve.moe_decode_experts_run`` (the held experts
whose FFN ran in decode calls, summed over layers).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import queue
import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import RequestType, Stage, build_context, propagate_tenant
from repro.launch.steps import build_decode_step, build_prefill_step
from repro.models import init_caches
from repro.models.model import ArchConfig, cast_for_compute
from repro.telemetry.metrics import MetricRegistry, get_registry
from repro.telemetry.spans import span


@dataclasses.dataclass
class RequestTimes:
    """When one request reached each point of the engine (``time.perf_counter``
    seconds; NaN until reached)."""

    submitted: float
    prefill_start: float = math.nan
    #: its first generated token is on the host
    first_token: float = math.nan
    #: its last generated token is on the host
    done: float = math.nan

    @property
    def queue_wait_s(self) -> float:
        return self.prefill_start - self.submitted

    @property
    def ttft_s(self) -> float:
        return self.first_token - self.submitted

    @property
    def latency_s(self) -> float:
        return self.done - self.submitted


@dataclasses.dataclass
class GenerationResult:
    tokens: List[int]
    prompt_len: int
    tenant: Optional[str] = None
    request_id: Optional[int] = None
    times: Optional[RequestTimes] = None


@dataclasses.dataclass
class _Pending:
    """A queued generation request awaiting batch admission."""

    prompts: np.ndarray
    max_new_tokens: int
    tenant: Optional[str]
    request_id: int = -1
    times: RequestTimes = dataclasses.field(default_factory=lambda: RequestTimes(time.perf_counter()))


@dataclasses.dataclass
class _Live:
    """One prefetched generation mid-decode (drain's lockstep loop state)."""

    pending: _Pending
    caches: object
    next_tok: object
    outs: List[List[int]]
    batch: int
    prompt_len: int


class ServeEngine:
    """Single-host serving: fixed max batch, greedy decoding.

    ``generate`` runs prompts through prefill then step-wise decode; when a
    ``stage`` is given, each generated token consumes tokens from the
    tenant's channel (context-only enforcement — the zero-copy fast path of
    paper §3.4), so token throughput per tenant is shaped by the control
    plane.

    ``params`` is the caller's tree; prefill and decode read a copy cast to
    the compute dtype (``cast_for_compute``), made once when ``params`` is
    set, instead of converting the float32 weights again in every call.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        max_seq: int = 512,
        stage: Optional[Stage] = None,
        drain_concurrency: int = 4,
        registry: Optional[MetricRegistry] = None,
    ) -> None:
        self.cfg = cfg
        self.max_seq = max_seq
        self.stage = stage
        #: serve statistics publish into the shared process-wide registry by
        #: default — one exporter endpoint covers serving and storage planes;
        #: pass an explicit registry for isolation
        self._registry = registry if registry is not None else get_registry()
        self._described: set = set()
        self.params = params
        #: lockstep window of ``drain``: how many queued requests decode (and
        #: hold KV caches) simultaneously. Peak drain memory is roughly
        #: ``drain_concurrency × init_caches(cfg, b, max_seq)`` — size it to
        #: the deployment; 1 restores the sequential (one-cache) envelope at
        #: the cost of per-window decode-enforcement coalescing.
        self.drain_concurrency = int(drain_concurrency)
        self._prefill = jax.jit(build_prefill_step(cfg))
        self._decode = jax.jit(build_decode_step(cfg), donate_argnums=1)
        self._queue: "queue.Queue[_Pending]" = queue.Queue()
        self._request_ids = itertools.count()

    @property
    def params(self):
        """The caller's weights, as given."""
        return self._params

    @params.setter
    def params(self, params) -> None:
        # the copy prefill and decode read; recomputed here so that swapped
        # weights are never served stale
        with span("paio.serve.cast_params"):
            cast = jax.block_until_ready(cast_for_compute(self.cfg, params))
        self._params, self._compute_params = params, cast
        precast = sum(
            c.nbytes
            for c, p in zip(jax.tree_util.tree_leaves(cast), jax.tree_util.tree_leaves(params))
            if c is not p
        )
        self._registry.set_gauge("serve.params_precast_bytes", float(precast))
        self._describe_once("serve.params_precast_bytes", "paio_serve_params_precast_bytes")

    def _describe_once(self, key: str, family: str, labels=None) -> None:
        # descriptors are immutable per key: describe once, not per decode
        # step (the registry lock + labels dict per call is avoidable churn)
        if key not in self._described:
            self._registry.describe(key, family, labels)
            self._described.add(key)

    def _publish(self, p: _Pending, n_tokens: int) -> None:
        """One completed request's telemetry: the per-tenant token counter,
        and windowed summaries (p50/p95/p99 on the exporter) of its own times
        from submission: to its last token on the host (``generate_ms``), to
        its first (``ttft_ms``) and to the start of its prefill
        (``queue_wait_ms``)."""
        tenant = p.tenant or "default"
        key = f"serve.{tenant}.tokens"
        self._registry.inc(key, float(n_tokens))
        self._describe_once(key, "paio_serve_tokens", {"tenant": tenant})
        t = p.times
        self._registry.observe("serve.generate_ms", t.latency_s * 1e3)
        self._describe_once("serve.generate_ms", "paio_serve_generate_ms")
        self._registry.observe("serve.ttft_ms", t.ttft_s * 1e3)
        self._describe_once("serve.ttft_ms", "paio_serve_ttft_ms")
        self._registry.observe("serve.queue_wait_ms", t.queue_wait_s * 1e3)
        self._describe_once("serve.queue_wait_ms", "paio_serve_queue_wait_ms")

    def _results(self, lv: "_Live") -> List[GenerationResult]:
        """A finished request's results, its telemetry published."""
        p = lv.pending
        self._publish(p, sum(len(o) for o in lv.outs))
        return [
            GenerationResult(
                tokens=o, prompt_len=lv.prompt_len, tenant=p.tenant, request_id=p.request_id, times=p.times
            )
            for o in lv.outs
        ]

    def _publish_prefill(self, caches, dropped: int) -> None:
        """One prefill's bytes of caches as allocated, and the expert choices
        its capacity bound dropped."""
        self._registry.set_gauge("serve.cache_bytes", float(sum(a.nbytes for a in jax.tree_util.tree_leaves(caches))))
        self._describe_once("serve.cache_bytes", "paio_serve_cache_bytes")
        self._registry.inc("serve.moe_dropped_choices", float(dropped))
        self._describe_once("serve.moe_dropped_choices", "paio_serve_moe_dropped_choices")

    def _publish_step(self, elapsed_s: float) -> None:
        """One decode step's wall time (drain: the lockstep step across all
        live requests; generate: the single request's step)."""
        self._registry.observe("serve.decode_step_ms", elapsed_s * 1e3)
        self._describe_once("serve.decode_step_ms", "paio_serve_decode_step_ms")

    def _enforce(self, tenant: Optional[str], n_tokens: int) -> None:
        if self.stage is None:
            return
        with propagate_tenant(tenant or "default"):
            ctx = build_context(RequestType.get, size=n_tokens)
            self.stage.enforce(ctx, None)

    # -- batched submit path (batched data plane) -------------------------
    def submit(
        self,
        prompts: np.ndarray,
        max_new_tokens: int = 32,
        tenant: Optional[str] = None,
    ) -> None:
        """Queue a generation request; ``drain`` admits and runs the queue."""
        self._queue.put(_Pending(np.asarray(prompts), int(max_new_tokens), tenant, next(self._request_ids)))

    def _admit_batch(self, pending: List[_Pending]) -> None:
        """Enforce the queued requests' prefill token cost as ONE batch.

        Each pending request contributes one context carrying its tenant and
        its prompt-token cost; the stage routes and rate-limits the whole drain
        in a single ``enforce_batch`` pass (per-tenant DRLs each see one
        cumulative consume instead of per-request lock/clock traffic).
        """
        if self.stage is None or not pending:
            return
        ctxs = []
        for p in pending:
            b, s0 = p.prompts.shape
            ctxs.append(
                build_context(
                    RequestType.get, size=b * s0, request_context="", workflow_id=None
                )
            )
            ctxs[-1].tenant = p.tenant or "default"
        self.stage.enforce_batch(ctxs)

    def _enforce_step_batch(self, lives: List[_Live]) -> None:
        """Coalesce one decode step's token costs across all live requests
        into ONE ``enforce_batch`` pass: each live request contributes a
        context carrying its tenant and its per-step cost (one token per
        sequence), exactly what ``generate`` enforces per step — but the
        stage routes/rate-limits the whole step at batch cost."""
        if self.stage is None or not lives:
            return
        ctxs = []
        for lv in lives:
            ctx = build_context(
                RequestType.get, size=lv.batch, request_context="", workflow_id=None
            )
            ctx.tenant = lv.pending.tenant or "default"
            ctxs.append(ctx)
        self.stage.enforce_batch(ctxs)

    def _prefill_one(self, p: _Pending) -> _Live:
        b, s0 = p.prompts.shape
        p.times.prefill_start = time.perf_counter()
        with span("paio.serve.prefill", request=p.request_id):
            with span("paio.serve.cache_init", request=p.request_id):
                caches = init_caches(self.cfg, b, self.max_seq, dtype=self.cfg.compute_dtype)
            batch = {
                "tokens": jnp.asarray(p.prompts, jnp.int32),
                "positions": jnp.broadcast_to(jnp.arange(s0, dtype=jnp.int32), (b, s0)),
            }
            next_tok, dropped, caches = self._prefill(self._compute_params, caches, batch)
            first, dropped = jax.device_get((next_tok, dropped))  # one pull for both
            outs = [[int(t)] for t in first[:, 0]]
        p.times.first_token = time.perf_counter()
        self._publish_prefill(caches, int(dropped))
        if p.max_new_tokens <= 1:
            p.times.done = p.times.first_token
        return _Live(p, caches, next_tok, outs, b, s0)

    def _decode_one_step(self, lv: _Live, step: int) -> None:
        # one call per live request: the dispatch and sync spans are per call,
        # at most ``drain_concurrency`` of each in a lockstep step
        rid = lv.pending.request_id
        pos = jnp.full((lv.batch, 1), lv.prompt_len + step - 1, jnp.int32)
        with span("paio.serve.dispatch", request=rid):
            lv.next_tok, ran, lv.caches = self._decode(
                self._compute_params, lv.caches, {"tokens": lv.next_tok, "positions": pos}
            )
        with span("paio.serve.sync", request=rid):
            tokens, ran = jax.device_get((lv.next_tok, ran))  # one pull for both
        self._registry.inc("serve.moe_decode_experts_run", float(ran))
        self._describe_once("serve.moe_decode_experts_run", "paio_serve_moe_decode_experts_run")
        for i, t in enumerate(tokens[:, 0]):
            lv.outs[i].append(int(t))
        if step + 1 >= lv.pending.max_new_tokens:
            lv.pending.times.done = time.perf_counter()

    def drain(self, max_concurrent: Optional[int] = None) -> List[GenerationResult]:
        """Drain the submit queue with batched enforcement end to end.

        Prefill admission for all queued requests is ONE ``enforce_batch``
        call (``_admit_batch``); the decode loops then run in lockstep so each
        decode *step* enforces its token costs across all live requests in one
        ``enforce_batch`` pass instead of one ``enforce`` per request per
        step. Token accounting per tenant is identical to sequential
        ``generate`` calls; only the lock/route/dispatch cost is amortized.

        Every lockstepped request holds its KV caches live simultaneously, so
        the queue is processed in windows of ``max_concurrent`` requests
        (default: the engine's ``drain_concurrency``) — memory is bounded by
        the window, not the (unbounded) queue depth.
        """
        window_size = max(
            self.drain_concurrency if max_concurrent is None else max_concurrent, 1
        )
        pending: List[_Pending] = []
        while True:
            try:
                pending.append(self._queue.get_nowait())
            except queue.Empty:
                break
        if not pending:
            return []
        with span("paio.serve.admit", requests=len(pending)):
            self._admit_batch(pending)
        results: List[GenerationResult] = []
        for at in range(0, len(pending), window_size):
            window = pending[at : at + window_size]
            lives = [self._prefill_one(p) for p in window]
            step = 1
            while True:
                active = [lv for lv in lives if step < lv.pending.max_new_tokens]
                if not active:
                    break
                ts = time.monotonic()
                with span("paio.serve.decode", step=step, live=len(active)):
                    self._enforce_step_batch(active)
                    for lv in active:
                        self._decode_one_step(lv, step)
                self._publish_step(time.monotonic() - ts)
                step += 1
            for lv in lives:
                results.extend(self._results(lv))
        return results

    def generate(
        self,
        prompts: np.ndarray,  # [B, S0] int32
        max_new_tokens: int = 32,
        tenant: Optional[str] = None,
        _prefill_admitted: bool = False,
    ) -> List[GenerationResult]:
        prompts = np.asarray(prompts)
        b, s0 = prompts.shape
        p = _Pending(prompts, int(max_new_tokens), tenant, next(self._request_ids))
        if not _prefill_admitted:  # drain() already batch-admitted prefill cost
            with span("paio.serve.admit", requests=1):
                self._enforce(tenant, b * s0)  # prefill cost: prompt tokens
        lv = self._prefill_one(p)
        for step in range(1, max_new_tokens):
            ts = time.monotonic()
            with span("paio.serve.decode", step=step, live=1):
                self._enforce(tenant, b)  # one token per sequence
                self._decode_one_step(lv, step)
            self._publish_step(time.monotonic() - ts)
        return self._results(lv)
