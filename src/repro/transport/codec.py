"""Binary payload codec for the stage-transport wire types.

The v2 protocol replaces per-call JSON string building with a compact,
self-describing binary encoding. Three layers, all little-endian:

* a **generic value codec** (:func:`pack_value` / :func:`unpack_value`)
  covering the JSON-native types (None, bool, int, float, str, bytes, list,
  dict) — used for ``stage_info`` replies and policy wire dicts. Unlike JSON
  it round-trips NaN/±inf and bytes, and never builds intermediate strings;
* a **rule codec** (:func:`encode_rule` / :func:`decode_rule`) with one type
  tag per rule dataclass (housekeeping / differentiation / enforcement) and
  ``struct``-packed fields;
* a **stats codec** (:func:`encode_stats` / :func:`decode_stats`): each
  :class:`~repro.core.stats.StatsSnapshot` is one fixed 104-byte ``struct``
  pack plus its channel name plus a sparse run of nonzero wait-histogram
  buckets — the collect hot path never touches a dict.

Decode failures raise :class:`TransportError` (a :class:`ConnectionError`
subclass) so the control plane's liveness machinery treats a corrupted
stream exactly like a dead peer: down-mark, defer, reconnect.
"""
from __future__ import annotations

import struct
from typing import Any, Dict, Tuple

from repro.core.rules import DifferentiationRule, EnforcementRule, HousekeepingRule
from repro.core.stats import StageStats, StatsSnapshot
from repro.filters.spec import INSTALL_FILTER, FilterSpec
from repro.telemetry.histogram import NBUCKETS


class TransportError(ConnectionError):
    """Protocol-level failure (bad frame, oversized payload, undecodable
    bytes). A ConnectionError subclass on purpose: the stream is
    desynchronized and the only safe recovery is reconnect."""


class StageError(ConnectionError):
    """The stage raised while serving a non-rule call (collect/stage_info).
    Also a ConnectionError subclass: the control plane down-marks the stage
    and re-admits it via a fresh probe instead of crashing the loop."""


# --------------------------------------------------------------------------- #
# generic value codec                                                          #
# --------------------------------------------------------------------------- #
_T_NONE = 0x00
_T_TRUE = 0x01
_T_FALSE = 0x02
_T_INT64 = 0x03
_T_BIGINT = 0x04
_T_FLOAT64 = 0x05
_T_STR = 0x06
_T_BYTES = 0x07
_T_LIST = 0x08
_T_DICT = 0x09

_U32 = struct.Struct("<I")
_I64 = struct.Struct("<q")
_F64 = struct.Struct("<d")

_INT64_MIN = -(1 << 63)
_INT64_MAX = (1 << 63) - 1


def _write_str(buf: bytearray, s: str) -> None:
    raw = s.encode("utf-8")
    buf += _U32.pack(len(raw))
    buf += raw


def _write_value(buf: bytearray, obj: Any) -> None:
    if obj is None:
        buf.append(_T_NONE)
    elif obj is True:
        buf.append(_T_TRUE)
    elif obj is False:
        buf.append(_T_FALSE)
    elif isinstance(obj, int):
        if _INT64_MIN <= obj <= _INT64_MAX:
            buf.append(_T_INT64)
            buf += _I64.pack(obj)
        else:
            raw = obj.to_bytes((obj.bit_length() + 8) // 8, "little", signed=True)
            buf.append(_T_BIGINT)
            buf += _U32.pack(len(raw))
            buf += raw
    elif isinstance(obj, float):
        buf.append(_T_FLOAT64)
        buf += _F64.pack(obj)
    elif isinstance(obj, str):
        buf.append(_T_STR)
        _write_str(buf, obj)
    elif isinstance(obj, (bytes, bytearray, memoryview)):
        raw = bytes(obj)
        buf.append(_T_BYTES)
        buf += _U32.pack(len(raw))
        buf += raw
    elif isinstance(obj, (list, tuple)):
        buf.append(_T_LIST)
        buf += _U32.pack(len(obj))
        for item in obj:
            _write_value(buf, item)
    elif isinstance(obj, dict):
        buf.append(_T_DICT)
        buf += _U32.pack(len(obj))
        for key, value in obj.items():
            _write_value(buf, key)
            _write_value(buf, value)
    else:
        raise TypeError(f"value of type {type(obj).__name__} is not wire-encodable")


class _Reader:
    """Cursor over an immutable payload; all decode errors surface as
    :class:`TransportError` with the offending offset."""

    __slots__ = ("buf", "off")

    def __init__(self, buf: bytes, off: int = 0) -> None:
        self.buf = buf
        self.off = off

    def take(self, n: int) -> bytes:
        end = self.off + n
        if end > len(self.buf):
            raise TransportError(
                f"truncated payload: wanted {n} bytes at offset {self.off}, have {len(self.buf)}"
            )
        out = self.buf[self.off:end]
        self.off = end
        return out

    def u8(self) -> int:
        return self.take(1)[0]

    def u32(self) -> int:
        return _U32.unpack(self.take(4))[0]

    def i64(self) -> int:
        return _I64.unpack(self.take(8))[0]

    def f64(self) -> float:
        return _F64.unpack(self.take(8))[0]

    def str_(self) -> str:
        n = self.u32()
        try:
            return self.take(n).decode("utf-8")
        except UnicodeDecodeError as exc:
            raise TransportError(f"invalid utf-8 in wire string: {exc}") from exc


def _read_value(r: _Reader) -> Any:
    tag = r.u8()
    if tag == _T_NONE:
        return None
    if tag == _T_TRUE:
        return True
    if tag == _T_FALSE:
        return False
    if tag == _T_INT64:
        return r.i64()
    if tag == _T_BIGINT:
        n = r.u32()
        return int.from_bytes(r.take(n), "little", signed=True)
    if tag == _T_FLOAT64:
        return r.f64()
    if tag == _T_STR:
        return r.str_()
    if tag == _T_BYTES:
        n = r.u32()
        return r.take(n)
    if tag == _T_LIST:
        n = r.u32()
        return [_read_value(r) for _ in range(n)]
    if tag == _T_DICT:
        n = r.u32()
        return {_read_value(r): _read_value(r) for _ in range(n)}
    raise TransportError(f"unknown value tag 0x{tag:02x} at offset {r.off - 1}")


def pack_value(obj: Any) -> bytes:
    buf = bytearray()
    _write_value(buf, obj)
    return bytes(buf)


def unpack_value(payload: bytes) -> Any:
    r = _Reader(payload)
    out = _read_value(r)
    if r.off != len(payload):
        raise TransportError(f"{len(payload) - r.off} trailing bytes after value")
    return out


# --------------------------------------------------------------------------- #
# rule codec                                                                   #
# --------------------------------------------------------------------------- #
_RULE_HSK = 0x01
_RULE_DIF = 0x02
_RULE_ENF = 0x03
#: install_filter housekeeping rules in canonical FilterSpec form get their
#: own struct-packed encoding (the spec fields flat, no generic value-codec
#: dict for the envelope); non-canonical ones fall back to _RULE_HSK
_RULE_FILTER = 0x04

#: sentinel flag byte for Optional[str] fields
_OPT_NONE = 0x00
_OPT_SOME = 0x01


def _write_opt_str(buf: bytearray, s) -> None:
    if s is None:
        buf.append(_OPT_NONE)
    else:
        buf.append(_OPT_SOME)
        _write_str(buf, s)


def _read_opt_str(r: _Reader):
    flag = r.u8()
    if flag == _OPT_NONE:
        return None
    if flag == _OPT_SOME:
        return r.str_()
    raise TransportError(f"bad optional-string flag 0x{flag:02x}")


def encode_filter_spec(spec: FilterSpec) -> bytes:
    """Flat struct-packed image of a :class:`FilterSpec` (the payload of a
    ``_RULE_FILTER`` frame, minus the tag byte)."""
    buf = bytearray()
    _write_str(buf, spec.name)
    buf += _U32.pack(spec.version)
    _write_str(buf, spec.channel)
    _write_str(buf, spec.filter_id)
    _write_value(buf, spec.params or {})
    return bytes(buf)


def decode_filter_spec(payload: bytes) -> FilterSpec:
    r = _Reader(payload)
    spec = FilterSpec(
        name=r.str_(),
        version=r.u32(),
        channel=r.str_(),
        filter_id=r.str_(),
        params=_read_value(r),
    )
    if r.off != len(payload):
        raise TransportError(f"{len(payload) - r.off} trailing bytes after filter spec")
    return spec


def encode_rule(rule) -> bytes:
    buf = bytearray()
    if isinstance(rule, HousekeepingRule):
        if rule.op == INSTALL_FILTER:
            spec = FilterSpec.from_rule(rule)
            if spec.to_rule() == rule:  # canonical — lossless fast path
                buf.append(_RULE_FILTER)
                buf += encode_filter_spec(spec)
                return bytes(buf)
        buf.append(_RULE_HSK)
        _write_str(buf, rule.op)
        _write_str(buf, rule.channel)
        _write_opt_str(buf, rule.object_id)
        _write_opt_str(buf, rule.object_kind)
        _write_value(buf, rule.params or {})
    elif isinstance(rule, DifferentiationRule):
        buf.append(_RULE_DIF)
        _write_str(buf, rule.channel)
        _write_value(buf, rule.match or {})
        _write_opt_str(buf, rule.object_id)
    elif isinstance(rule, EnforcementRule):
        buf.append(_RULE_ENF)
        _write_str(buf, rule.channel)
        _write_str(buf, rule.object_id)
        _write_value(buf, rule.state or {})
    else:
        raise TypeError(f"not a rule: {rule!r}")
    return bytes(buf)


def decode_rule(payload: bytes):
    r = _Reader(payload)
    tag = r.u8()
    if tag == _RULE_HSK:
        return HousekeepingRule(
            op=r.str_(),
            channel=r.str_(),
            object_id=_read_opt_str(r),
            object_kind=_read_opt_str(r),
            params=_read_value(r),
        )
    if tag == _RULE_DIF:
        return DifferentiationRule(
            channel=r.str_(), match=_read_value(r), object_id=_read_opt_str(r)
        )
    if tag == _RULE_ENF:
        return EnforcementRule(channel=r.str_(), object_id=r.str_(), state=_read_value(r))
    if tag == _RULE_FILTER:
        return decode_filter_spec(payload[1:]).to_rule()
    raise TransportError(f"unknown rule tag 0x{tag:02x}")


# --------------------------------------------------------------------------- #
# stats codec                                                                  #
# --------------------------------------------------------------------------- #
#: fixed numeric fields of one StatsSnapshot, in dataclass order after
#: ``channel``: ops, bytes, window_seconds, throughput, iops, cumulative_ops,
#: cumulative_bytes, inflight, wait_seconds, wait_p50_ms, wait_p95_ms,
#: wait_p99_ms; then cumulative_wait_seconds
_SNAP = struct.Struct("<qqdddqqqddddd")
#: one sparse wait-histogram entry: bucket index (u8), op count (i64). The
#: fixed struct is followed by a u8 count of these pairs — a typical window
#: touches a handful of buckets, so sparse beats shipping all 26 counts
_HIST_PAIR = struct.Struct("<Bq")
#: u8 sentinel for "no histogram at all" (old-wire / merged snapshots) —
#: distinct from zero pairs, which means "histogram present, all buckets 0"
#: (an idle window still owns its histogram)
_HIST_ABSENT = 0xFF


def encode_stats(stats: StageStats) -> bytes:
    per_channel = stats.per_channel
    buf = bytearray(_U32.pack(len(per_channel)))
    for name, s in per_channel.items():
        _write_str(buf, name)
        _write_str(buf, s.channel)
        buf += _SNAP.pack(
            s.ops,
            s.bytes,
            s.window_seconds,
            s.throughput,
            s.iops,
            s.cumulative_ops,
            s.cumulative_bytes,
            s.inflight,
            s.wait_seconds,
            s.wait_p50_ms,
            s.wait_p95_ms,
            s.wait_p99_ms,
            s.cumulative_wait_seconds,
        )
        if s.wait_hist:
            nonzero = [(i, c) for i, c in enumerate(s.wait_hist) if c]
            buf.append(len(nonzero))
            for i, c in nonzero:
                buf += _HIST_PAIR.pack(i, c)
        else:
            buf.append(_HIST_ABSENT)
        # filter-plane extras: sparse (key, f64) run — typically empty
        extras = s.extras
        buf += _U32.pack(len(extras))
        for ekey, eval_ in extras.items():
            _write_str(buf, ekey)
            buf += _F64.pack(eval_)
    return bytes(buf)


def decode_stats(payload: bytes) -> StageStats:
    r = _Reader(payload)
    count = r.u32()
    per_channel: Dict[str, StatsSnapshot] = {}
    for _ in range(count):
        key = r.str_()
        channel = r.str_()
        (
            ops,
            nbytes,
            window_seconds,
            throughput,
            iops,
            cumulative_ops,
            cumulative_bytes,
            inflight,
            wait_seconds,
            wait_p50_ms,
            wait_p95_ms,
            wait_p99_ms,
            cumulative_wait_seconds,
        ) = _SNAP.unpack(r.take(_SNAP.size))
        npairs = r.u8()
        wait_hist: Tuple[int, ...] = ()
        if npairs != _HIST_ABSENT:
            if npairs > NBUCKETS:
                raise TransportError(f"histogram pair count {npairs} exceeds {NBUCKETS} buckets")
            counts = [0] * NBUCKETS
            for _ in range(npairs):
                idx, c = _HIST_PAIR.unpack(r.take(_HIST_PAIR.size))
                if idx >= NBUCKETS:
                    raise TransportError(f"histogram bucket index {idx} out of range")
                counts[idx] = c
            wait_hist = tuple(counts)
        nextras = r.u32()
        extras: Dict[str, float] = {}
        for _ in range(nextras):
            ekey = r.str_()
            extras[ekey] = r.f64()
        per_channel[key] = StatsSnapshot(
            channel=channel,
            ops=ops,
            bytes=nbytes,
            window_seconds=window_seconds,
            throughput=throughput,
            iops=iops,
            cumulative_ops=cumulative_ops,
            cumulative_bytes=cumulative_bytes,
            inflight=inflight,
            wait_seconds=wait_seconds,
            wait_p50_ms=wait_p50_ms,
            wait_p95_ms=wait_p95_ms,
            wait_p99_ms=wait_p99_ms,
            wait_hist=wait_hist,
            extras=extras,
            cumulative_wait_seconds=cumulative_wait_seconds,
        )
    if r.off != len(payload):
        raise TransportError(f"{len(payload) - r.off} trailing bytes after stats")
    return StageStats(per_channel=per_channel)


# --------------------------------------------------------------------------- #
# enforce-batch codec (shard router → shard stage)                             #
# --------------------------------------------------------------------------- #
#: fixed numeric fields of one enforce group: workflow_id, request_type,
#: size, count (how many identical requests the group stands for)
_ENF_GROUP = struct.Struct("<qqqq")


def encode_enforce_batch(shard_id: str, groups) -> bytes:
    """Encode a shard-addressed enforce batch.

    ``groups`` is a sequence of ``(workflow_id, request_type, size,
    request_context, tenant, count)`` tuples — one entry per *flow* in the
    batch, not per request. The router groups a batch by flow before
    dispatch, so a 4096-request batch over a handful of flows crosses the
    socket as a handful of group records; request payload bytes never do
    (ROADMAP's "only control frames need the socket").

    ``shard_id`` is the frame-level addressee: the serving shard rejects a
    mismatch, which turns a router placement bug into a loud error instead
    of silently enforcing on the wrong shard's channels.
    """
    buf = bytearray()
    _write_str(buf, shard_id)
    buf += _U32.pack(len(groups))
    for workflow_id, request_type, size, request_context, tenant, count in groups:
        buf += _ENF_GROUP.pack(workflow_id, int(request_type), size, count)
        _write_str(buf, request_context)
        _write_opt_str(buf, tenant)
    return bytes(buf)


def decode_enforce_batch(payload: bytes):
    """Inverse of :func:`encode_enforce_batch` → ``(shard_id, groups)``."""
    r = _Reader(payload)
    shard_id = r.str_()
    n = r.u32()
    groups = []
    for _ in range(n):
        workflow_id, request_type, size, count = _ENF_GROUP.unpack(r.take(_ENF_GROUP.size))
        request_context = r.str_()
        tenant = _read_opt_str(r)
        if count < 0:
            raise TransportError(f"negative enforce group count {count}")
        groups.append((workflow_id, request_type, size, request_context, tenant, count))
    if r.off != len(payload):
        raise TransportError(f"{len(payload) - r.off} trailing bytes after enforce batch")
    return shard_id, groups


def decode_int(payload: bytes) -> int:
    value = unpack_value(payload)
    if isinstance(value, bool) or not isinstance(value, int):
        raise TransportError(f"expected int reply, got {type(value).__name__}")
    return value


def decode_bool(payload: bytes) -> bool:
    value = unpack_value(payload)
    if not isinstance(value, bool):
        raise TransportError(f"expected bool reply, got {type(value).__name__}")
    return value
