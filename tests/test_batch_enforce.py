"""Batched data plane: enforce_batch ≡ sequential enforce (routing, Results,
stats totals), vectorized tokenizer exactness, and the token-bucket
cumulative-admission invariant under batch consume."""
from __future__ import annotations

import os
import random
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    BG_COMPACTION_HIGH,
    BG_COMPACTION_L0,
    BG_FLUSH,
    DRL,
    Checksum,
    Context,
    DifferentiationRule,
    HousekeepingRule,
    Instance,
    Noop,
    PriorityGate,
    QuantizeInt8,
    RequestType,
    Stage,
    TokenBucket,
    VirtualClock,
    murmur3_32,
    murmur3_32_batch,
    token_for,
    token_for_batch,
)


# --------------------------------------------------------------------------- #
# vectorized tokenizer                                                         #
# --------------------------------------------------------------------------- #
class TestBatchedHashing:
    def test_murmur_batch_matches_scalar_all_tail_lengths(self):
        rng = random.Random(7)
        datas = [bytes(rng.randrange(256) for _ in range(n)) for n in range(0, 70)]
        datas += [bytes(rng.randrange(256) for _ in range(rng.randrange(0, 300))) for _ in range(100)]
        for seed in (0, 1, 0x5D5, 0xFFFFFFFF, 0x9747B28C):
            assert murmur3_32_batch(datas, seed) == [murmur3_32(d, seed) for d in datas]

    def test_murmur_batch_reference_vectors(self):
        datas = [b"", b"hello", b"hello, world"]
        assert murmur3_32_batch(datas, 0) == [0x00000000, 0x248BFA47, 0x149BBB7F]

    def test_token_for_batch_matches_scalar(self):
        parts = [
            (),
            (1,),
            (2, 1, "bg_flush"),
            (123, "x", None),
            ("ü", "日本語", -5),
            tuple(range(20)),
        ]
        assert token_for_batch(parts) == [token_for(p) for p in parts]

    def test_empty_batch(self):
        assert murmur3_32_batch([]) == []
        assert token_for_batch([]) == []


# --------------------------------------------------------------------------- #
# stage/channel batch ≡ sequential                                             #
# --------------------------------------------------------------------------- #
def _mixed_stage(clock: VirtualClock) -> Stage:
    """Channels + per-object routing covering noop-copy, checksum and DRL."""
    st = Stage("kvs", clock=clock)
    for ch in ("fg", "flush", "compact"):
        st.hsk_rule(HousekeepingRule(op="create_channel", channel=ch))
    st.dif_rule(DifferentiationRule(channel="fg", match={"request_context": ""}))
    st.dif_rule(DifferentiationRule(channel="flush", match={"request_context": BG_FLUSH}))
    st.dif_rule(DifferentiationRule(channel="compact", match={"request_context": BG_COMPACTION_L0}))
    st.dif_rule(DifferentiationRule(channel="compact", match={"request_context": BG_COMPACTION_HIGH}))
    st.channel("fg").add_object("0", Noop(copy_content=True))
    st.channel("flush").add_object("0", Checksum())
    st.hsk_rule(
        HousekeepingRule(
            op="create_object", channel="compact", object_id="drl_l0", object_kind="drl", params={"rate": 1000.0}
        )
    )
    st.dif_rule(
        DifferentiationRule(channel="compact", match={"request_context": BG_COMPACTION_L0}, object_id="drl_l0")
    )
    return st


def _mixed_requests(n: int):
    rng = random.Random(3)
    rcs = ["", BG_FLUSH, BG_COMPACTION_L0, BG_COMPACTION_HIGH, "unknown_ctx"]
    ctxs, reqs = [], []
    for i in range(n):
        rc = rcs[i % len(rcs)]
        size = rng.choice([16, 64, 4096])
        ctxs.append(Context(i % 4, RequestType.write, size, rc))
        reqs.append(bytes([i % 251]) * size)
    return ctxs, reqs


class TestBatchEquivalence:
    def test_mixed_channels_and_objects(self):
        ctxs, reqs = _mixed_requests(40)
        s_seq, s_bat = _mixed_stage(VirtualClock()), _mixed_stage(VirtualClock())
        seq = [s_seq.enforce(c, r) for c, r in zip(ctxs, reqs)]
        bat = s_bat.enforce_batch(ctxs, reqs)
        assert len(seq) == len(bat)
        for a, b in zip(seq, bat):
            assert bytes(a.content) == bytes(b.content)
            assert a.meta == b.meta
        # same routing → same per-channel stats totals
        st_seq, st_bat = s_seq.collect(), s_bat.collect()
        assert set(st_seq.per_channel) == set(st_bat.per_channel)
        for ch in st_seq.per_channel:
            a, b = st_seq.per_channel[ch], st_bat.per_channel[ch]
            assert (a.ops, a.bytes) == (b.ops, b.bytes), ch
        # DRL total imposed wait matches the sequential walk (same debt)
        assert sum(r.wait_seconds for r in bat) == pytest.approx(
            sum(r.wait_seconds for r in seq)
        )

    def test_homogeneous_fast_path(self):
        s_seq, s_bat = _mixed_stage(VirtualClock()), _mixed_stage(VirtualClock())
        ctx = Context(1, RequestType.write, 64, "")
        payload = b"p" * 64
        seq = [s_seq.enforce(ctx, payload) for _ in range(32)]
        bat = s_bat.enforce_batch([ctx] * 32, [payload] * 32)
        assert [r.content for r in seq] == [r.content for r in bat]
        a = s_seq.collect().per_channel["fg"]
        b = s_bat.collect().per_channel["fg"]
        assert (a.ops, a.bytes) == (b.ops, b.bytes) == (32, 32 * 64)

    def test_batch_routing_matches_select_channel(self):
        st = _mixed_stage(VirtualClock())
        ctxs, _ = _mixed_requests(25)
        assert st.select_channels_batch(ctxs) == [st.select_channel(c) for c in ctxs]
        # and again with a warm cache
        assert st.select_channels_batch(ctxs) == [st.select_channel(c) for c in ctxs]

    def test_empty_and_none_requests(self):
        st = _mixed_stage(VirtualClock())
        assert st.enforce_batch([], None) == []
        ctxs = [Context(1, RequestType.read, 8, ""), Context(1, RequestType.read, 8, BG_FLUSH)]
        out = st.enforce_batch(ctxs, None)
        assert [r.content for r in out] == [None, None]

    def test_bare_stage_passthrough(self):
        st = Stage("bare", clock=VirtualClock(), create_default_channel=False)
        out = st.enforce_batch([Context(1, RequestType.read, 4)] * 2, [b"a", b"b"])
        assert [r.content for r in out] == [b"a", b"b"]

    def test_noop_batch_copies_mutable_buffers(self):
        noop = Noop(copy_content=True)
        bufs = [bytearray(b"x" * 32) for _ in range(4)]
        out = noop.obj_enf_batch([Context(1, 2, 32)] * 4, bufs)
        bufs[0][0] = 0
        assert out[0].content == b"x" * 32  # enforced copy unaffected

    def test_noop_batch_mixed_payload_kinds(self):
        # mixed batches must match sequential obj_enf, not crash or coerce
        noop = Noop(copy_content=True)
        ctxs = [Context(1, 2, 8)] * 4
        reqs = [b"abcdefgh", None, np.arange(2, dtype=np.float64), bytearray(b"12345678")]
        out = noop.obj_enf_batch(ctxs, reqs)
        seq = [noop.obj_enf(c, r) for c, r in zip(ctxs, reqs)]
        assert out[0].content == seq[0].content
        assert out[1].content is None
        assert isinstance(out[2].content, np.ndarray)
        assert np.array_equal(out[2].content, seq[2].content)
        assert bytes(out[3].content) == bytes(seq[3].content)

    def test_noop_batch_ndarray_stack(self):
        noop = Noop(copy_content=True)
        arrs = [np.full((8,), i, np.float32) for i in range(4)]
        out = noop.obj_enf_batch([Context(1, 2, 32)] * 4, arrs)
        arrs[2][:] = -1.0
        assert out[2].content[0] == 2.0  # vectorized copy is a real copy
        for i, r in enumerate(out[:2]):
            assert np.array_equal(r.content, np.full((8,), i, np.float32))

    def test_instance_batch_submit(self):
        st = _mixed_stage(VirtualClock())
        inst = Instance(st, workflow_of=lambda: 1)
        sizes = [16, 32, 64]
        out = inst.enforce_batch(RequestType.write, sizes, [b"a" * s for s in sizes])
        assert [len(r.content) for r in out] == sizes
        snap = st.collect().per_channel["fg"]
        assert (snap.ops, snap.bytes) == (3, 112)

    def test_array_instance_write_batch(self):
        from repro.core import ArrayInstance

        st = _mixed_stage(VirtualClock())
        inst = ArrayInstance(st, workflow_of=lambda: 1)
        arrays = [np.full((8,), i, np.float32) for i in range(3)]
        written = {}
        inst.on_write_batch(arrays, lambda i, payload: written.__setitem__(i, payload))
        assert sorted(written) == [0, 1, 2]
        for i in range(3):
            assert np.array_equal(written[i], arrays[i])
        snap = st.collect().per_channel["fg"]
        assert (snap.ops, snap.bytes) == (3, 3 * 32)

    def test_array_instance_read_batch(self):
        from repro.core import ArrayInstance

        st = _mixed_stage(VirtualClock())
        inst = ArrayInstance(st, workflow_of=lambda: 1)
        out = inst.on_read_batch([64, 64], [lambda: np.zeros(16), lambda: np.ones(16)])
        assert out[1][0] == 1.0
        snap = st.collect().per_channel["fg"]
        assert (snap.ops, snap.bytes) == (2, 128)

    def test_write_shards_enforced_through_stage(self, tmp_path):
        from repro.data.pipeline import DATA_PREP, FileTokenSource

        clk = VirtualClock()
        st = Stage("io", clock=clk)
        st.hsk_rule(HousekeepingRule(op="create_channel", channel="prep"))
        st.dif_rule(DifferentiationRule(channel="prep", match={"request_context": DATA_PREP}))
        paths = [str(tmp_path / f"s{i}.bin") for i in range(3)]
        arrays = [np.arange(50, dtype=np.int32) + i for i in range(3)]
        FileTokenSource.write_shards(paths, arrays, stage=st)
        src = FileTokenSource(paths, batch=1, seq=10)
        assert np.array_equal(src.read(0).reshape(-1), arrays[0][:10])
        snap = st.collect().per_channel["prep"]
        assert (snap.ops, snap.bytes) == (3, 3 * 200)


# --------------------------------------------------------------------------- #
# token bucket admission under batch consume                                   #
# --------------------------------------------------------------------------- #
class TestBatchAdmission:
    def test_cumulative_invariant_under_batched_consume(self):
        """admitted(T) ≤ capacity + rate·(T − t0) must hold when whole batches
        are admitted with one consume (the DRL batch path)."""
        rng = random.Random(11)
        clk = VirtualClock()
        rate, capacity = 500.0, 100.0
        drl = DRL(rate=rate, refill_period=capacity / rate, clock=clk)
        admitted = 0.0
        for _ in range(30):
            bs = rng.randrange(1, 64)
            sizes = [rng.randrange(1, 50) for _ in range(bs)]
            ctxs = [Context(1, RequestType.write, s) for s in sizes]
            drl.obj_enf_batch(ctxs)
            admitted += sum(sizes)
            assert admitted <= capacity + rate * clk.now() + 1e-6 * admitted + 1e-9

    def test_batch_wait_equals_sequential_total(self):
        clk_a, clk_b = VirtualClock(), VirtualClock()
        a = DRL(rate=100.0, refill_period=1.0, clock=clk_a)
        b = DRL(rate=100.0, refill_period=1.0, clock=clk_b)
        ctxs = [Context(1, RequestType.write, 50) for _ in range(8)]
        seq_wait = sum(a.obj_enf(c).wait_seconds for c in ctxs)
        bat_wait = sum(r.wait_seconds for r in b.obj_enf_batch(ctxs))
        assert bat_wait == pytest.approx(seq_wait)
        assert clk_a.now() == pytest.approx(clk_b.now())

    def test_batch_wait_attributed_proportionally(self):
        clk = VirtualClock()
        drl = DRL(rate=100.0, refill_period=0.01, clock=clk)
        ctxs = [Context(1, RequestType.write, s) for s in (100, 300)]
        out = drl.obj_enf_batch(ctxs)
        total = sum(r.wait_seconds for r in out)
        assert total > 0
        assert out[1].wait_seconds == pytest.approx(3 * out[0].wait_seconds)

    def test_token_bucket_batch_vs_scalar_arithmetic(self):
        # one consume(sum) leaves the bucket exactly where n consumes would
        clk_a, clk_b = VirtualClock(), VirtualClock()
        ta = TokenBucket(rate=50.0, capacity=200.0, clock=clk_a)
        tb = TokenBucket(rate=50.0, capacity=200.0, clock=clk_b)
        for n in (30.0, 70.0, 25.0):
            ta.consume(n)
        tb.consume(125.0)
        assert ta.available() == pytest.approx(tb.available())


class TestPriorityGateBatch:
    def test_high_admitted_low_waits(self):
        clk = VirtualClock()
        gate = PriorityGate(priority_of={"fg": 1}, clock=clk)
        ctxs = [
            Context(1, RequestType.write, 1, "fg"),
            Context(1, RequestType.write, 1, "bg"),
            Context(2, RequestType.write, 1, "fg"),
        ]
        out = gate.obj_enf_batch(ctxs, [b"a", b"b", b"c"])
        assert out[0].wait_seconds == 0.0 and out[2].wait_seconds == 0.0
        assert out[1].wait_seconds > 0.0  # low yields while high is recent
        assert [r.content for r in out] == [b"a", b"b", b"c"]

    def test_shared_wait_attributed_once(self):
        # the single batch yield must not be multiplied across low requests
        clk = VirtualClock()
        gate = PriorityGate(priority_of={"fg": 1}, clock=clk)
        ctxs = [Context(1, 2, 1, "fg")] + [Context(1, 2, 1, "bg")] * 5
        out = gate.obj_enf_batch(ctxs)
        low_waits = [r.wait_seconds for r in out[1:]]
        assert low_waits[0] > 0.0
        assert all(w == 0.0 for w in low_waits[1:])

    def test_all_low_no_recent_high_passes(self):
        clk = VirtualClock()
        gate = PriorityGate(priority_of={"fg": 1}, clock=clk)
        clk.sleep(1.0)  # any initial high-window long expired
        out = gate.obj_enf_batch([Context(1, 2, 1, "bg")] * 3)
        assert all(r.wait_seconds == 0.0 for r in out)


# --------------------------------------------------------------------------- #
# transformation batches                                                       #
# --------------------------------------------------------------------------- #
class TestTransformationBatches:
    def test_quantize_batch_identical_to_per_item(self):
        q = QuantizeInt8(block=64)
        ctx = Context(1, RequestType.write, 0)
        arrs = [
            np.random.default_rng(i).normal(size=(7, 13)).astype(np.float32) for i in range(6)
        ]
        per = [q.obj_enf(ctx, a) for a in arrs]
        bat = q.obj_enf_batch([ctx] * 6, arrs)
        for a, b in zip(per, bat):
            assert np.array_equal(a.content[0], b.content[0])
            assert np.allclose(a.content[1], b.content[1])
            assert a.meta == b.meta
            back = QuantizeInt8.dequantize(b.content, b.meta)
            assert back.shape == (7, 13)

    def test_quantize_batch_ragged_and_none(self):
        q = QuantizeInt8(block=32)
        ctx = Context(1, RequestType.write, 0)
        arrs = [np.ones(10, np.float32), None, np.ones(100, np.float32)]
        out = q.obj_enf_batch([ctx] * 3, arrs)
        assert out[1].content is None
        for i in (0, 2):
            per = q.obj_enf(ctx, arrs[i])
            assert np.array_equal(per.content[0], out[i].content[0])

    def test_quantize_pallas_path_matches_numpy(self):
        pytest.importorskip("jax")
        ctx = Context(1, RequestType.write, 0)
        arrs = [np.random.default_rng(i).normal(size=(256,)).astype(np.float32) for i in range(5)]
        qp = QuantizeInt8(block=128, use_pallas=True, interpret=True)
        qn = QuantizeInt8(block=128, use_pallas=False)
        rp = qp.obj_enf_batch([ctx] * 5, arrs)
        rn = qn.obj_enf_batch([ctx] * 5, arrs)
        for a, b in zip(rp, rn):
            assert np.array_equal(np.asarray(a.content[0]), b.content[0])
            np.testing.assert_allclose(np.asarray(a.content[1]), b.content[1], rtol=1e-6)

    def test_quantize_kernel_failure_propagates(self, monkeypatch):
        pytest.importorskip("jax")
        import repro.kernels.quantize.ops as qops

        def broken(*args, **kwargs):
            raise RuntimeError("kernel refused")

        monkeypatch.setattr(qops, "quantize_rows_int8", broken)
        ctx = Context(1, RequestType.write, 0)
        qp = QuantizeInt8(block=128, use_pallas=True, interpret=True)
        with pytest.raises(RuntimeError, match="kernel refused"):
            qp.obj_enf(ctx, np.ones(256, np.float32))
        with pytest.raises(RuntimeError, match="kernel refused"):
            qp.obj_enf_batch([ctx] * 2, [np.ones(256, np.float32)] * 2)
        assert qp.kernel_calls == 0

    def test_quantize_pallas_needs_lane_aligned_block(self):
        with pytest.raises(ValueError, match="multiple of 128"):
            QuantizeInt8(block=96, use_pallas=True).obj_enf(
                Context(1, RequestType.write, 0), np.ones(96, np.float32)
            )

    def test_quantize_auto_off_tpu_is_numpy(self):
        jax = pytest.importorskip("jax")
        jax.devices()  # this process runs JAX, on the CPU
        q = QuantizeInt8(block=128)
        q.obj_enf(Context(1, RequestType.write, 0), np.ones(256, np.float32))
        assert q.kernel_calls == 0

    def test_quantize_auto_never_initializes_jax(self):
        """A stage process that has not touched JAX stays off the chip."""
        pytest.importorskip("jax")
        code = (
            "import sys, numpy as np\n"
            "from repro.core import Context, RequestType\n"
            "from repro.core.objects import QuantizeInt8\n"
            "q = QuantizeInt8(block=256)\n"
            "q.obj_enf(Context(1, RequestType.write, 0), np.ones(512, np.float32))\n"
            "assert 'jax' not in sys.modules, 'QuantizeInt8 imported jax'\n"
            "import jax\n"
            "from jax._src import xla_bridge\n"
            "q = QuantizeInt8(block=256)\n"
            "q.obj_enf(Context(1, RequestType.write, 0), np.ones(512, np.float32))\n"
            "assert not xla_bridge.backends_are_initialized(), 'QuantizeInt8 initialized a backend'\n"
            "assert q.kernel_calls == 0\n"
        )
        src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120
        )
        assert proc.returncode == 0, proc.stderr

    def test_checksum_batch_matches_per_item(self):
        ck = Checksum()
        ctx = Context(1, RequestType.write, 0)
        reqs = [b"abcd" * i for i in range(1, 6)] + [None]
        per = [ck.obj_enf(ctx, r) for r in reqs]
        bat = ck.obj_enf_batch([ctx] * 6, reqs)
        assert [r.meta for r in per] == [r.meta for r in bat]


# --------------------------------------------------------------------------- #
# stats batch recording                                                        #
# --------------------------------------------------------------------------- #
class TestStatsBatch:
    def test_record_batch_equals_sequential_records(self):
        from repro.core.stats import ChannelStats

        clk = VirtualClock()
        a, b = ChannelStats("a", clk), ChannelStats("b", clk)
        for s in (10, 20, 30):
            a.record(s)
        b.record_batch(3, 60)
        clk.sleep(1.0)
        sa, sb = a.collect(), b.collect()
        assert (sa.ops, sa.bytes) == (sb.ops, sb.bytes) == (3, 60)
        assert sa.throughput == pytest.approx(sb.throughput)

    def test_begin_ops_inflight(self):
        from repro.core.stats import ChannelStats

        clk = VirtualClock()
        st = ChannelStats("x", clk)
        st.begin_ops(5)
        assert st.collect().inflight == 5
        st.record_batch(5, 100)
        assert st.collect().inflight == 0
