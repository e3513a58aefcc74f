#!/usr/bin/env python3
"""Records ``pspans_v5e.xplane.pb``, the small trace that ``pspans.py``'s tests
read, on one TPU v5e chip.

    python3 tests/bench_chip/fixtures/record_pspans_trace.py <out.xplane.pb>
    python3 tests/bench_chip/fixtures/record_pspans_trace.py --trim <recorded.xplane.pb> <out.xplane.pb>

Inside one ``window`` span, with the program's spans on and the profiler's
Python tracer off: a drain of two requests of the test-size dense model, cut
to one layer (two prefills, then lockstep decode steps of both), behind a
stage with one channel per tenant, while a control plane ticks every 5 ms on its own thread;
then an asynchronous checkpoint save of a small state made by a jitted call
just before it (the snapshot waits for that call), written on its own thread.
Everything is compiled before the trace starts.

The recorded trace is then cut to what the reductions read (``--trim`` does
only this, on the host, to a trace recorded before): the device's ``XLA
Modules`` (with ``run_id``) and ``XLA Ops`` (each op's text cut to its name);
the host's ``paio.*`` spans (with ``span_id``), the ``window`` span,
``DoEnqueueProgram`` and ``CompleteCallbacks`` (with ``run_id``), and the
runtime's execute events that link a launch to its enqueue (with their flow
ids, ``_p`` and ``_c``), each on the line of the thread that recorded it. Cutting needs TensorFlow's ``xplane``
protocol buffer, which the recording does not.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


KEEP_HOST = (
    "window", "DoEnqueueProgram", "CompleteCallbacks",
    # a launch's flow: the runtime's execute call, and the event around the enqueue that consumes it
    "tpu::System::Execute", "tpu::System::Execute=>IssueSequencedEvent",
)
KEEP_STATS = ("run_id", "span_id", "_p", "_c")


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip import harness, xtrace
    from repro.checkpoint import AsyncCheckpointer, CheckpointManager
    from repro.core import ControlPlane, DifferentiationRule, HousekeepingRule, Stage
    from repro.models import init_params
    from repro.models.model import ArchConfig
    from repro.serve import ServeEngine
    from repro.telemetry import MetricRegistry, spans

    harness.device_info(1)
    with open(os.path.join(HERE, "configs", "tiny-dense.json")) as f:
        cfg = ArchConfig(**{**json.load(f)["arch"], "n_layers": 1})
    params = init_params(cfg, jax.random.PRNGKey(0))
    stage = Stage("serve")
    for t in ("a", "b"):
        stage.hsk_rule(HousekeepingRule(op="create_channel", channel=t))
        stage.dif_rule(DifferentiationRule(channel=t, match={"tenant": t}))
    cp = ControlPlane(loop_interval=0.005)
    cp.register_stage(stage)
    engine = ServeEngine(cfg, params, max_seq=32, stage=stage, drain_concurrency=2, registry=MetricRegistry())
    make_state = jax.jit(lambda x: {"w": x * 2.0, "b": x[0] + 1.0})
    x = jnp.ones((64, 64), jnp.float32)
    ckpt_dir = tempfile.mkdtemp(prefix="pspans_ckpt_")
    ckpt = AsyncCheckpointer(CheckpointManager(ckpt_dir, stage=stage))

    def work(new_tokens: int):
        # two requests, then lockstep decode steps of both: one per token after the first
        for t in ("a", "b"):
            engine.submit(np.full((1, 8), 3, np.int32), max_new_tokens=new_tokens, tenant=t)
        engine.drain()
        ckpt.save(1, make_state(x))
        ckpt.wait()

    work(2)  # compile every shape, and a first save
    cp.start()
    try:
        spans.enable()
        logdir = tempfile.mkdtemp(prefix="pspans_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            work(2)
            time.sleep(0.012)  # a control tick or two after the work
        jax.profiler.stop_trace()
    finally:
        cp.close()
        spans.disable()
    trim(xtrace.find_xplane(logdir), out)
    shutil.rmtree(logdir, ignore_errors=True)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    return 0


def trim(recorded: str, out: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmarks.chip import xtrace

    space = xplane_pb2.XSpace()
    with open(recorded, "rb") as f:
        space.ParseFromString(f.read())
    kept = xplane_pb2.XSpace()
    for plane in space.planes:
        if plane.name.startswith("/device:") and any(line.name == xtrace.OPS_LINE for line in plane.lines):
            keep_line = {xtrace.MODULES_LINE, xtrace.OPS_LINE}.__contains__
            keep_event = lambda name: True  # noqa: E731
        elif plane.name.startswith("/host:") and plane.lines:
            keep_line = lambda name: True  # noqa: E731
            keep_event = lambda name: name.startswith("paio.") or name in KEEP_HOST  # noqa: E731
        else:
            continue
        new = kept.planes.add(id=plane.id, name=plane.name)
        stats = {k for k, m in plane.stat_metadata.items() if m.name in KEEP_STATS}
        for k in stats:
            new.stat_metadata[k].CopyFrom(plane.stat_metadata[k])
        for line in plane.lines:
            events = [e for e in line.events if keep_line(line.name) and keep_event(plane.event_metadata[e.metadata_id].name)]
            if not events:
                continue
            new_line = new.lines.add(
                id=line.id, display_id=line.display_id, name=line.name, timestamp_ns=line.timestamp_ns,
                duration_ps=line.duration_ps,
            )
            for e in events:
                ev = new_line.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps, duration_ps=e.duration_ps)
                for st in e.stats:
                    if st.metadata_id in stats:
                        ev.stats.add().CopyFrom(st)
                if e.metadata_id not in new.event_metadata:
                    md = plane.event_metadata[e.metadata_id]
                    new.event_metadata[e.metadata_id].CopyFrom(
                        xplane_pb2.XEventMetadata(id=md.id, name=md.name.split(" = ", 1)[0])
                    )
    with open(out, "wb") as f:
        f.write(kept.SerializeToString())
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    if sys.argv[1] == "--trim":
        trim(sys.argv[2], sys.argv[3])
    else:
        sys.exit(main(sys.argv[1]))
