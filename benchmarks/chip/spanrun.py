#!/usr/bin/env python3
"""Run one cell of the chip benchmark once with the program's own spans on,
and print what they show beside the cell's result.

    python3 benchmarks/chip/spanrun.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--out <file>]

The run is ``run.py``'s (the same set-up, window and checks) with
``repro.telemetry.spans`` enabled before the job starts. With ``--trace 0``
the result holds the end-to-end metrics, so that runs of this and of
``run.py`` on the same seeds give the cost of recording spans. With
``--trace 1`` the profiler's trace is also reduced by ``pspans.py``, over the
same traced part of the window as ``xtrace.py``.

Besides the spans, three things the jobs keep to themselves are observed from
outside them: the serving engine's per-request times (as it publishes them),
the training stage's channel statistics, and the path of the trace file. It
is not a benchmark run: the benchmark's command is ``run.py``, which runs
with spans off.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from statistics import median
from typing import Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmarks.chip import run as run_mod  # noqa: E402  (first: it reads the start time)
from benchmarks.chip import harness, pspans, spec as spec_mod, xtrace  # noqa: E402


class Observers:
    """What the program publishes during a run and the jobs do not keep."""

    def __init__(self) -> None:
        #: per served request: (tenant, submitted, prefill_start, first_token, done)
        self.requests: List[tuple] = []
        self.stages: list = []
        self.trace: Optional[dict] = None
        self._undo: list = []

    def _patch(self, owner, name: str, value) -> None:
        self._undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    def install(self) -> None:
        import repro.launch.train as train_mod
        from repro.serve.engine import ServeEngine

        publish = ServeEngine._publish
        build_io_stage = train_mod.build_io_stage
        load = xtrace.load

        def observed_publish(engine, p, n_tokens):
            t = p.times
            self.requests.append((p.tenant, t.submitted, t.prefill_start, t.first_token, t.done))
            return publish(engine, p, n_tokens)

        def observed_build_io_stage(*args, **kwargs):
            stage, cp = build_io_stage(*args, **kwargs)
            self.stages.append(stage)
            return stage, cp

        def observed_load(path):
            trace = load(path)
            self.trace = pspans.reduce(trace, pspans.load(path))
            return trace

        self._patch(ServeEngine, "_publish", observed_publish)
        self._patch(train_mod, "build_io_stage", observed_build_io_stage)
        self._patch(xtrace, "load", observed_load)

    def remove(self) -> None:
        while self._undo:
            owner, name, value = self._undo.pop()
            setattr(owner, name, value)


def span_readings(records, window_ns, obs: dict) -> Dict[str, Optional[float]]:
    """The readings of the program's spans over the run's window."""
    lo, hi = window_ns
    inside = [r for r in records if r.t1_ns > lo and r.t0_ns < hi]

    def durations(name: str) -> List[float]:
        return [r.duration_ns * 1e-9 for r in inside if r.name == name]

    def mean_ms(name: str) -> Optional[float]:
        d = durations(name)
        return sum(d) / len(d) * 1e3 if d else None

    tick_s = sum(min(r.t1_ns, hi) - max(r.t0_ns, lo) for r in inside if r.name == "paio.control.tick") * 1e-9
    out = {
        "control_share": 100.0 * tick_s / ((hi - lo) * 1e-9),
        "control_ticks": len(durations("paio.control.tick")),
        "control_tick_ms_mean": mean_ms("paio.control.tick"),
    }
    if obs.get("kind") == "train":
        out.update(
            ckpt_snapshot_ms=mean_ms("paio.ckpt.snapshot"),
            ckpt_ready_ms=mean_ms("paio.ckpt.ready"),
            ckpt_d2h_ms=mean_ms("paio.ckpt.d2h"),
            ckpt_write_s_total=sum(durations("paio.ckpt.write")),
            ckpt_commit_ms=mean_ms("paio.ckpt.commit"),
            data_fetch_ms=mean_ms("paio.data.fetch"),
        )
    else:
        out.update(
            serve_decode_ms=mean_ms("paio.serve.decode"),
            serve_prefill_ms=mean_ms("paio.serve.prefill"),
            serve_admit_ms=mean_ms("paio.serve.admit"),
        )
    return out


def table(records) -> Dict[str, Dict[str, float]]:
    """Count, total and self seconds per span name, by thread."""
    from repro.telemetry import spans

    own = spans.self_ns(records)
    out: Dict[str, Dict[str, float]] = {}
    for r in records:
        row = out.setdefault(f"{r.thread}:{r.name}", {"count": 0, "total_s": 0.0, "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += r.duration_ns * 1e-9
        row["self_s"] += own[r.span_id] * 1e-9
    return out


def spanrun(spec: spec_mod.Spec, workload: str, seed: int, seconds: float, trace: bool, require_tpu: bool = True) -> dict:
    from repro.telemetry import spans

    observers = Observers()
    observers.install()
    spans.enable()
    try:
        result = run_mod.run_cell(spec, workload, seed, seconds, trace, require_tpu=require_tpu)
        records = spans.records()
        dropped = spans.dropped()
    finally:
        spans.disable()
        observers.remove()
    obs = result.pop("_obs")
    result.pop("_check_lines")
    t0 = run_mod.T_START + obs["setup_s"]
    window_ns = (int(t0 * 1e9), int((t0 + obs["window_s"]) * 1e9))
    readings = span_readings(records, window_ns, obs)
    if obs.get("kind") == "train":
        saves = len(obs.get("ckpt_stall_s") or [])
        stage = observers.stages[-1] if observers.stages else None
        if stage is not None and saves:
            ckpt = stage.collect().per_channel["ckpt"]
            readings["ckpt_drl_wait_s"] = ckpt.cumulative_wait_seconds / saves
        readings["ckpt_stall_ms"] = 1e3 * sum(obs["ckpt_stall_s"]) / saves if saves else None
        readings["ckpt_durable_s"] = sum(obs["ckpt_durable_s"]) / saves if saves else None
    else:
        served = [r for r in observers.requests if r[0] != "warmup"]
        readings["requests"] = len(served)
        if served:
            readings["queue_wait_ms"] = 1e3 * median(r[2] - r[1] for r in served)
            readings["ttft_ms"] = 1e3 * median(r[3] - r[1] for r in served)
            readings["generate_ms"] = 1e3 * median(r[4] - r[1] for r in served)
    if observers.trace is not None:
        tr = observers.trace
        steps = tr["in_window"].get("paio.serve.decode")
        if steps:
            readings["decode_idle_ms"] = 1e3 * tr["idle_in"]["paio.serve.decode"] / steps
        result["pspans"] = tr
    result["spans"] = readings
    result["span_table"] = table(records)
    result["spans_dropped"] = dropped
    result["observations"] = {k: v for k, v in obs.items() if isinstance(v, (int, float, str)) or v is None}
    result["observations"]["idle_gaps"] = (obs.get("trace") or {}).get("idle_gaps")
    return result


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None, help="also write the result here")
    args = ap.parse_args(argv)
    spec = spec_mod.Spec.load()
    harness.enable_compile_cache()
    try:
        result = spanrun(spec, args.workload, args.seed, args.seconds, bool(args.trace))
    except harness.NoChip as e:
        print(f"spanrun.py: {e}; no result", file=sys.stderr)
        return 2
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    short = {k: v for k, v in result.items() if k not in ("pspans", "span_table")}
    print(json.dumps(short), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
