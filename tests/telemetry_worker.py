"""Telemetry replay-safety worker (subprocess: forces 8 host devices).

Sharded cases of the §2.11 replay-safety contract, reported as JSON
verdicts for tests/test_telemetry.py:

* a tracing-enabled sharded service run is bitwise identical to the
  tracing-off run (final state + every per-interval output);
* crash -> restore -> replay with tracing on reproduces the untraced
  uninterrupted run bitwise, while the trace validates against the
  pipeline-stage schema (including ``reshard``-free sharded spans).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import sys
import tempfile
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.apps import ALL_APPS                                 # noqa: E402
from repro.core.intervals import ReplaySource, WatermarkPolicy  # noqa: E402
from repro.core.scheduler import DualModeEngine, EngineConfig   # noqa: E402
from repro.core.sharded_stream import stream_mesh               # noqa: E402
from repro.runtime.service import ServiceConfig, StreamService  # noqa: E402
from repro.runtime.telemetry import (PIPELINE_STAGES, TelemetryConfig,
                                     validate_trace)            # noqa: E402

MESH = stream_mesh((8,), ("dev",))
INTERVAL = 32


def _mk_source(app, n_events=192, seed=5, jitter=4):
    return ReplaySource(app.gen_events, n_events, seed=seed,
                        arrival_batch=19, jitter=jitter)


def _outputs_equal(a_list, b_list):
    if len(a_list) != len(b_list):
        return f"interval count {len(a_list)} != {len(b_list)}"
    for i, (a, b) in enumerate(zip(a_list, b_list)):
        for k in a:
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                return f"output {k} interval {i} differs"
    return None


def check_traced_sharded_identical(app_name):
    app = ALL_APPS[app_name]
    store = app.make_store()
    eng = DualModeEngine(app, store, EngineConfig(), mesh=MESH,
                         exchange_slack=8.0)

    def run(tcfg):
        return StreamService(eng, ServiceConfig(
            punct_interval=INTERVAL, chunk_intervals=2,
            watermark=WatermarkPolicy(allowed_lateness=4),
            telemetry=tcfg)).run(_mk_source(app))

    ref = run(None)
    with tempfile.TemporaryDirectory() as tmp:
        trace = os.path.join(tmp, "trace.json")
        rec = run(TelemetryConfig(trace_path=trace))
        if not np.array_equal(rec.final_values, ref.final_values):
            return dict(ok=False, why="final state differs with tracing on")
        why = _outputs_equal(rec.outputs, ref.outputs)
        if why:
            return dict(ok=False, why=f"traced vs untraced: {why}")
        want = [s for s in PIPELINE_STAGES if s != "snapshot.publish"]
        ok, vwhy, info = validate_trace(trace, require_stages=want)
        if not ok:
            return dict(ok=False, why=f"invalid trace: {vwhy}")
    if rec.stats != ref.stats:
        diff = [k for k in ref.stats if rec.stats.get(k) != ref.stats[k]]
        if diff != ["chunks"]:          # lat_s wall-clock only
            return dict(ok=False, why=f"stats diverge beyond timing: {diff}")
    if rec.stats.get("exchange") is None:
        return dict(ok=False, why="exchange stats missing from traced view")
    return dict(ok=True, n_events=info["n_events"])


def check_traced_crash_resume(app_name):
    app = ALL_APPS[app_name]
    store = app.make_store()
    eng = DualModeEngine(app, store, EngineConfig(), mesh=MESH,
                         exchange_slack=8.0)
    ref = StreamService(eng, ServiceConfig(
        punct_interval=INTERVAL, chunk_intervals=2,
        watermark=WatermarkPolicy(allowed_lateness=4))).run(_mk_source(app))
    with tempfile.TemporaryDirectory() as d:
        trace_a = os.path.join(d, "crash.json")
        trace_b = os.path.join(d, "resume.json")
        cfg = lambda t: ServiceConfig(
            punct_interval=INTERVAL, chunk_intervals=2, snapshot_every=2,
            ckpt_dir=os.path.join(d, "ckpt"),
            watermark=WatermarkPolicy(allowed_lateness=4),
            telemetry=TelemetryConfig(trace_path=t))
        svc = StreamService(eng, cfg(trace_a))
        try:
            svc.run(_mk_source(app), crash_after_interval=3)
            return dict(ok=False, why="injected crash did not fire")
        except RuntimeError:
            pass
        crashed = svc.last_run
        if not crashed.snapshots:
            return dict(ok=False, why="no snapshot before the crash")
        rec = StreamService(eng, cfg(trace_b)).resume(_mk_source(app))
        snap = rec.stats["replayed"] // INTERVAL
        if not np.array_equal(rec.final_values, ref.final_values):
            return dict(ok=False,
                        why="final state differs after traced recovery")
        why = _outputs_equal(rec.outputs, ref.outputs[snap:])
        if why:
            return dict(ok=False, why=f"post-resume {why}")
        # the crashed run's trace must close cleanly and carry snapshot
        # spans; the resume trace covers the replay pipeline
        ok, vwhy, _ = validate_trace(trace_a,
                                     require_stages=["snapshot.publish"])
        if not ok:
            return dict(ok=False, why=f"crash trace invalid: {vwhy}")
        ok, vwhy, _ = validate_trace(trace_b, require_stages=[
            "chunk.dispatch", "chunk.execute", "chunk.commit"])
        if not ok:
            return dict(ok=False, why=f"resume trace invalid: {vwhy}")
        return dict(ok=True, resumed_from=snap)


def main():
    out = {}

    def run(name, fn, *a):
        try:
            out[name] = fn(*a)
        except Exception as e:  # pragma: no cover - surfaced via verdict
            traceback.print_exc(file=sys.stderr)
            out[name] = dict(ok=False, why=f"{type(e).__name__}: {e}")

    run("gs/traced_identical", check_traced_sharded_identical, "gs")
    run("gs/traced_crash_resume", check_traced_crash_resume, "gs")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
