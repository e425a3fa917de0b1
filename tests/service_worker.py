"""Streaming-service worker (subprocess: forces 8 host devices).

Sharded cases of the service contracts (DESIGN.md §2.6), reported as
JSON verdicts for tests/test_service_sharded.py:

* the K-chunked service over the sharded fused driver is bit-identical
  to the monolithic sharded ``run_stream`` AND to the single-device
  fused driver on the same in-order events;
* crash -> restore -> replay on the sharded driver reproduces the
  uninterrupted run bitwise (final state + every per-interval output);
* per-chunk exchange statistics aggregate into the service's merged
  accounting record.
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

from repro.apps import ALL_APPS                                # noqa: E402
from repro.core.intervals import ReplaySource, WatermarkPolicy  # noqa: E402
from repro.core.scheduler import DualModeEngine, EngineConfig   # noqa: E402
from repro.core.sharded_stream import stream_mesh               # noqa: E402
from repro.runtime.service import ServiceConfig, StreamService  # noqa: E402

MESH = stream_mesh((8,), ("dev",))
INTERVAL = 32


def _mk_source(app, n_events=192, seed=5, jitter=4):
    return ReplaySource(app.gen_events, n_events, seed=seed,
                        arrival_batch=19, jitter=jitter)


def _outputs_equal(a_list, b_list):
    for i, (a, b) in enumerate(zip(a_list, b_list)):
        for k in a:
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                return f"output {k} interval {i} differs"
    if len(a_list) != len(b_list):
        return f"interval count {len(a_list)} != {len(b_list)}"
    return None


def check_chunked_sharded_bit_identical(app_name):
    app = ALL_APPS[app_name]
    store = app.make_store()
    jitter = 4
    # single-device fused reference
    eng1 = DualModeEngine(app, store, EngineConfig())
    outs_1, vals_1 = eng1.run_stream(
        store.values, _mk_source(app).in_order_events, INTERVAL, fused=True)
    # monolithic sharded
    eng8 = DualModeEngine(app, store, EngineConfig(), mesh=MESH,
                          exchange_slack=8.0)
    outs_m, vals_m = eng8.run_stream(
        store.values, _mk_source(app).in_order_events, INTERVAL)
    # chunked service over the sharded driver
    rec = StreamService(eng8, ServiceConfig(
        punct_interval=INTERVAL, chunk_intervals=2,
        watermark=WatermarkPolicy(allowed_lateness=jitter))).run(
            _mk_source(app))
    for tag, outs, vals in (("1dev", outs_1, vals_1),
                            ("sharded-monolithic", outs_m, vals_m)):
        if not np.array_equal(rec.final_values, np.asarray(vals)):
            return dict(ok=False, why=f"final state differs vs {tag}")
        why = _outputs_equal(rec.outputs, outs)
        if why:
            return dict(ok=False, why=f"vs {tag}: {why}")
    if rec.stats.get("exchange") is None:
        return dict(ok=False, why="exchange stats missing from record")
    if rec.stats["exchange"]["shipped"] <= 0:
        return dict(ok=False, why="exchange shipped not aggregated")
    return dict(ok=True, shipped=rec.stats["exchange"]["shipped"],
                dropped=rec.stats["drops"]["exchange"])


def check_sharded_crash_resume(app_name):
    app = ALL_APPS[app_name]
    store = app.make_store()
    eng = DualModeEngine(app, store, EngineConfig(), mesh=MESH,
                         exchange_slack=8.0)
    with tempfile.TemporaryDirectory() as d:
        cfg = ServiceConfig(
            punct_interval=INTERVAL, chunk_intervals=2, snapshot_every=2,
            ckpt_dir=d, watermark=WatermarkPolicy(allowed_lateness=4))
        ref = StreamService(eng, ServiceConfig(
            punct_interval=INTERVAL, chunk_intervals=2,
            watermark=WatermarkPolicy(allowed_lateness=4))).run(
                _mk_source(app))
        svc = StreamService(eng, cfg)
        try:
            svc.run(_mk_source(app), crash_after_interval=3)
            return dict(ok=False, why="injected crash did not fire")
        except RuntimeError:
            pass
        crashed = svc.last_run
        if not crashed.snapshots:
            return dict(ok=False, why="no snapshot before the crash")
        rec = StreamService(eng, cfg).resume(_mk_source(app))
        snap = rec.stats["replayed"] // INTERVAL
        if snap != crashed.snapshots[-1]:
            return dict(ok=False, why=f"resumed from {snap}, "
                        f"snapshot was {crashed.snapshots[-1]}")
        if not np.array_equal(rec.final_values, ref.final_values):
            return dict(ok=False, why="final state differs after recovery")
        why = _outputs_equal(rec.outputs, ref.outputs[snap:])
        if why:
            return dict(ok=False, why=f"post-resume {why}")
        why = _outputs_equal(crashed.outputs,
                             ref.outputs[: len(crashed.outputs)])
        if why:
            return dict(ok=False, why=f"pre-crash {why}")
        return dict(ok=True, resumed_from=snap)


def main():
    out = {}

    def run(name, fn, *a):
        try:
            out[name] = fn(*a)
        except Exception as e:  # pragma: no cover - surfaced via verdict
            traceback.print_exc(file=sys.stderr)
            out[name] = dict(ok=False, why=f"{type(e).__name__}: {e}")

    run("gs/chunked", check_chunked_sharded_bit_identical, "gs")
    run("sl/chunked", check_chunked_sharded_bit_identical, "sl")
    run("gs/crash_resume", check_sharded_crash_resume, "gs")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
