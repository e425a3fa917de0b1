"""Unified telemetry plane (DESIGN.md §2.11).

Contracts pinned here:

1. **Replay safety**: a tracing-enabled service run is bitwise identical
   to the tracing-off run — final state and every per-interval output —
   including crash -> restore -> replay with tracing on both sides.
   (The 8-device sharded cases live in tests/telemetry_worker.py.)
2. **Deterministic histograms**: log-bucket assignment is a pure
   function of the geometry; merge is exact (integer bucket counts +
   integer-nanosecond totals), associative, and conserves count/total.
3. **Advisory-only timing**: with snapshots on, ``allow_timing`` hints
   are recorded and logged but the applied plan never moves on timing
   evidence.
4. **Schema/trace validity**: the Perfetto writer emits a parseable
   Chrome-trace array (tolerating a missing ``]`` after a crash) that
   covers every pipeline stage; ``stats_view`` renders the legacy stats
   dict from a registry snapshot; ``StreamService.stats`` is
   schema-valid before any run.
"""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.core.intervals import (IntervalAssembler, ReplaySource,
                                  WatermarkPolicy)
from repro.core.scheduler import DualModeEngine, EngineConfig
from repro.runtime.controller import ControllerConfig
from repro.runtime.service import ServiceConfig, StreamService
from repro.runtime.telemetry import (PIPELINE_STAGES, Histogram, Telemetry,
                                     TelemetryConfig, TraceWriter,
                                     counter_value, empty_stats,
                                     histogram_from, stage_summary,
                                     stats_view, validate_trace)


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------
def test_histogram_bucketing_deterministic():
    a, b = Histogram(), Histogram()
    vals = [1e-7, 1e-6, 3.7e-4, 0.2, 5.0, 1e9]   # under lo .. overflow
    a.observe_many(vals)
    for v in vals:
        b.observe(v)
    np.testing.assert_array_equal(a.counts, b.counts)
    assert a.count == b.count == len(vals)
    assert a.total_ns == b.total_ns
    assert a.counts[0] >= 2            # <= lo lands in bucket 0
    assert a.counts[-1] == 1           # overflow bucket holds 1e9


def test_histogram_merge_exact_and_associative():
    rng = np.random.default_rng(7)
    parts = [rng.uniform(1e-6, 10.0, size=n) for n in (13, 57, 220)]
    whole = Histogram()
    whole.observe_many(np.concatenate(parts))

    def hist(v):
        h = Histogram()
        h.observe_many(v)
        return h

    # (a + b) + c == a + (b + c) == whole, bit-for-bit
    left = hist(parts[0]).merge(hist(parts[1])).merge(hist(parts[2]))
    right = hist(parts[0]).merge(hist(parts[1]).merge(hist(parts[2])))
    for m in (left, right):
        np.testing.assert_array_equal(m.counts, whole.counts)
        assert m.count == whole.count
        assert m.total_ns == whole.total_ns      # integer-exact, no float drift
        assert m.vmin == whole.vmin and m.vmax == whole.vmax


def test_histogram_geometry_mismatch_refused():
    with pytest.raises(AssertionError, match="geometry mismatch"):
        Histogram().merge(Histogram(lo=1e-3))


def test_histogram_percentile_within_observed_range():
    h = Histogram()
    h.observe_many([0.001, 0.002, 0.010, 0.500])
    for q in (0, 50, 99, 100):
        assert 0.001 <= h.percentile(q) <= 0.500
    assert np.isnan(Histogram().percentile(50))


def test_histogram_roundtrip():
    h = Histogram()
    h.observe_many([1e-5, 0.3, 7.0])
    r = Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    np.testing.assert_array_equal(r.counts, h.counts)
    assert (r.count, r.total_ns, r.vmin, r.vmax) == \
        (h.count, h.total_ns, h.vmin, h.vmax)


def test_hypothesis_merge_conservation_and_assembler_ledger():
    """Property suite: histogram merge conserves count/total under any
    split, and the assembler's published ledger satisfies the
    conservation law for any arrival pattern."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.floats(min_value=1e-9, max_value=1e4),
                        min_size=0, max_size=80),
               st.integers(min_value=0, max_value=80))
    @hyp.settings(max_examples=50, deadline=None)
    def check_merge(vals, cut):
        cut = min(cut, len(vals))
        whole, a, b = Histogram(), Histogram(), Histogram()
        whole.observe_many(vals)
        a.observe_many(vals[:cut])
        b.observe_many(vals[cut:])
        m = a.merge(b)
        np.testing.assert_array_equal(m.counts, whole.counts)
        assert m.count == whole.count and m.total_ns == whole.total_ns

    @hyp.given(st.lists(st.lists(st.integers(min_value=0, max_value=200),
                                 min_size=1, max_size=20),
                        min_size=1, max_size=12),
               st.integers(min_value=0, max_value=8),
               st.sampled_from(["reroute", "drop"]))
    @hyp.settings(max_examples=50, deadline=None)
    def check_ledger(batches, lateness, late):
        asm = IntervalAssembler(4, WatermarkPolicy(
            allowed_lateness=lateness, late=late))
        for times in batches:
            t = np.asarray(times, np.int64)
            asm.push({"x": np.arange(t.size)}, t)
            asm.pop_ready()
        assert asm.conservation_ok(), asm.ledger
        tele = Telemetry()
        asm.publish(tele)
        snap = tele.snapshot()
        led = asm.ledger
        for k, v in led.items():
            assert counter_value(snap, f"assembly.{k}") == v
        assert led["arrived"] == (led["assembled"] + led["dropped"]
                                  + led["pending"])

    check_merge()
    check_ledger()


# ---------------------------------------------------------------------------
# registry: events, merge, stats view
# ---------------------------------------------------------------------------
def test_event_rate_limit(caplog):
    tele = Telemetry()
    logger = logging.getLogger("repro.test.telemetry")
    with caplog.at_level(logging.WARNING, logger=logger.name):
        for _ in range(5):
            tele.event("dropped", "dropped %d", 3, logger=logger)
    assert sum("dropped 3" in r.message for r in caplog.records) == 1
    ev = [e for e in tele.snapshot()["events"] if e["name"] == "dropped"]
    assert ev[0]["count"] == 5 and ev[0]["emitted"] == 1


def test_device_peaks_resolve_reported_kinds():
    from repro.runtime.telemetry import DEVICE_PEAKS, device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert v5e == DEVICE_PEAKS["tpu v5 lite"]
    assert (v5e["peak_flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    assert device_peaks("cpu") == DEVICE_PEAKS["cpu"]
    assert device_peaks("TPU v5 lite", override=dict(
        hbm_bw=1.0, link_bw=None))["hbm_bw"] == 1.0


@pytest.mark.parametrize("kind", ["TPU v5e", "TPU v7x", "gpu", ""])
def test_device_peaks_unknown_kind_raises(kind):
    from repro.runtime.telemetry import device_peaks
    with pytest.raises(KeyError):
        device_peaks(kind)


def test_registry_merge():
    a, b = Telemetry(), Telemetry()
    a.count("n", 2, kind="x")
    b.count("n", 3, kind="x")
    a.observe("lat", 0.5)
    b.observe("lat", 0.25)
    a.gauge("g", 1.0)
    b.gauge("g", 9.0)
    b.record("r", step=4)
    a.merge(b)
    snap = a.snapshot()
    assert counter_value(snap, "n", kind="x") == 5
    assert histogram_from(snap, "lat").count == 2
    assert [g["value"] for g in snap["gauges"] if g["name"] == "g"] == [9.0]
    assert snap["records"]["r"] == [dict(step=4)]


def test_empty_stats_schema_valid():
    s = empty_stats()
    assert s["arrived"] == 0 and not s["crashed"]
    assert s["drops"] == dict(watermark=0, admission=0, exchange=0)
    assert s["assembly"]["arrived"] == 0
    assert s["source"]["pulls"] == 0
    assert s["snapshots"] == [] and s["chunks"] == []


def test_service_stats_before_any_run():
    """Regression: ``service.stats`` used to be None before the first
    run — every consumer needed a guard.  Now it is the schema-valid
    zero record."""
    app = ALL_APPS["gs"]
    svc = StreamService(
        DualModeEngine(app, app.make_store(), EngineConfig()),
        ServiceConfig(punct_interval=16))
    assert svc.stats["drops"]["watermark"] == 0
    assert svc.stats["crashed"] is False
    assert svc.stats == empty_stats()


# ---------------------------------------------------------------------------
# trace writer / validator
# ---------------------------------------------------------------------------
def test_trace_writer_and_validator(tmp_path):
    path = str(tmp_path / "t.json")
    w = TraceWriter(path)
    w.emit(dict(name="chunk.execute", ph="X", ts=1, dur=5, pid=1, tid=1,
                cat="pipeline"))
    w.emit(dict(name="mark", ph="i", ts=2, pid=1, tid=1))
    w.close()
    ok, why, info = validate_trace(path,
                                   require_stages=["chunk.execute"])
    assert ok, why
    assert info["n_events"] == 2


def test_validator_tolerates_truncated_trace(tmp_path):
    """A crashed writer never gets to append the closing ``]`` — the
    validator (and Perfetto) must still parse the array."""
    path = str(tmp_path / "t.json")
    w = TraceWriter(path)
    w.emit(dict(name="source.pull", ph="X", ts=0, dur=1, pid=1, tid=1,
                cat="pipeline"))
    w.flush()            # no close(): simulated crash
    ok, why, info = validate_trace(path, require_stages=["source.pull"])
    assert ok, why


def test_validator_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('[{"ph": "X", "ts": -4}')
    ok, why, _ = validate_trace(str(bad))
    assert not ok


# ---------------------------------------------------------------------------
# replay safety on the live service (single device)
# ---------------------------------------------------------------------------
def _run_service(app, tcfg, *, n_events=80, cfg_kw=None, **run_kw):
    src = ReplaySource(app.gen_events, n_events, seed=11,
                       arrival_batch=13, jitter=5)
    store = app.make_store()
    eng = DualModeEngine(app, store, EngineConfig(scheme="tstream"))
    svc = StreamService(eng, ServiceConfig(
        punct_interval=16, chunk_intervals=2,
        watermark=WatermarkPolicy(allowed_lateness=5),
        telemetry=tcfg, **(cfg_kw or {})))
    return svc, svc.run(src, **run_kw)


def test_tracing_bitwise_identical_single_device(tmp_path):
    app = ALL_APPS["gs"]
    _, ref = _run_service(app, None)
    trace = str(tmp_path / "trace.json")
    _, rec = _run_service(app, TelemetryConfig(trace_path=trace))
    np.testing.assert_array_equal(rec.final_values, ref.final_values)
    assert len(rec.outputs) == len(ref.outputs)
    for a, b in zip(rec.outputs, ref.outputs):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))
    # stats agree except wall-clock chunk latencies
    for k in ref.stats:
        if k != "chunks":
            assert rec.stats[k] == ref.stats[k], k
    want = [s for s in PIPELINE_STAGES if s != "snapshot.publish"]
    ok, why, info = validate_trace(trace, require_stages=want)
    assert ok, why
    assert stage_summary(trace)          # non-empty per-stage table
    # the registry carries the span histograms without touching stats
    snap = rec.telemetry.snapshot()
    assert histogram_from(snap, "span.chunk.execute").count > 0
    assert stats_view(snap) == rec.stats


def test_traced_crash_restore_replay_bitwise(tmp_path):
    app = ALL_APPS["gs"]
    _, ref = _run_service(app, None)
    ck = str(tmp_path / "ckpt")
    kw = dict(snapshot_every=2, ckpt_dir=ck)
    crash_trace = str(tmp_path / "crash.json")
    svc = StreamService(
        DualModeEngine(app, app.make_store(),
                       EngineConfig(scheme="tstream")),
        ServiceConfig(punct_interval=16, chunk_intervals=2,
                      watermark=WatermarkPolicy(allowed_lateness=5),
                      telemetry=TelemetryConfig(trace_path=crash_trace),
                      **kw))
    src = lambda: ReplaySource(app.gen_events, 80, seed=11,
                               arrival_batch=13, jitter=5)
    with pytest.raises(RuntimeError):
        svc.run(src(), crash_after_interval=3)
    assert svc.last_run.snapshots
    # crashed run's trace still parses and carries the snapshot spans
    ok, why, _ = validate_trace(crash_trace,
                                require_stages=["snapshot.publish"])
    assert ok, why
    resume_trace = str(tmp_path / "resume.json")
    rec = StreamService(
        svc.engine, ServiceConfig(
            punct_interval=16, chunk_intervals=2,
            watermark=WatermarkPolicy(allowed_lateness=5),
            telemetry=TelemetryConfig(trace_path=resume_trace),
            **kw)).resume(src())
    snap = rec.stats["replayed"] // 16
    np.testing.assert_array_equal(rec.final_values, ref.final_values)
    assert len(rec.outputs) == len(ref.outputs[snap:])
    for a, b in zip(rec.outputs, ref.outputs[snap:]):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))
    ok, why, _ = validate_trace(resume_trace, require_stages=[
        "chunk.dispatch", "chunk.execute", "chunk.commit"])
    assert ok, why


def test_advisory_timing_recorded_never_applied(tmp_path):
    """With snapshots on, ``allow_timing=True`` becomes advisory: the
    grow-on-low-latency rule fires as a recorded hint, the applied plan
    never moves, and the run still matches the untraced reference."""
    app = ALL_APPS["gs"]
    ctl = ControllerConfig(window=2, sustain=1, cooldown=1,
                           degrade_scheme="", chunk_ladder=(2, 4),
                           backlog_grow=1e9,      # backlog rule can't fire
                           allow_timing=True, grow_lat_s=1e9)
    kw = dict(cfg_kw=dict(controller=ctl, snapshot_every=4,
                          ckpt_dir=str(tmp_path / "ck")), n_events=160)
    _, ref = _run_service(app, None, **kw)
    assert ref.stats["controller"]["plan"]["chunk"] == 2, \
        "timing grow leaked into the applied plan"
    assert not any(d["knob"] == "chunk" for d in ref.decisions)
    hints = ref.stats["controller"].get("advisory", [])
    assert hints, "advisory channel recorded no hints"
    assert all(h["advisory"] for h in hints)
    assert any(h["knob"] == "chunk" and h["reason"] == "amortize-dispatch"
               for h in hints)
    # hints are not decisions: the decision trace stays empty and the
    # snapshot meta (replayed plan) is unaffected
    assert ref.stats["controller"]["decisions"] == []


# ---------------------------------------------------------------------------
# sharded replay safety (subprocess forces 8 host devices)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def telemetry_worker_verdicts():
    worker = os.path.join(os.path.dirname(__file__), "telemetry_worker.py")
    proc = subprocess.run([sys.executable, worker], capture_output=True,
                          text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["gs/traced_identical",
                                  "gs/traced_crash_resume"])
def test_sharded_telemetry_replay_safety(telemetry_worker_verdicts, case):
    v = telemetry_worker_verdicts[case]
    assert v["ok"], f"{case}: {v.get('why')}"
