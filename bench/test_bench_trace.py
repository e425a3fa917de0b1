"""The trace readers on a small window recorded on a TPU v5e chip.

``bench/testdata/gs_window.*`` is a 0.4-second traced window of
``gs_paper.backlog`` (profiler trace and the service's span trace,
gzipped, and the host-clock stamps the harness took), recorded on a TPU
v5e with the whole window traced.  The readers'
numbers are checked against independent reckonings from the same files.
"""
import gzip
import json
import os
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(BENCH, "testdata")
sys.path.insert(0, BENCH)

from benchlib import spec  # noqa: E402
from benchlib.devtrace import DeviceTrace  # noqa: E402
from benchlib.record import RunRecord  # noqa: E402


def gunzip(name, tmp):
    out = tmp / name
    with gzip.open(os.path.join(DATA, name + ".gz"), "rb") as f, \
            open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(out)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("trace")
    with open(os.path.join(DATA, "gs_window.meta.json")) as f:
        meta = json.load(f)
    trace = DeviceTrace.load(gunzip("gs_window.xplane.pb", tmp),
                             meta["t_mark"])
    import run
    spans, half = run.read_spans(gunzip("gs_window.spans.json", tmp),
                                 np.asarray(meta["commit_s"]))
    meta["half"] = half
    cell = spec.Cell(spec.load_spec(), "gs_paper.backlog")
    commit_s = np.asarray(meta["commit_s"])
    committed = int(np.sum(commit_s < meta["t1"])) * meta["interval"]
    rec = RunRecord(
        cell=cell.name, cfg=cell.cfg, traffic=cell.traffic, chips=1,
        seconds=meta["seconds"], t0=meta["t0"], t1=meta["t1"], setup_s=1.0,
        committed=committed, interval=meta["interval"], spans=spans,
        device=trace, peaks=spec.peaks(meta["device_kind"]),
        work=cell.module("work"))
    return meta, trace, rec


def timeline_busy(rows, t0, t1, step=1e-6):
    """Busy seconds by marking 1-us bins: an independent union."""
    bins = np.zeros(int(np.ceil((t1 - t0) / step)) + 1, bool)
    for _, s, e in rows:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            bins[int((s - t0) / step):int(np.ceil((e - t0) / step))] = True
    return bins.sum() * step


def test_trace_has_the_chip_and_the_chunk_program(recorded):
    meta, trace, rec = recorded
    assert trace.devices and all(d.startswith("/device:TPU")
                                 for d in trace.devices)
    mods = trace.top_modules(rec.t0, rec.t1)
    # the chunk program takes nearly all of the device's program time
    assert mods and mods[0][1] > 0.9 * sum(v for _, v in mods), mods
    ops = trace.ops[trace.devices[0]]
    inside = [r for r in ops if rec.t0 <= r[1] < rec.t1]
    assert inside, "the device ran nothing inside the window"


def test_spans_are_placed_on_the_host_clock(recorded):
    meta, trace, rec = recorded
    commits = [(s, e) for n, _, s, e in rec.spans if n == "chunk.commit"]
    assert commits
    stamps = np.unique(meta["commit_s"])
    half = meta["half"]
    assert 0 <= half < 3e-3
    # every chunk's commit stamp lies inside its commit span, to within
    # the placement's half width
    for (s, e), t in zip(commits, stamps):
        assert s - half <= t <= e + half
    # the offset found from the stamps is the tracer's own start, which
    # the recording kept apart
    first = min(s for _, _, s, _ in rec.spans)
    raw = json.load(gzip.open(os.path.join(DATA,
                                           "gs_window.spans.json.gz")))
    ts0 = min(e["ts"] for e in raw if e.get("ph") == "X") * 1e-6
    assert abs(first - ts0 - meta["epoch_ns"] * 1e-9) <= half + 1e-6


def test_busy_is_the_union_of_op_intervals(recorded):
    meta, trace, rec = recorded
    rows = trace.ops[trace.devices[0]]
    busy = trace.busy_s(rec.t0, rec.t1)
    assert 0 < busy <= rec.seconds
    assert busy == pytest.approx(timeline_busy(rows, rec.t0, rec.t1),
                                 rel=0.02, abs=2e-5)
    gaps = trace.idle_gaps(rec.t0, rec.t1, k=10 ** 6)
    assert sum(e - s for s, e in gaps) == pytest.approx(
        rec.seconds - busy, rel=1e-6, abs=1e-9)


def read(name, rec):
    return spec.reader(name)(rec)


def test_per_layer_readers(recorded):
    meta, trace, rec = recorded
    assert rec.committed > 0
    idle = read("device_idle_pct.backlog", rec)
    assert 0.0 <= idle < 100.0
    assert idle == pytest.approx(
        (1 - trace.busy_s(rec.t0, rec.t1) / rec.seconds) * 100)
    dev = read("chunk_device_us_per_event.backlog", rec)
    chunk_s = sum(e - s for n, s, e in trace.clip(
        trace.modules[trace.devices[0]], rec.t0, rec.t1))
    assert dev == pytest.approx(chunk_s / rec.committed * 1e6)
    roof = read("chunk_roofline_pct.backlog", rec)
    least = rec.committed * rec.work.event_bytes(rec.cfg) / 819e9
    assert roof == pytest.approx(least / chunk_s * 100)
    assert 0 < roof < 100
    main = [(n, s, e) for n, th, s, e in rec.spans
            if th == "MainThread" and rec.t0 <= s < rec.t1]
    feed = (sum(e - s for n, s, e in main
                if n in ("admission", "assembly", "chunk.submit"))
            - sum(e - s for n, s, e in main if n == "source.pull"))
    assert read("feed_us_per_event.backlog", rec) == pytest.approx(
        feed / rec.committed * 1e6)
    assert read("commit_us_per_event.backlog", rec) > 0
    assert read("chunk_exec_ms.r80", rec) > 0
    # one chip: no exchange to read
    assert read("collective_us_per_event.backlog", rec) is None


def test_readers_without_a_trace_return_nothing(recorded):
    meta, trace, rec = recorded
    import dataclasses
    bare = dataclasses.replace(rec, spans=None, device=None)
    for m in spec.load_spec()["per_layer"]:
        assert read(m["name"], bare) is None, m["name"]
