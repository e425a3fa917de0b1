"""The readers of the executor's spans and of device work by phase, on
synthetic run records: ``held_ms.r80``, ``fetch_us_per_event.backlog``
and ``restructure_device_pct.backlog``.  Each returns ``None`` where the
program wrote nothing for it (a program without these spans or without
the ``op_phases`` table)."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from benchlib import optable, spec  # noqa: E402
from benchlib.devtrace import DeviceTrace  # noqa: E402
from benchlib.record import RunRecord  # noqa: E402

EX = "stream-service-executor"


def record(spans=None, device=None, cell="gs_paper.backlog", committed=4000):
    return RunRecord(
        cell=cell, cfg={}, traffic={}, chips=1, seconds=1.0, t0=10.0,
        t1=11.0, setup_s=1.0, committed=committed, interval=500,
        spans=spans, device=device)


def test_held_ms_is_the_median_hold_per_committed_chunk():
    read = spec.reader("held_ms.r80")
    spans = [
        ("executor.hold", EX, 10.10, 10.111),       # 11 ms
        ("executor.hold", EX, 10.20, 10.203),       # 3 ms
        ("executor.hold", EX, 10.30, 10.313),       # 13 ms
        ("executor.hold", EX, 9.90, 9.95),          # before the part
        ("executor.idle", EX, 10.40, 10.45),        # nothing held
        ("executor.hold", "MainThread", 10.5, 10.6),
    ] + [("chunk.commit", EX, 10.05 + 0.1 * i, 10.06 + 0.1 * i)
         for i in range(4)]
    # four committed chunks, three held: 0, 3, 11, 13 -> median 7 ms
    assert read(record(spans)) == pytest.approx(7.0)
    assert read(record(None)) is None
    assert read(record([("chunk.commit", EX, 10.1, 10.2)])) is None


def test_fetch_us_per_event_reads_the_executor_fetch_spans():
    read = spec.reader("fetch_us_per_event.backlog")
    spans = [("chunk.commit", EX, 10.1, 10.2),
             ("chunk.fetch", EX, 10.1, 10.15),
             ("chunk.fetch", EX, 10.3, 10.31),
             ("chunk.fetch", EX, 11.5, 11.6)]       # after the part
    assert read(record(spans)) == pytest.approx(0.06 / 4000 * 1e6)
    assert read(record(None)) is None
    assert read(record([("chunk.commit", EX, 10.1, 10.2)])) is None


def write_spans(out, cell, tables):
    os.makedirs(os.path.join(out, cell))
    events = [dict(name="trace_epoch", ph="M", ts=0, pid=1, tid=0,
                   args=dict(name="CLOCK_MONOTONIC", epoch_ns=1))]
    events += [dict(name="op_phases", ph="M", ts=0, pid=1, tid=0,
                    args=dict(name=p, phases=ph, containers=c))
               for p, (ph, c) in tables.items()]
    with open(os.path.join(out, cell, "spans.json"), "w") as f:
        json.dump(events, f)


def device():
    dev = "/device:TPU:0"
    modules = {dev: [("jit_tstream_chunk(77)", 10.10, 10.20),
                     ("jit_tstream_outputs(78)", 10.20, 10.21),
                     ("jit_tstream_chunk(77)", 10.30, 10.40)]}
    ops = {dev: [
        ("%while.2 = (s32[]) while(%t), body=%b", 10.11, 10.19),
        ("%fusion.1 = f32[8] fusion(%p), calls=%f1", 10.10, 10.11),   # r
        ("%fusion.2 = f32[8] fusion(%p), calls=%f2", 10.11, 10.15),   # e
        ("%copy.3 = f32[8] copy(%p)", 10.15, 10.16),                  # c
        ("%fusion.9 = f32[8] fusion(%p), calls=%f9", 10.16, 10.17),   # ?
        ("%fusion.1 = f32[8] fusion(%p), calls=%f1", 10.20, 10.21),   # out
        ("%fusion.1 = f32[8] fusion(%p), calls=%f1", 10.30, 10.33),   # r
        ("%fusion.2 = f32[8] fusion(%p), calls=%f2", 10.33, 10.40),   # e
    ]}
    return DeviceTrace(ops, modules, 0.0, [])


TABLE = {"jit_tstream_chunk": (
    {"fusion.1": "restructure", "fusion.2": "evaluate", "copy.3": "commit"},
    ["while.2"])}


def test_restructure_device_pct_reads_the_programs_table(tmp_path,
                                                         monkeypatch):
    read = spec.reader("restructure_device_pct.backlog")
    out = str(tmp_path / "out")
    monkeypatch.setattr(optable, "OUT", out)
    rec = record(device=device())
    assert read(rec) is None            # no span file
    write_spans(out, rec.cell, {})
    assert read(rec) is None            # a span file without the table
    os.remove(os.path.join(out, rec.cell, "spans.json"))
    os.rmdir(os.path.join(out, rec.cell))
    write_spans(out, rec.cell, TABLE)
    # chunk executions: restructure 0.01 + 0.03, evaluate 0.04 + 0.07,
    # commit 0.01, an op missing from the table 0.01; the while's 0.08
    # is its body's, and the output program's op is not the chunk's
    by_phase = optable.phase_seconds(rec, optable.op_phases(rec.cell))
    assert by_phase == pytest.approx(dict(
        restructure=0.04, evaluate=0.11, commit=0.01, unknown=0.01))
    assert read(rec) == pytest.approx(0.04 / 0.17 * 100)
    assert read(record(device=None)) is None


def test_op_phases_survive_a_span_file_cut_short(tmp_path):
    out = str(tmp_path)
    write_spans(out, "c", TABLE)
    path = os.path.join(out, "c", "spans.json")
    with open(path) as f:
        events = json.load(f)
    with open(path, "w") as f:           # the program's one-per-line form
        f.write("[\n" + ",\n".join(json.dumps(e) for e in events)
                + ',\n{"name": "chunk.co')
    tables = optable.op_phases("c", out=out)
    assert tables["jit_tstream_chunk"][0] == TABLE["jit_tstream_chunk"][0]
    assert optable.op_name("%fusion.13 = pred[4] fusion(%a)") == "fusion.13"
    assert optable.program_name("jit_tstream_chunk(123)") == \
        "jit_tstream_chunk"
