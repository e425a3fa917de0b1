"""The served path's tracing (DESIGN.md §2.11): one clock, buffered spans,
the executor's waits, device work by phase, counters.

Contracts pinned here:

1. **One clock**: the span file's first event carries the tracer's
   ``CLOCK_MONOTONIC`` epoch; placed by it, every ``chunk.commit`` span
   encloses the ``commit_s`` stamps of its intervals, with nothing fitted.
2. **Where the executor waits**: ``executor.hold`` (a dispatched chunk is
   uncommitted), ``executor.idle`` (nothing in flight) and ``chunk.fetch``
   (inside ``chunk.commit``) appear in a traced run.
3. **Device work by phase**: the ``op_phases`` table of a compiled chunk
   program covers every op the device runs and names its phases; building
   it compiles nothing.
4. **Counters**: chunk records carry their restructure rung and path,
   ``chunks`` counts by them, ``jax.compiles`` counts compilations.
5. **Crash prefix**: a buffered trace that was never closed, even one cut
   mid-line, still validates.
"""
import json
import re

import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.core.intervals import ReplaySource, WatermarkPolicy
from repro.core.scheduler import DualModeEngine, EngineConfig
from repro.runtime.service import ServiceConfig, StreamService
from repro.runtime.telemetry import (Telemetry, TelemetryConfig,
                                     TraceWriter, Tracer,
                                     compile_watch, counter_value,
                                     op_phase_table, validate_trace)

INTERVAL, K = 16, 2


def _source(app, n_events=160):
    return ReplaySource(app.gen_events, n_events, seed=11,
                        arrival_batch=13, jitter=5)


def _service(eng, trace="", **kw):
    return StreamService(eng, ServiceConfig(
        punct_interval=INTERVAL, chunk_intervals=kw.pop("k", K),
        watermark=WatermarkPolicy(allowed_lateness=5),
        telemetry=TelemetryConfig(trace_path=trace) if trace else None,
        **kw))


def _events(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced_gs(tmp_path_factory):
    """One traced GS run with snapshots (so the executor idles after each
    snapshot drain) and its span file's events."""
    tmp = tmp_path_factory.mktemp("traced")
    app = ALL_APPS["gs"]
    eng = DualModeEngine(app, app.make_store(), EngineConfig())
    trace = str(tmp / "spans.json")
    run = _service(eng, trace, snapshot_every=2 * K,
                   ckpt_dir=str(tmp / "ck")).run(_source(app))
    return eng, run, _events(trace), trace


def test_epoch_is_the_first_event_and_places_commits(traced_gs):
    _, run, events, _ = traced_gs
    first = events[0]
    assert first["ph"] == "M" and first["name"] == "trace_epoch"
    assert first["args"]["name"] == "CLOCK_MONOTONIC"
    epoch_s = first["args"]["epoch_ns"] * 1e-9
    commit_s = {c["interval"]: c["commit_s"] for c in run.commits}
    commits = [e for e in events
               if e["ph"] == "X" and e["name"] == "chunk.commit"]
    assert len(commits) == len(run.commits) // K
    for e in commits:
        start = epoch_s + e["ts"] * 1e-6
        end = start + e["dur"] * 1e-6
        for g in range(e["args"]["g0"], e["args"]["g0"] + e["args"]["k"]):
            # 1 us: the file's ts/dur are microseconds with 3 decimals
            assert start - 1e-6 <= commit_s[g] <= end + 1e-6, (g, e)


def test_executor_waits_and_fetch_nested_in_commit(traced_gs):
    _, run, events, _ = traced_gs
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"executor.hold", "executor.idle", "chunk.fetch"} <= names
    threads = {e["tid"]: e["args"]["name"] for e in events
               if e["ph"] == "M" and e["name"] == "thread_name"}
    commits = {e["args"]["g0"]: e for e in events
               if e["ph"] == "X" and e["name"] == "chunk.commit"}
    fetches = [e for e in events
               if e["ph"] == "X" and e["name"] == "chunk.fetch"]
    assert len(fetches) == len(commits)
    for f in fetches:
        c = commits[f["args"]["g0"]]
        assert f["tid"] == c["tid"]
        assert threads[f["tid"]] == "stream-service-executor"
        assert c["ts"] <= f["ts"]
        assert f["ts"] + f["dur"] <= c["ts"] + c["dur"] + 1e-3
    for e in events:
        if e["ph"] == "X" and e["name"].startswith("executor."):
            assert threads[e["tid"]] == "stream-service-executor"
    # a hold names the chunk it holds, and only a dispatched chunk is held
    holds = [e["args"]["g0"] for e in events
             if e["ph"] == "X" and e["name"] == "executor.hold"]
    assert holds and set(holds) <= set(commits)


def test_dispatch_spans_and_chunk_records_carry_rung_and_path(traced_gs):
    eng, run, events, _ = traced_gs
    route = eng.chunk_route(INTERVAL)
    assert route[0] in ("partition", "packed", "lexsort", "mega")
    assert route[1] == "segscan"
    chunks = run.stats["chunks"]
    assert chunks and all((c["rung"], c["path"]) == route for c in chunks)
    dispatch = [e for e in events
                if e["ph"] == "X" and e["name"] == "chunk.dispatch"]
    assert dispatch and all(
        (e["args"]["rung"], e["args"]["path"]) == route for e in dispatch)
    snap = run.telemetry.snapshot()
    assert counter_value(snap, "chunks", rung=route[0],
                         path=route[1]) == len(dispatch)


@pytest.mark.parametrize("app_name,kw,path", [
    ("gs", {}, "segscan"),
    ("sl", dict(abort_repass=True), "lockstep"),
])
def test_chunk_route_matches_the_program_it_runs(app_name, kw, path):
    """The host-side route agrees with the path the compiled program
    reports (``EngineStats.path``), and the run's records carry it."""
    app = ALL_APPS[app_name]
    eng = DualModeEngine(app, app.make_store(), EngineConfig(**kw))
    run = _service(eng).run(_source(app, 96))
    rung, got = eng.chunk_route(INTERVAL)
    assert got == path
    assert {(c["rung"], c["path"]) for c in run.stats["chunks"]} == {
        (rung, path)}
    ev = app.gen_events(np.random.default_rng(3), K * INTERVAL)
    batched = {k: np.asarray(v).reshape((K, INTERVAL) + np.shape(v)[1:])
               for k, v in ev.items()}
    *_, stats = eng.run_stream_chunk(
        np.array(eng.init_store.values), batched, 0)
    assert stats["engine"].path == path


def _leaf_ops(hlo: str):
    """Independently of the parser: every instruction of the entry
    computation and of the computations a ``while`` runs, less the
    ``while``s, ``conditional``s and ``call``s themselves."""
    blocks = re.split(r"\n(?=\S)", hlo)
    comps = {}
    entry = None
    for b in blocks:
        head = b.split("\n", 1)[0]
        m = re.match(r"(ENTRY )?%?([\w.\-]+) \(", head)
        if m and head.rstrip().endswith("{"):
            comps[m.group(2)] = b
            entry = m.group(2) if m.group(1) else entry
    run = {entry}
    for b in comps.values():
        run |= set(re.findall(r"\b(?:body|condition)=%?([\w.\-]+)", b))
    ops, containers = set(), set()
    for name in run:
        for m in re.finditer(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$",
                             comps[name], re.M):
            if re.search(r"\s(while|conditional|call)\(", m.group(2)):
                containers.add(m.group(1))
            else:
                ops.add(m.group(1))
    return ops, containers


def test_op_phases_cover_every_leaf_op(traced_gs):
    eng, run, events, _ = traced_gs
    tables = [e for e in events if e["ph"] == "M"
              and e["name"] == "op_phases"]
    assert len(tables) == 1
    t = tables[0]["args"]
    assert t["name"] == "jit_tstream_chunk"
    ev = ALL_APPS["gs"].gen_events(np.random.default_rng(3), K * INTERVAL)
    batched = {k: np.asarray(v).reshape((K, INTERVAL) + np.shape(v)[1:])
               for k, v in ev.items()}
    hlo = eng.chunk_lowered_text(eng.init_store.values, batched)
    ops, containers = _leaf_ops(hlo)
    assert containers, "the chunk program scans its intervals in a while"
    assert set(t["phases"]) == ops
    assert set(t["containers"]) == containers
    assert {"restructure", "evaluate", "register", "commit"} <= set(
        t["phases"].values())
    assert op_phase_table(hlo) == dict(program=t["name"],
                                       phases=t["phases"],
                                       containers=t["containers"])


def test_op_phases_name_the_abort_repass_and_exchange():
    """SL's abort repass, and the sharded driver's exchange (one device),
    are phases of their programs."""
    from repro.core.sharded_stream import stream_mesh
    sl = ALL_APPS["sl"]
    eng = DualModeEngine(sl, sl.make_store(), EngineConfig(abort_repass=True))
    ev = sl.gen_events(np.random.default_rng(3), K * INTERVAL)
    batched = {k: np.asarray(v).reshape((K, INTERVAL) + np.shape(v)[1:])
               for k, v in ev.items()}
    t = op_phase_table(eng.chunk_lowered_text(eng.init_store.values,
                                              batched))
    assert "abort_repass" in t["phases"].values()
    gs = ALL_APPS["gs"]
    sh = DualModeEngine(gs, gs.make_store(), EngineConfig(),
                        mesh=stream_mesh((1,), ("dev",)))
    ev = gs.gen_events(np.random.default_rng(3), K * INTERVAL)
    batched = {k: np.asarray(v).reshape((K, INTERVAL) + np.shape(v)[1:])
               for k, v in ev.items()}
    t = op_phase_table(sh.chunk_lowered_text(
        sh.carry_in(gs.make_store().values), batched))
    assert t["program"] == "jit_tstream_chunk_sharded"
    assert {"exchange", "restructure", "evaluate"} <= set(
        t["phases"].values())


def test_op_phases_compile_nothing(tmp_path):
    """The table comes from the executable the run compiled: a traced run
    after a warm one counts no compilation, table included."""
    app = ALL_APPS["gs"]
    eng = DualModeEngine(app, app.make_store(), EngineConfig())
    warm = _service(eng).run(_source(app))
    assert counter_value(warm.telemetry.snapshot(), "jax.compiles") >= 1
    trace = str(tmp_path / "spans.json")
    run = _service(eng, trace).run(_source(app))
    assert counter_value(run.telemetry.snapshot(), "jax.compiles") == 0
    assert any(e["name"] == "op_phases" for e in _events(trace))


def test_compile_counter_counts_a_forced_recompile(tmp_path):
    app = ALL_APPS["gs"]
    eng = DualModeEngine(app, app.make_store(), EngineConfig())
    _service(eng).run(_source(app))
    again = _service(eng).run(_source(app))
    assert counter_value(again.telemetry.snapshot(), "jax.compiles") == 0
    # a new chunk size is a new program shape: it compiles in this run
    trace = str(tmp_path / "spans.json")
    run = _service(eng, trace, k=4).run(_source(app))
    n = counter_value(run.telemetry.snapshot(), "jax.compiles")
    assert n >= 1
    events = _events(trace)
    threads = {e["tid"]: e["args"]["name"] for e in events
               if e["ph"] == "M" and e["name"] == "thread_name"}
    spans = [e for e in events
             if e["ph"] == "X" and e["name"] == "jax.compile"]
    assert len(spans) == n
    assert {threads[e["tid"]] for e in spans} == {"stream-service-executor"}


def test_compile_watch_counts_only_while_open():
    import jax
    import jax.numpy as jnp
    reg = Telemetry()
    x, y = jnp.arange(7.0), jnp.arange(9.0)
    with compile_watch(reg):
        jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
    jax.jit(lambda v: v * 5 - 2)(y).block_until_ready()
    assert reg.counter_value("jax.compiles") == 1


def test_truncated_buffered_trace_validates(tmp_path):
    """A run that dies leaves every drained event: the file validates
    without its closing ``]``, and with its last line cut short."""
    path = str(tmp_path / "t.json")
    reg = Telemetry()
    tr = Tracer(TraceWriter(path), reg, flush_s=1e6)
    for g in range(5):
        with tr.span("chunk.commit", g0=g, k=2):
            pass
    tr.instant("mark")
    tr.flush()                      # what the writer thread does; no close
    ok, why, info = validate_trace(path, require_stages=["chunk.commit"])
    assert ok, why
    n = info["n_events"]
    assert n == 2 + 1 + 5 + 1       # epoch, process, thread, spans, mark
    assert reg.histogram("span.chunk.commit").count == 5
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text[:-7])          # the crash cut the last event short
    ok, why, info = validate_trace(path, require_stages=["chunk.commit"])
    assert ok, why
    assert info["n_events"] == n - 1
    tr.close()


def test_tracer_writes_off_the_span_thread(tmp_path):
    """Spans leave the buffer at bounded intervals without a flush from
    the caller; close writes the rest and makes the file strict JSON."""
    import time
    path = str(tmp_path / "t.json")
    tr = Tracer(TraceWriter(path), flush_s=0.01)
    with tr.span("source.pull"):
        pass
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        ok, _, info = validate_trace(path, require_stages=["source.pull"])
        if ok:
            break
        time.sleep(0.01)
    assert ok
    tr.metadata("op_phases", name="jit_p", phases={"a": "evaluate"})
    tr.close()
    events = _events(path)          # strict JSON after close
    meta = [e for e in events if e["name"] == "op_phases"]
    assert meta and meta[0]["args"]["phases"] == {"a": "evaluate"}
    assert meta[0]["ts"] == 0



def test_no_span_is_lost_under_concurrent_writers(tmp_path):
    """Span threads append while the writer thread drains: every span is
    written exactly once and counted once in its histogram."""
    import sys
    import threading
    path = str(tmp_path / "t.json")
    reg = Telemetry()
    tr = Tracer(TraceWriter(path), reg, flush_s=0.001)
    n_threads, n_spans = 16, 300

    def emit(t):
        for i in range(n_spans):
            with tr.span("s", t=t, i=i):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=emit, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    tr.close()
    spans = [(e["args"]["t"], e["args"]["i"]) for e in _events(path)
             if e["ph"] == "X"]
    assert sorted(spans) == [(t, i) for t in range(n_threads)
                             for i in range(n_spans)]
    assert reg.histogram("span.s").count == n_threads * n_spans


def test_span_args_keep_their_json_types(tmp_path):
    """Argument sets are serialized once and remembered by value: ``1``,
    ``1.0`` and ``True`` compare equal in Python but are different JSON."""
    path = str(tmp_path / "t.json")
    tr = Tracer(TraceWriter(path), flush_s=1e6)
    for v in (1, True, 1.0, 1, "1", None, [1]):
        with tr.span("s", v=v):
            pass
    tr.close()
    got = [e["args"]["v"] for e in _events(path) if e["ph"] == "X"]
    assert got == [1, True, 1.0, 1, "1", None, [1]]
    assert [type(v) for v in got] == [int, bool, float, int, str,
                                      type(None), list]
