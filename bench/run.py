"""TStream benchmark: one cell, one run, on the chips it asks for.

    python3 bench/run.py --workload gs_paper.backlog --seed 7 \
        --seconds 20 --trace 0

Loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, builds the table and the events from ``--seed``,
warms up every program the window runs (counted as set-up), then serves
the traffic through ``StreamService.run`` over ``DualModeEngine`` for
``--seconds``.  Once the window has closed it compares every committed
interval's outputs, the final table and the snapshots kept on disk with
the plain reference (``bench/reference/<app>.py``, run in the order the
intervals were cut, ``benchlib/assembly.py``) and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each number compared
beside its limit.  The same numbers end standard error.

It refuses to run, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is the
program's fixed one (``repro.compile_cache``), inside the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from benchlib import assembly, compare, snapshots, spec  # noqa: E402
from benchlib.record import RunRecord  # noqa: E402
from benchlib.traffic import Traffic, block_rng  # noqa: E402

STREAM_TABLE, STREAM_WARM = 3, 4
SERVICE_KEYS = ("punct_interval", "chunk_intervals", "queue_intervals",
                "admission", "snapshot_every", "keep_last")
OUT = os.path.join(BENCH, "out")
TRACE_FROM, TRACE_SECONDS = 0.25, 3.0


class NoChip(SystemExit):
    pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class CompileClock:
    """Seconds and counts of JAX's tracing and compilation, from its own
    monitoring events, each stamped with the host time it ended."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self.EVENTS:
            self.events.append((time.perf_counter(), name, secs))

    def seconds(self, t_from=0.0, t_to=float("inf")):
        return sum(s for t, _, s in self.events if t_from <= t < t_to)

    def count(self, kind, t_from=0.0, t_to=float("inf")):
        return sum(1 for t, n, _ in self.events
                   if t_from <= t < t_to and n.endswith(kind))


class GcClock:
    """Pauses of the interpreter's cyclic garbage collector, each stamped
    with the host time it ended, by generation."""

    def __init__(self):
        import gc
        self.events, self._start = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            t = time.perf_counter()
            self.events.append((t, info["generation"], t - self._start))

    def summary(self, t_from, t_to):
        """Full collections ending inside ``[t_from, t_to)``: count and
        longest pause (ms), and the pauses of all generations summed."""
        inside = [(g, d) for t, g, d in self.events if t_from <= t < t_to]
        full = [d for g, d in inside if g == 2]
        return dict(full=len(full), full_max_ms=max(full, default=0) * 1e3,
                    all_ms=sum(d for _, d in inside) * 1e3)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class TraceWindow(threading.Thread):
    """The profiler over a part of the window: from ``TRACE_FROM`` of it,
    for at most ``TRACE_SECONDS``.  A whole window of the lockstep path
    holds more device ops than the profiler keeps (it stopped recording
    after about six million), and a steady stretch is what the per-layer
    metrics read.  ``ts0``/``ts1`` are the host-clock bounds of the traced
    part; the profiler's clock is tied to ``ts0`` by the annotation
    :data:`benchlib.devtrace.MARK`."""

    def __init__(self, jax, prof_dir, t0, seconds):
        super().__init__(name="bench-profiler", daemon=True)
        self.jax, self.prof_dir = jax, prof_dir
        self.t_start = t0 + TRACE_FROM * seconds
        self.t_stop = self.t_start + min(TRACE_SECONDS, seconds / 2)
        self.ts0 = self.ts1 = None
        self.error = None
        self._done = threading.Event()

    def run(self):
        try:
            self._trace()
        except Exception as e:      # reported by finish(), never swallowed
            self.error = e

    def _trace(self):
        from benchlib.devtrace import MARK
        jax = self.jax
        if self._done.wait(max(0.0, self.t_start - time.perf_counter())):
            return
        shutil.rmtree(self.prof_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.prof_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(MARK)
        self.ts0 = time.perf_counter()
        ann.__enter__()
        ann.__exit__(None, None, None)
        self._done.wait(max(0.0, self.t_stop - time.perf_counter()))
        self.ts1 = time.perf_counter()
        jax.profiler.stop_trace()

    def finish(self):
        self._done.set()
        self.join()
        if self.error is not None:
            raise self.error
        if self.ts0 is None:
            raise RuntimeError("the run ended before its traced part began")


class Bench:
    """One cell set up in this process: table, engine, traffic, warm."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float, *,
                 require_chip: bool = True):
        self.cell, self.cfg, self.seed = cell, cell.cfg, int(seed)
        self.seconds = float(seconds)
        self.ckpt_dir = os.path.join(OUT, cell.name, "ckpt")
        self.watermark = cell.cfg.get("service", {}).get("watermark", {})
        assembly.check_policy(self.watermark)
        self.setup = {}
        t = time.perf_counter()
        sys.path.insert(0, os.path.join(ROOT, "src"))
        try:
            from repro.compile_cache import setup_compile_cache
        except ImportError as e:
            raise SystemExit(f"bench: the program is not here ({e}); "
                             f"nothing was run")
        self.cache_dir = setup_compile_cache()
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.jax = jax
        devices = jax.devices()
        self.devices = devices[:cell.chips]
        kind = devices[0].device_kind
        if require_chip and (devices[0].platform != "tpu"
                             or len(devices) < cell.chips):
            raise NoChip(f"bench: {cell.name} needs {cell.chips} TPU "
                         f"chip(s); JAX found {len(devices)} "
                         f"{devices[0].platform} device(s); nothing was run")
        self.peaks = spec.peaks(kind) if require_chip else None
        self.clock = CompileClock(jax)
        self.gc_clock = GcClock()
        import repro.runtime.service  # noqa: F401  (import cost is set-up)
        self.setup["import_s"] = time.perf_counter() - t

    def build(self):
        """Table and engine, the table from the seed."""
        from repro.apps import ALL_APPS
        from repro.core.scheduler import DualModeEngine, EngineConfig
        from repro.core.types import make_store
        jax, cfg, cell = self.jax, self.cfg, self.cell
        t = time.perf_counter()
        self.gen = cell.module("gen")
        self.sampler = self.gen.Sampler(cfg)
        self.init = self.gen.initial_table(
            block_rng(self.seed, STREAM_TABLE, 0), cfg)
        self.app = ALL_APPS[cfg["app"]]
        with jax.default_device(self.devices[0]):
            store = make_store(cfg["tables"], cfg["width"],
                               init=jax.numpy.asarray(self.init))
        self.ecfg = EngineConfig(**cfg["engine"])
        mesh = None
        if cfg.get("layout"):
            from repro.core.sharded_stream import stream_mesh
            mesh = stream_mesh((cell.chips,), ("dev",), devices=self.devices)
        self.engine = DualModeEngine(
            self.app, store, self.ecfg, mesh=mesh,
            layout=cfg.get("layout", "shared_nothing"),
            exchange_slack=cfg.get("exchange_slack", 2.0))
        self.store = store
        self.setup["engine_s"] = time.perf_counter() - t

    def make_traffic(self, traffic: dict, seed: int):
        """The traffic of one window, from ``seed``; in backlog mode its
        queue is full when this returns."""
        t = time.perf_counter()
        cfg = self.cfg
        self.traffic = Traffic(self.sampler, traffic, seed,
                               stop_multiple=(cfg["punct_interval"]
                                              * cfg["chunk_intervals"]),
                               seconds=self.seconds)
        self.traffic.prefill()
        self.setup["events_s"] = time.perf_counter() - t

    def service_cfg(self, trace_path=""):
        """The configuration's ``ServiceConfig``: its interval, chunk,
        queue, admission and snapshot settings, and whatever further
        fields its ``service`` object sets."""
        from repro.runtime.service import ServiceConfig
        from repro.runtime.telemetry import TelemetryConfig
        cfg = self.cfg
        fields = {k: cfg[k] for k in SERVICE_KEYS}
        fields.update(cfg.get("service", {}))
        fields.update(
            ckpt_dir=self.ckpt_dir,
            telemetry=(TelemetryConfig(trace_path=trace_path)
                       if trace_path else None))
        return spec.build(ServiceConfig, fields)

    def warm(self):
        """One snapshot period of the service on other events: compiles
        (or loads from the cache) the chunk, output and snapshot
        programs at the window's shapes."""
        import numpy as np
        from repro.runtime.service import StreamService
        cfg = self.cfg
        t = time.perf_counter()
        c0 = self.clock.seconds()
        n = cfg["snapshot_every"] * cfg["punct_interval"]
        ev = self.sampler.events(block_rng(self.seed, STREAM_WARM, 0), n)
        b = 64
        batches = [({k: v[i:i + b] for k, v in ev.items()},
                    np.arange(i, min(i + b, n)))
                   for i in range(0, n, b)]
        scfg = self.service_cfg()
        shutil.rmtree(scfg.ckpt_dir, ignore_errors=True)
        StreamService(self.engine, scfg).run(batches)
        shutil.rmtree(scfg.ckpt_dir, ignore_errors=True)
        self.setup["compile_s"] = self.clock.seconds() - c0
        self.setup["warm_s"] = time.perf_counter() - t

    def window(self, trace: bool):
        """Serve the traffic for the window; returns the service's run.

        With ``trace`` the service writes its spans, and the profiler
        records a part of the window (:class:`TraceWindow`)."""
        from repro.runtime.service import StreamService
        out = os.path.join(OUT, self.cell.name)
        span_path = os.path.join(out, "spans.json") if trace else ""
        scfg = self.service_cfg(span_path)
        shutil.rmtree(scfg.ckpt_dir, ignore_errors=True)
        svc = StreamService(self.engine, scfg)
        tw = None
        try:
            self.t0 = time.perf_counter()
            self.t1 = self.t0 + self.seconds
            self.traffic.open_window(self.t0)
            if trace:
                tw = TraceWindow(self.jax, os.path.join(out, "profile"),
                                 self.t0, self.seconds)
                tw.start()
            run = svc.run(self.traffic)
            self.t_done = time.perf_counter()
        finally:
            self.traffic.close()
            if tw is not None:
                tw.finish()
        self.span_path, self.trace_window = span_path, tw
        return run

    def peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    def free_program(self):
        """Drop the program's device state before the reference runs."""
        self.engine = self.store = None
        gc.collect()


def span_offset(events, commit_s):
    """``(offset, half_width)``: the host-clock time of the span clock's
    zero, to within ``half_width`` seconds.

    Span times count from the tracer's own start, which the span file does
    not record.  Each chunk's ``chunk.commit`` span encloses the
    host-clock stamp the run keeps as that chunk's ``commit_s``
    (``commit_s[g]`` for interval ``g``): every pair bounds the offset
    from both sides, and the offset is the middle of what all allow."""
    commits = [e for e in events
               if e.get("ph") == "X" and e["name"] == "chunk.commit"]
    if not commits:
        raise ValueError("no chunk.commit span to place the spans by")
    lo = max(commit_s[e["args"]["g0"]] - (e["ts"] + e["dur"]) * 1e-6
             for e in commits)
    hi = min(commit_s[e["args"]["g0"]] - e["ts"] * 1e-6 for e in commits)
    if lo > hi + 1e-6:
        raise ValueError(f"commit spans and stamps disagree by "
                         f"{lo - hi:.6f} s")
    return (lo + hi) / 2, (hi - lo) / 2


def read_spans(path: str, commit_s):
    """The service's spans as ``(name, thread, start, end)`` on the host
    clock, and the half width of their placement (:func:`span_offset`)."""
    with open(path) as f:
        events = json.load(f)
    threads = {e["tid"]: e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    base, half = span_offset(events, commit_s)
    return [(e["name"], threads.get(e["tid"], str(e["tid"])),
             base + e["ts"] * 1e-6, base + (e["ts"] + e["dur"]) * 1e-6)
            for e in events if e.get("ph") == "X"], half


def gap_label(spans, t):
    """What the host was doing at ``t``: the innermost open span of each
    thread."""
    open_ = {}
    for n, th, s, e in spans:
        if s <= t < e and (th not in open_ or s >= open_[th][1]):
            open_[th] = (n, s)
    return "; ".join(f"{th}:{n}" for th, (n, _) in sorted(open_.items())) \
        or "no host span"


def run_cell(argv=None, *, require_chip=True, bench_spec=None):
    """One run; returns ``(result line dict, run info dict)``.
    ``bench_spec`` stands in for ``BENCHMARK.json`` (tests)."""
    import numpy as np
    args = parse_args(argv)
    bench_spec = bench_spec or spec.load_spec()
    cell = spec.Cell(bench_spec, args.workload)
    b = Bench(cell, args.seed, args.seconds, require_chip=require_chip)
    b.build()
    b.make_traffic(cell.traffic, args.seed)
    b.warm()
    run = b.window(trace=bool(args.trace))
    setup_s = b.t0 - T_START
    cfg, tr = cell.cfg, b.traffic
    interval = cfg["punct_interval"]
    commit_s = np.asarray([c["commit_s"] for c in run.commits])
    committed = int(np.sum(commit_s < b.t1)) * interval
    n_done = len(run.outputs) * interval
    xdrop = int(run.exchange_dropped)
    info = dict(
        cell=cell.name, seed=b.seed, seconds=b.seconds,
        setup=dict(b.setup, total_s=setup_s),
        compiles_in_window=b.clock.count("backend_compile_duration",
                                         b.t0, b.t1),
        traces_in_window=b.clock.count("jaxpr_trace_duration", b.t0, b.t1),
        generator=dict(mode=tr.mode, handed=tr.handed,
                       backlog_at_close=(None if tr.released_at_close is None
                                         else tr.released_at_close
                                         - tr.handed_at_close),
                       ran_dry=tr.ran_dry),
        committed_in_window=committed, committed_total=n_done,
        drain_s=b.t_done - b.t1, exchange_dropped=xdrop,
        late_rerouted=int(sum(c["n_late"] for c in run.commits)),
        admission_dropped=int(run.admission_dropped),
        cache_dir=b.cache_dir, gc=b.gc_clock.summary(b.t0, b.t1))
    memory_peak = b.peak_bytes()
    info["peak_bytes_in_use"] = memory_peak
    # host stalls show as long gaps between chunk commits; a snapshot is
    # written between a chunk that ends on a snapshot boundary and the next
    chunk_t, last = np.unique(commit_s[commit_s < b.t1], return_index=True)
    ends = np.append(last[1:], len(commit_s[commit_s < b.t1]))
    gaps = np.diff(chunk_t) * 1e3
    if gaps.size:
        every = cfg["snapshot_every"] or 1
        long_ = gaps > 100.0
        info["commit_gap_ms"] = dict(
            p50=float(np.median(gaps)), p99=float(np.percentile(gaps, 99)),
            max=float(gaps.max()), over_100=int(long_.sum()),
            over_100_after_snapshot=int(np.sum(
                long_ & (ends[:-1] % every == 0))))

    # the serial order the intervals ran the stream in (arrival order
    # unless events arrive out of event-time order)
    events = tr.events(0, n_done) if n_done else {}
    lateness = int(b.watermark.get("allowed_lateness", 0))
    order = rank = None
    if n_done and (tr.jitter or lateness):
        order = assembly.emission_order(events["_time"], tr.batch_ends,
                                        lateness)
        events = {k: v[order] for k, v in events.items()}
        rank = np.empty(n_done, np.int64)
        rank[order] = np.arange(n_done)

    latency = gen_lag = None
    if tr.mode == "open":
        due = tr.due_s[:n_done]
        in_win = due < b.seconds
        iv = (np.arange(n_done) if rank is None else rank) // interval
        latency = (commit_s[iv] - (b.t0 + due))[in_win]
        gen_lag = np.asarray(tr.lag_s).reshape(-1, 2)
        info["events_due_in_window"] = int(in_win.sum())
        info["latency_ms"] = {f"p{q}": float(np.percentile(latency, q)) * 1e3
                              for q in (90, 95, 99)} if latency.size else {}

    spans = dtrace = None
    t_a, t_b = b.t0, b.t1
    if args.trace:
        from benchlib.devtrace import DeviceTrace, xplane_path
        tw = b.trace_window
        t_a, t_b = tw.ts0, min(tw.ts1, b.t1)
        spans, half = read_spans(b.span_path, commit_s)
        info["span_placement_ms"] = half * 1e3
        t = time.perf_counter()
        dtrace = DeviceTrace.load(xplane_path(tw.prof_dir), tw.ts0)
        info["trace_read_s"] = time.perf_counter() - t
        info["traced"] = dict(from_s=t_a - b.t0, seconds=t_b - t_a)
        info["trace_planes"] = dtrace.planes
        info["trace_modules"] = dtrace.top_modules(t_a, t_b)
    if gen_lag is not None:
        due = b.t0 + gen_lag[:, 0]
        gen_lag = gen_lag[(due >= t_a) & (due < t_b), 1]

    dev0 = b.devices[0]
    device = dict(platform=dev0.platform, kind=dev0.device_kind,
                  count=len(b.devices), memory_peak_bytes=memory_peak)

    # -- the window has closed: the reference, then the comparison --------
    outputs, table = run.outputs, run.final_values
    b.free_program()
    t = time.perf_counter()
    expected = snapshots.expected_steps(len(outputs), cfg["snapshot_every"])
    kept = {s: snapshots.read_values(b.ckpt_dir, s)
            for s in snapshots.kept_steps(b.ckpt_dir)}
    at = [s * interval for s in compare.kept_expected(cfg, expected)]
    ref = cell.module("reference")
    ref_out, ref_table, ref_at = ref.run(b.init, events, n_done, cfg, at=at)
    snaps = dict(expected=expected, recorded=list(run.snapshots), kept=kept,
                 ref={c // interval: ref_at[c] for c in at})
    checks = compare.compare(cfg, outputs, table, ref_out, ref_table, n_done,
                             snaps)
    info["snapshots"] = dict(published=len(run.snapshots),
                             kept=sorted(kept))
    info["reference_s"] = time.perf_counter() - t
    failed = (tr.handed - n_done) + xdrop
    correct = compare.passed(checks) and failed == 0 and n_done > 0

    # end-to-end metrics read the window, per-layer ones its traced part
    rec = RunRecord(
        cell=cell.name, cfg=cfg, traffic=cell.traffic, chips=cell.chips,
        seconds=t_b - t_a, t0=t_a, t1=t_b, setup_s=setup_s,
        committed=int(np.sum((commit_s >= t_a) & (commit_s < t_b)))
        * interval, interval=interval, latency_s=latency,
        gen_lag_s=gen_lag, spans=spans, device=dtrace, peaks=b.peaks,
        work=cell.module("work"))
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(section):
        v = spec.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
    line = dict(correct=bool(correct), attempted=int(tr.handed),
                failed=int(failed), metrics=metrics, device=device)
    if args.trace:
        device["busy_s"] = dtrace.busy_s(t_a, t_b)
        device["window_s"] = t_b - t_a
        line["breakdown"] = dict(
            device_ops=dtrace.top_ops(t_a, t_b),
            idle_gaps=[[gap_label(spans, (s + e) / 2), e - s]
                       for s, e in dtrace.idle_gaps(t_a, t_b)])
    line["checks"] = checks
    return line, info


def main(argv=None, *, require_chip=True):
    try:
        line, info = run_cell(argv, require_chip=require_chip)
    except NoChip as e:
        log(str(e))
        return 2
    print("[run] " + json.dumps(info, default=str), flush=True)
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
