"""Unified telemetry plane (DESIGN.md §2.11).

One registry for every observability surface the runtime grew piecemeal:

* **Labeled counters / gauges** — monotone totals (drops by category,
  exchange ship/drop counts, the assembler's conservation ledger) and
  point-in-time levels (watermark, exchange capacity, backfill ratio).
* **Deterministic log-bucketed histograms** — latency distributions with
  geometric bucket bounds fixed at construction, so two histograms built
  from the same observations are bit-equal and *merge exactly*: bucket
  counts are integer sums, the running total is kept in integer
  nanoseconds, and min/max merge by min/max.  Merge is associative and
  conservation-respecting (pinned by tests/test_telemetry.py).
* **Bounded structured record logs** — the chunk-record ring, decision
  trace, fired faults, migrations: ordered lists of JSON documents.
* **Rate-limited events** — the once-per-run log lines ("watermark
  policy dropped …") become structured events that still emit through
  the caller's logger with the exact legacy message, but carry a
  occurrence count and a per-registry emission limit instead of
  hand-rolled "logged once" flags.
* **Span tracing** — Chrome-trace / Perfetto-compatible JSONL covering
  the whole service pipeline (source pull → interval assembly →
  admission → chunk dispatch → device execute → commit →
  ``controller.decide`` → snapshot publish → ``reshard.apply``), the
  executor's waits (``executor.hold``/``executor.idle``, ``chunk.fetch``)
  and compilations (``jax.compile``), on one clock whose epoch the file
  carries; buffered and written off the span's thread.
* **Device work by phase** — :func:`op_phase_table` maps each op of a
  compiled chunk program to the named scope it runs under, so a device
  trace's ops can be summed by phase.

**Replay-safety contract** (the §2.11 hard invariant): telemetry is
observability only.  The tracer reads a clock *only when a trace sink is
attached*; span data and histograms never feed ``controller.decide``;
a tracing-enabled run is bitwise identical to a tracing-off run —
including crash → restore → replay.  The only sanctioned timing→control
bridge is the *advisory* channel (``runtime/controller.AdvisoryTiming``):
timing-tier hints are logged and recorded here but never applied while
snapshots are on.

The registry snapshot is versioned (``SCHEMA`` / ``SCHEMA_VERSION``);
``stats_view`` renders the legacy ``StreamService.stats`` dict from a
snapshot so the old surface survives as a compatibility view.
"""
from __future__ import annotations

import collections
import json
import logging
import os
import re
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger(__name__)

SCHEMA = "repro.telemetry"
SCHEMA_VERSION = 1

# default latency-histogram geometry: 4 buckets per octave from 1 µs,
# 30 octaves (~18 min) before the overflow bucket — wide enough for a
# cold-compile chunk, fine enough for sub-ms percentile reads
HIST_LO_S = 1e-6
HIST_GROWTH = 2.0 ** 0.25
HIST_BUCKETS = 120

_LabelKey = Tuple[Tuple[str, Any], ...]


def _label_key(labels: Dict[str, Any]) -> _LabelKey:
    return tuple(sorted(labels.items()))


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------
class Histogram:
    """Log-bucketed histogram with deterministic bucketing + exact merge.

    Bucket *i* covers ``(bound[i-1], bound[i]]`` with
    ``bound[i] = lo * growth**i`` (bucket 0 additionally absorbs
    everything ``<= lo``, the last bucket is the overflow).  The bounds
    are a pure function of ``(lo, growth, n_buckets)``, so any two
    histograms with the same geometry bucket identically and merging is
    per-bucket integer addition — associative and lossless.  The value
    total is kept in integer nanoseconds (``total_ns``) so merged sums
    are exact, not float-order-dependent.
    """

    __slots__ = ("lo", "growth", "n_buckets", "_bounds", "counts",
                 "count", "total_ns", "vmin", "vmax")

    def __init__(self, lo: float = HIST_LO_S, growth: float = HIST_GROWTH,
                 n_buckets: int = HIST_BUCKETS):
        assert lo > 0 and growth > 1.0 and n_buckets >= 1
        self.lo = float(lo)
        self.growth = float(growth)
        self.n_buckets = int(n_buckets)
        self._bounds = self.lo * self.growth ** np.arange(self.n_buckets)
        self.counts = np.zeros(self.n_buckets + 1, np.int64)
        self.count = 0
        self.total_ns = 0
        self.vmin = float("inf")
        self.vmax = float("-inf")

    def geometry(self) -> Tuple[float, float, int]:
        return (self.lo, self.growth, self.n_buckets)

    def observe(self, value: float) -> None:
        self.observe_many([value])

    def observe_many(self, values) -> None:
        a = np.asarray(values, np.float64).ravel()
        if a.size == 0:
            return
        idx = np.searchsorted(self._bounds, a, side="left")
        np.add.at(self.counts, idx, 1)
        self.count += int(a.size)
        self.total_ns += int(np.rint(a * 1e9).astype(np.int64).sum())
        self.vmin = min(self.vmin, float(a.min()))
        self.vmax = max(self.vmax, float(a.max()))

    @property
    def mean_s(self) -> float:
        return (self.total_ns / 1e9 / self.count) if self.count else float("nan")

    def percentile(self, q: float) -> float:
        """Deterministic percentile read: the upper bound of the bucket
        holding the q-th ranked observation, clipped to the observed
        [min, max] — exact to within one bucket's width."""
        if self.count == 0:
            return float("nan")
        rank = max(1.0, q / 100.0 * self.count)
        cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, rank, side="left"))
        est = self._bounds[min(i, self.n_buckets - 1)]
        return float(min(max(est, self.vmin), self.vmax))

    def merge(self, other: "Histogram") -> "Histogram":
        assert self.geometry() == other.geometry(), \
            (f"histogram geometry mismatch: {self.geometry()} != "
             f"{other.geometry()} — exact merge requires identical buckets")
        self.counts = self.counts + other.counts
        self.count += other.count
        self.total_ns += other.total_ns
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)
        return self

    def to_dict(self) -> Dict:
        nz = np.nonzero(self.counts)[0]
        return dict(
            lo=self.lo, growth=self.growth, n_buckets=self.n_buckets,
            counts={str(int(i)): int(self.counts[i]) for i in nz},
            count=int(self.count), total_ns=int(self.total_ns),
            min=(None if self.count == 0 else self.vmin),
            max=(None if self.count == 0 else self.vmax))

    @staticmethod
    def from_dict(d: Dict) -> "Histogram":
        h = Histogram(lo=float(d["lo"]), growth=float(d["growth"]),
                      n_buckets=int(d["n_buckets"]))
        for i, c in d.get("counts", {}).items():
            h.counts[int(i)] = int(c)
        h.count = int(d["count"])
        h.total_ns = int(d["total_ns"])
        if h.count:
            h.vmin = float(d["min"])
            h.vmax = float(d["max"])
        return h


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------
class Telemetry:
    """Thread-safe metrics registry: counters, gauges, histograms,
    bounded record logs and rate-limited events, snapshotted behind the
    versioned schema.  One instance per service run (merged views come
    from :meth:`merge`); a process-wide instance serves code paths with
    no run context (:func:`get_default`)."""

    def __init__(self, record_cap: int = 4096):
        self._lock = threading.RLock()
        self.record_cap = int(record_cap)
        self._counters: Dict[str, Dict[_LabelKey, float]] = {}
        self._gauges: Dict[str, Dict[_LabelKey, float]] = {}
        self._hists: Dict[str, Histogram] = {}
        self._records: Dict[str, List[Any]] = {}
        self._events: Dict[str, Dict[str, int]] = {}

    # -- counters / gauges -------------------------------------------------
    def count(self, name: str, n: float = 1, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            series = self._counters.setdefault(name, {})
            series[key] = series.get(key, 0) + n

    def gauge(self, name: str, value: float, **labels) -> None:
        with self._lock:
            self._gauges.setdefault(name, {})[_label_key(labels)] = value

    def counter_value(self, name: str, **labels) -> float:
        with self._lock:
            return self._counters.get(name, {}).get(_label_key(labels), 0)

    # -- histograms --------------------------------------------------------
    def histogram(self, name: str, lo: float = HIST_LO_S,
                  growth: float = HIST_GROWTH,
                  n_buckets: int = HIST_BUCKETS) -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(lo, growth, n_buckets)
            return h

    def observe(self, name: str, value: float) -> None:
        self.histogram(name).observe(value)

    def observe_many(self, name: str, values) -> None:
        a = np.asarray(values, np.float64).ravel()
        if a.size:
            self.histogram(name).observe_many(a)

    # -- structured record logs --------------------------------------------
    def ensure_records(self, name: str) -> None:
        with self._lock:
            self._records.setdefault(name, [])

    def record(self, name: str, **fields) -> None:
        self.record_doc(name, fields)

    def record_doc(self, name: str, doc: Any) -> None:
        with self._lock:
            lst = self._records.setdefault(name, [])
            lst.append(doc)
            if len(lst) > self.record_cap:
                del lst[: len(lst) - self.record_cap]

    def records(self, name: str) -> List[Any]:
        with self._lock:
            return list(self._records.get(name, ()))

    # -- rate-limited structured events ------------------------------------
    def event(self, name: str, msg: str, *args, logger=None,
              level: int = logging.WARNING, limit: int = 1) -> bool:
        """Count an occurrence of ``name``; emit ``msg % args`` through
        ``logger`` for the first ``limit`` occurrences (``limit=-1``:
        always).  Returns whether this occurrence was emitted — the
        replacement for the hand-rolled "logged once per run" flags."""
        with self._lock:
            st = self._events.setdefault(
                name, dict(count=0, emitted=0, limit=int(limit)))
            st["count"] += 1
            emit = st["limit"] < 0 or st["emitted"] < st["limit"]
            if emit:
                st["emitted"] += 1
        if emit and logger is not None:
            logger.log(level, msg, *args)
        return emit

    # -- merge / snapshot --------------------------------------------------
    def merge(self, other: "Telemetry") -> "Telemetry":
        """Fold ``other`` into this registry: counters and event counts
        add, histograms merge exactly, records concatenate (cap kept),
        gauges take ``other``'s value (latest wins)."""
        with self._lock, other._lock:
            for name, series in other._counters.items():
                mine = self._counters.setdefault(name, {})
                for k, v in series.items():
                    mine[k] = mine.get(k, 0) + v
            for name, series in other._gauges.items():
                self._gauges.setdefault(name, {}).update(series)
            for name, h in other._hists.items():
                if name in self._hists:
                    self._hists[name].merge(h)
                else:
                    self._hists[name] = Histogram.from_dict(h.to_dict())
            for name, lst in other._records.items():
                for doc in lst:
                    self.record_doc(name, doc)
            for name, st in other._events.items():
                mine = self._events.setdefault(
                    name, dict(count=0, emitted=0, limit=st["limit"]))
                mine["count"] += st["count"]
                mine["emitted"] += st["emitted"]
        return self

    def snapshot(self) -> Dict:
        """The versioned schema document (JSON-serializable)."""
        with self._lock:
            return {
                "schema": SCHEMA,
                "schema_version": SCHEMA_VERSION,
                "counters": [
                    dict(name=name, labels=dict(k), value=v)
                    for name, series in sorted(self._counters.items())
                    for k, v in sorted(series.items())],
                "gauges": [
                    dict(name=name, labels=dict(k), value=v)
                    for name, series in sorted(self._gauges.items())
                    for k, v in sorted(series.items())],
                "histograms": {name: h.to_dict()
                               for name, h in sorted(self._hists.items())},
                "events": [dict(name=name, **st)
                           for name, st in sorted(self._events.items())],
                "records": {name: list(lst)
                            for name, lst in self._records.items()},
            }

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, default=_json_default)


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON-serializable: {type(o)}")


_DEFAULT: Optional[Telemetry] = None
_DEFAULT_LOCK = threading.Lock()


def get_default() -> Telemetry:
    """The process-wide registry — for code paths outside a service run
    (the batch drivers' overflow accounting, ad-hoc counters)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = Telemetry()
        return _DEFAULT


# ---------------------------------------------------------------------------
# snapshot accessors (consumed by benchmarks/report over saved JSON too)
# ---------------------------------------------------------------------------
def counter_value(snap: Dict, name: str, default: float = 0, **labels):
    want = dict(labels)
    for c in snap.get("counters", ()):
        if c["name"] == name and dict(c.get("labels", {})) == want:
            return c["value"]
    return default


def gauge_value(snap: Dict, name: str, default: float = 0, **labels):
    want = dict(labels)
    for g in snap.get("gauges", ()):
        if g["name"] == name and dict(g.get("labels", {})) == want:
            return g["value"]
    return default


def has_gauge(snap: Dict, name: str) -> bool:
    return any(g["name"] == name for g in snap.get("gauges", ()))


def record_entries(snap: Dict, name: str) -> List[Any]:
    return list(snap.get("records", {}).get(name, ()))


def has_records(snap: Dict, name: str) -> bool:
    return name in snap.get("records", {})


def counters_with_prefix(snap: Dict, prefix: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for c in snap.get("counters", ()):
        if c["name"].startswith(prefix) and not c.get("labels"):
            out[c["name"][len(prefix):]] = c["value"]
    return out


def histogram_from(snap: Dict, name: str) -> Optional[Histogram]:
    d = snap.get("histograms", {}).get(name)
    return None if d is None else Histogram.from_dict(d)


def load_snapshot(path: str) -> Dict:
    with open(path) as f:
        snap = json.load(f)
    assert snap.get("schema") == SCHEMA, f"not a telemetry snapshot: {path}"
    assert int(snap.get("schema_version", 0)) <= SCHEMA_VERSION, \
        (f"telemetry snapshot {path} has schema_version "
         f"{snap.get('schema_version')} > supported {SCHEMA_VERSION}")
    return snap


# ---------------------------------------------------------------------------
# the legacy stats dict as a view over the schema
# ---------------------------------------------------------------------------
def stats_view(snap: Dict) -> Dict:
    """Render ``StreamService.stats``' legacy shape from a registry
    snapshot — the compatibility view: every consumer of the old merged
    dict keeps working while the registry is the source of truth."""
    def C(name, **labels):
        return counter_value(snap, name, **labels)

    def G(name, default=0.0):
        return gauge_value(snap, name, default)

    assembly = dict(arrived=0, assembled=0, dropped=0, pending=0,
                    rerouted=0, emitted=0)
    assembly.update({k: int(v) for k, v in
                     counters_with_prefix(snap, "assembly.").items()})
    stats: Dict[str, Any] = dict(
        arrived=int(C("service.arrived")),
        processed=int(C("service.processed")),
        replayed=int(C("service.replayed")),
        late_rerouted=int(C("service.late_rerouted")),
        drops=dict(
            watermark=int(C("service.drops", kind="watermark")),
            admission=int(C("service.drops", kind="admission")),
            exchange=int(C("service.drops", kind="exchange"))),
        unprocessed=int(C("service.unprocessed")),
        snapshots=[int(r["step"]) for r in record_entries(snap, "snapshots")],
        watermark=int(G("service.watermark")),
        crashed=bool(G("service.crashed")),
        assembly=assembly,
        source=dict(
            pulls=int(C("source.pulls")),
            retries=int(C("source.retries")),
            deadline_misses=int(C("source.deadline_misses")),
            backoff_s=float(C("source.backoff_s")),
            backfill_ratio=float(G("source.backfill_ratio")),
            alarm_threshold=float(G("source.alarm_threshold")),
            alarm=bool(G("source.alarm"))),
        chunks=[dict(r) for r in record_entries(snap, "chunks")],
    )
    ctl = record_entries(snap, "controller")
    if ctl:
        stats["controller"] = dict(
            dict(ctl[0]),
            decisions=[dict(d) for d in record_entries(snap, "decisions")])
        adv = record_entries(snap, "advisory")
        if adv:
            stats["controller"]["advisory"] = [dict(h) for h in adv]
    err = record_entries(snap, "error")
    if err:
        stats["error"] = dict(err[0])
    if has_records(snap, "faults"):
        stats["faults"] = record_entries(snap, "faults")
    if has_gauge(snap, "exchange.capacity"):
        stats["exchange"] = dict(
            dropped=int(C("exchange.dropped")),
            shipped=int(C("exchange.shipped")),
            capacity=int(G("exchange.capacity")),
            escalations=int(G("exchange.escalations")),
            slack=float(G("exchange.slack")))
        pl = record_entries(snap, "placement")
        placement = (dict(pl[0]) if pl
                     else dict(shard_events=[], imbalance=1.0, owners=[]))
        placement["migrations"] = [dict(m) for m
                                   in record_entries(snap, "migrations")]
        placement["moved_rows"] = int(sum(
            m.get("moved", 0) for m in placement["migrations"]))
        stats["placement"] = placement
    return stats


def empty_stats() -> Dict:
    """The schema-valid zero record ``StreamService.stats`` returns
    before any run (the old ``None`` footgun, fixed)."""
    return stats_view(Telemetry().snapshot())


# ---------------------------------------------------------------------------
# span tracing (Chrome trace event format / Perfetto JSON)
# ---------------------------------------------------------------------------
# the pipeline stages a service trace must cover (CI validation list);
# "reshard.apply" joins when an elastic run actually migrates
PIPELINE_STAGES = ("source.pull", "admission", "assembly", "chunk.submit",
                   "chunk.dispatch", "chunk.execute", "chunk.commit",
                   "snapshot.publish")


class TraceWriter:
    """Incremental Chrome-trace JSON array writer.  Events go out one per
    line, so a crashed run leaves a readable prefix (the format's closing
    ``]`` is optional for trace viewers and for :func:`validate_trace`);
    :meth:`close` makes the file strict JSON."""

    def __init__(self, path: str):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self.path = path
        self._f = open(path, "w")
        self._f.write("[")
        self._first = True
        self._lock = threading.Lock()

    def emit(self, ev: Dict) -> None:
        self.write_lines([json.dumps(ev, separators=(",", ":"),
                                     default=_json_default)])

    def write_lines(self, lines: Sequence[str]) -> None:
        """Append already-serialized events in one write, then flush."""
        if not lines:
            return
        with self._lock:
            if self._f.closed:
                return
            head = "\n" if self._first else ",\n"
            self._f.write(head + ",\n".join(lines))
            self._first = False
            self._f.flush()

    def flush(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.flush()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.write("\n]\n")
                self._f.close()


class _Span:
    """A ``ph="X"`` complete event; ``set(**args)`` attaches arguments
    any time before exit.  Exit appends one tuple to the tracer's buffer:
    no serialization and no registry update on the span's thread."""

    __slots__ = ("_tr", "name", "cat", "args", "_t0")

    def __init__(self, tracer, name, cat, args):
        self._tr = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def set(self, **kw) -> "_Span":
        self.args.update(kw)
        return self

    def __enter__(self) -> "_Span":
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self._tr._append(self.name, self.cat, self._t0, time.monotonic_ns(),
                         self.args)
        return False


class _NullSpan:
    __slots__ = ()

    def set(self, **kw) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracing off: never reads a clock, never allocates — the replay
    path's proof that telemetry is pure observability."""

    enabled = False

    def span(self, name, cat="pipeline", **args):
        return _NULL_SPAN

    def annotation(self, name, step):
        return _NULL_SPAN

    def complete_at(self, name, t0_s, t1_s, cat="pipeline", **args):
        pass

    def instant(self, name, cat="pipeline", **args):
        pass

    def metadata(self, event, **args):
        pass

    def close(self):
        pass


NULL_TRACER = NullTracer()

# the buffer's marks for events that are not complete spans
_INSTANT, _META = -1, -2


class Tracer:
    """Span emitter over a :class:`TraceWriter`, on one clock.

    Every stamp is ``CLOCK_MONOTONIC`` nanoseconds (``time.monotonic_ns``;
    ``time.monotonic`` seconds for :meth:`complete_at`).  The file's first
    event is the metadata event ``trace_epoch``, whose ``args.epoch_ns``
    is the clock's reading at ``ts`` 0: an event's host time is
    ``epoch_ns + ts * 1e3`` nanoseconds, with nothing fitted.

    A span's exit appends one tuple (name, category, start, end, thread,
    args) to a buffer.  A writer thread drains the buffer every
    ``flush_s`` seconds: it serializes the events, writes and flushes them
    in one call, and adds the drained spans' durations to the registry's
    ``span.<name>`` histograms in bulk (observability only — nothing on
    the decision path reads them).  A run that dies leaves every event
    drained before it; :meth:`close` drains the rest and closes the file.
    """

    enabled = True

    def __init__(self, writer: TraceWriter, registry: Optional[Telemetry]
                 = None, process_name: str = "repro-stream-service",
                 flush_s: float = 0.25):
        self._w = writer
        self._reg = registry
        self.pid = os.getpid()
        self.epoch_ns = time.monotonic_ns()
        self._buf: collections.deque = collections.deque()
        self._names: Dict[int, str] = {}    # thread ident -> thread name
        self._tids: Dict[int, int] = {}     # thread ident -> trace tid
        self._drain_lock = threading.Lock()
        self._warned = False
        self._heads: Dict[Tuple, str] = {}   # (name, cat, thread) -> head
        self._args_seen: Dict[Tuple, str] = {}
        self._dumps = json.JSONEncoder(separators=(",", ":"),
                                       default=_json_default).encode
        self._w.emit(dict(name="trace_epoch", ph="M", ts=0, pid=self.pid,
                          tid=0, args=dict(name="CLOCK_MONOTONIC",
                                           epoch_ns=self.epoch_ns)))
        self._w.emit(dict(name="process_name", ph="M", ts=0, pid=self.pid,
                          tid=0, args=dict(name=process_name)))
        self._stop = threading.Event()
        self._flush_s = float(flush_s)
        self._writer = threading.Thread(target=self._run, daemon=True,
                                        name="trace-writer")
        self._writer.start()

    # -- the span's thread: one tuple, no serialization -------------------
    def _append(self, name, cat, t0_ns, t1_ns, args) -> None:
        ident = threading.get_ident()
        if ident not in self._names:
            self._names[ident] = threading.current_thread().name
        self._buf.append((name, cat, t0_ns, t1_ns, ident, args))

    def span(self, name: str, cat: str = "pipeline", **args) -> _Span:
        return _Span(self, name, cat, args)

    def annotation(self, name: str, step: int):
        """A ``jax.profiler.TraceAnnotation`` named ``<name> <step>``: the
        host plane of any running profiler shows it, whoever started the
        profiler; without one it costs a check."""
        import jax
        return jax.profiler.TraceAnnotation(f"{name} {int(step)}")

    def complete_at(self, name: str, t0_s: float, t1_s: float,
                    cat: str = "pipeline", **args) -> None:
        """A complete event from two ``time.monotonic()`` stamps the
        caller already took for its own accounting (no clock read of the
        tracer's own)."""
        self._append(name, cat, int(t0_s * 1e9), int(t1_s * 1e9), args)

    def instant(self, name: str, cat: str = "pipeline", **args) -> None:
        self._append(name, cat, time.monotonic_ns(), _INSTANT, args)

    def metadata(self, event: str, **args) -> None:
        """A ``ph="M"`` event called ``event`` at ``ts`` 0 (``args`` must
        hold ``name``)."""
        self._append(event, "", self.epoch_ns, _META, args)

    # -- the writer thread ------------------------------------------------
    def _run(self) -> None:
        while not self._stop.wait(self._flush_s):
            self.flush()

    def _head(self, name: str, cat: str, ident: int,
              lines: List[str]) -> str:
        """An event's fixed leading fields, up to its ``ts`` value; the
        first event of a thread also writes the thread's name."""
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids) + 1
            lines.append(json.dumps(dict(
                name="thread_name", ph="M", ts=0, pid=self.pid, tid=tid,
                args=dict(name=self._names.get(ident, str(ident)))),
                separators=(",", ":")))
        return '{"name":%s,"cat":%s,"pid":%d,"tid":%d,"ts":' % (
            json.dumps(name), json.dumps(cat), self.pid, tid)

    def flush(self) -> None:
        """Drain the buffer into the file and the registry now."""
        with self._drain_lock:
            n = len(self._buf)
            if not n:
                return
            pop, epoch, heads = self._buf.popleft, self.epoch_ns, self._heads
            lines: List[str] = []
            durs: Dict[str, List[int]] = {}
            for _ in range(n):
                name, cat, t0, t1, ident, args = pop()
                if t1 == _META:
                    line = '{"name":%s,"ph":"M","ts":0,"pid":%d,"tid":0' % (
                        json.dumps(name), self.pid)
                else:
                    key = (name, cat, ident)
                    head = heads.get(key)
                    if head is None:
                        head = heads[key] = self._head(name, cat, ident,
                                                       lines)
                    if t1 == _INSTANT:
                        line = '%s%.3f,"ph":"i","s":"t"' % (
                            head, (t0 - epoch) / 1e3)
                    else:
                        d = max(t1 - t0, 0)
                        line = '%s%.3f,"ph":"X","dur":%.3f' % (
                            head, (t0 - epoch) / 1e3, d / 1e3)
                        ds = durs.get(name)
                        if ds is None:
                            ds = durs[name] = []
                        ds.append(d)
                if args:
                    try:
                        line += ',"args":' + self._args_json(args)
                    except (TypeError, ValueError) as e:
                        if not self._warned:
                            self._warned = True
                            log.warning("trace event %r written without "
                                        "its args (%s)", name, e)
                lines.append(line + "}")
            self._w.write_lines(lines)
            if self._reg is not None:
                for name, d in durs.items():
                    self._reg.observe_many("span." + name,
                                           np.asarray(d) / 1e9)

    def _args_json(self, args: Dict) -> str:
        """``args`` as JSON, remembered by value and type (``1``, ``1.0``
        and ``True`` are equal keys): most spans repeat a few small
        argument sets (an admission's outcome and queue fill)."""
        try:
            key = (tuple(args.items()), tuple(map(type, args.values())))
            text = self._args_seen.get(key)
        except TypeError:               # an unhashable value
            return self._dumps(args)
        if text is None:
            if len(self._args_seen) >= 4096:
                self._args_seen.clear()
            text = self._args_seen[key] = self._dumps(args)
        return text

    def close(self) -> None:
        self._stop.set()
        if self._writer.is_alive() and \
                self._writer is not threading.current_thread():
            self._writer.join()
        self.flush()
        self._w.close()


@dataclass(frozen=True)
class TelemetryConfig:
    """Opt-in observability surfaces for one service run.  Everything
    defaults off; any combination is replay-safe (DESIGN.md §2.11)."""

    trace_path: str = ""        # Perfetto/Chrome JSONL sink; "" = no tracing
    profile_dir: str = ""       # jax.profiler window over the run; "" = off
    record_cap: int = 4096      # bound on every structured record log


def make_tracer(tcfg: Optional[TelemetryConfig],
                registry: Optional[Telemetry] = None):
    if tcfg is None or not tcfg.trace_path:
        return NULL_TRACER
    return Tracer(TraceWriter(tcfg.trace_path), registry)


# ---------------------------------------------------------------------------
# profiling hooks (opt-in; never on the replay path)
# ---------------------------------------------------------------------------
class Profiler:
    """A ``jax.profiler`` trace over one run, into ``profile_dir``.  Fully
    inert unless ``profile_dir`` is set; failures degrade to a one-time
    warning, never to a run error.  Chunks show in it by the tracer's
    per-dispatch annotation (:meth:`Tracer.annotation`)."""

    def __init__(self, profile_dir: str = ""):
        self.profile_dir = profile_dir
        self.active = False

    def start(self) -> None:
        if not self.profile_dir:
            return
        try:
            import jax
            jax.profiler.start_trace(self.profile_dir)
            self.active = True
        except Exception as e:
            log.warning("jax.profiler start failed (%s: %s) — profiling "
                        "disabled for this run", type(e).__name__, e)

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("jax.profiler stop failed (%s: %s)",
                        type(e).__name__, e)


# Peak (bf16 FLOP/s, HBM bytes/s, bytes/s per inter-chip link) per device,
# keyed by the lower-cased ``device_kind`` JAX reports (a v5e reports
# "TPU v5 lite", a v6e "TPU v6 lite", a v5p "TPU v5").  TPU rows: Google
# Cloud TPU documentation, system-architecture pages "TPU v4", "TPU v5e",
# "TPU v5p" and "TPU v6e" (peak compute, HBM bandwidth, and ICI bandwidth
# per chip divided over its links).  The cpu row prices CPU hosts only: a
# modest desktop-class estimate (AVX2 f32, dual-channel DDR4, UPI) so host
# rooflines stay finite.  A kind missing here is an error, never priced
# with another row.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "tpu v4":      dict(peak_flops=275e12, hbm_bw=1200e9, link_bw=50e9),
    "tpu v5 lite": dict(peak_flops=197e12, hbm_bw=819e9,  link_bw=50e9),
    "tpu v5":      dict(peak_flops=459e12, hbm_bw=2765e9, link_bw=100e9),
    "tpu v6 lite": dict(peak_flops=918e12, hbm_bw=1640e9, link_bw=112e9),
    "cpu":         dict(peak_flops=1e12,   hbm_bw=40e9,   link_bw=20e9),
}


def device_peaks(device_kind: Optional[str] = None,
                 override: Optional[Dict[str, float]] = None
                 ) -> Dict[str, float]:
    """The ``DEVICE_PEAKS`` row for ``device_kind`` (default: the running
    backend's ``jax.devices()[0].device_kind``); ``override`` keys replace
    resolved entries.  Raises ``KeyError`` for a kind not in the table."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).strip().lower()
    if kind not in DEVICE_PEAKS:
        raise KeyError(f"no peak row for device_kind {device_kind!r}; "
                       f"known kinds: {sorted(DEVICE_PEAKS)}")
    row = dict(DEVICE_PEAKS[kind])
    if override:
        row.update({k: float(v) for k, v in override.items()
                    if v is not None})
    return row


# ---------------------------------------------------------------------------
# compilations (one jax.monitoring listener per process)
# ---------------------------------------------------------------------------
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_WATCHERS: List[Tuple[Telemetry, Any]] = []
_WATCH_LOCK = threading.Lock()
_LISTENING = False


def _on_compile(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    with _WATCH_LOCK:
        watchers = list(_WATCHERS)
    if not watchers:
        return
    now = time.monotonic()
    for reg, tracer in watchers:
        reg.count("jax.compiles")
        tracer.complete_at("jax.compile", now - secs, now, cat="jax",
                           seconds=secs)


class compile_watch:
    """Count every XLA compilation while the block runs into ``registry``
    as ``jax.compiles``, and put a ``jax.compile`` span on the compiling
    thread of ``tracer``.  The listener is registered with
    ``jax.monitoring`` once per process; a compilation counts in every
    watch open at the time."""

    def __init__(self, registry: Telemetry, tracer=NULL_TRACER):
        self._entry = (registry, tracer)

    def __enter__(self) -> "compile_watch":
        global _LISTENING
        with _WATCH_LOCK:
            if not _LISTENING:
                import jax
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile)
                _LISTENING = True
            _WATCHERS.append(self._entry)
        return self

    def __exit__(self, *exc) -> bool:
        with _WATCH_LOCK:
            _WATCHERS.remove(self._entry)
        return False


# ---------------------------------------------------------------------------
# device work by phase: HLO instruction -> named scope
# ---------------------------------------------------------------------------
# the chunk program's named scopes (core/scheduler.py,
# core/sharded_stream.py, core/engines.py); an op takes the outermost one
# on its op_name path
PHASES = ("register", "restructure", "evaluate", "abort_repass", "commit",
          "exchange")
CONTAINER_OPS = ("while", "conditional", "call")

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(")
_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLEE_RE = re.compile(
    r"\b(body|condition|to_apply|true_computation|false_computation|calls)="
    r"%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _opcode(rhs: str) -> Tuple[str, List[str]]:
    """The opcode of an instruction's right-hand side (after its shape)
    and the names of its operands."""
    i = 0
    if rhs.startswith("("):             # tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    rest = rhs[i:].lstrip()
    k = rest.find("(")
    if k < 0:
        return rest, []
    depth, j = 0, k
    for j in range(k, len(rest)):
        depth += (rest[j] == "(") - (rest[j] == ")")
        if depth == 0:
            break
    return rest[:k], _OPERAND_RE.findall(rest[k:j])


def op_phase_table(hlo_text: str, phases: Sequence[str] = PHASES) -> Dict:
    """The device ops of a compiled program, each with its phase.

    Reads the program's compiled HLO text: the ops a device runs are the
    instructions of the entry computation and, through each ``while``,
    ``conditional`` and ``call``, of the computations those run (never
    the inside of a fusion).  An op's phase is the first of ``phases`` on
    its ``op_name`` path — scan and while bodies sit between the program's
    name and the phase.  An op that names none takes, if a fusion, its
    fused root's phase or else the first its fused instructions name;
    failing that, the phase of its first operand that has one (the
    compiler drops the metadata of some ops, such as a cumulative sum's
    ``reduce-window``); failing that ``"other"``.  Returns
    ``dict(program=<module name>, phases={op: phase}, containers=[ops
    whose time is their bodies'])``."""
    comps: Dict[str, List[Tuple]] = {}
    entry = cur = None
    program = ""
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            program = line.split()[1].rstrip(",")
            continue
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        m = _INSTR_RE.match(line)
        if m and cur is not None:
            rhs = m.group(3)
            op, operands = _opcode(rhs)
            comps[cur].append((m.group(2), op, operands, rhs,
                               bool(m.group(1))))

    def phase_of(rhs: str) -> str:
        m = _OPNAME_RE.search(rhs)
        path = m.group(1).split("/") if m else ()
        return next((p for p in path if p in phases), "other")

    def fused_phase(comp: str) -> str:
        instrs = sorted(comps.get(comp, ()), key=lambda i: not i[4])
        return next((p for p in (phase_of(i[3]) for i in instrs)
                     if p != "other"), "other")

    table: Dict[str, str] = {}
    known: Dict[str, str] = {}          # every instruction's phase so far
    containers: List[str] = []
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp is None or comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, op, operands, rhs, _ in comps[comp]:
            callees = [c for _, c in _CALLEE_RE.findall(rhs)]
            if op in CONTAINER_OPS:
                containers.append(name)
                todo += callees
                for grp in _BRANCHES_RE.findall(rhs):
                    todo += [c.strip().lstrip("%") for c in grp.split(",")]
                continue
            phase = phase_of(rhs)
            if phase == "other" and op == "fusion" and callees:
                phase = fused_phase(callees[0])
            if phase == "other":
                phase = next((known[o] for o in operands
                              if known.get(o, "other") != "other"), "other")
            table[name] = known[name] = phase
    return dict(program=program, phases=table, containers=sorted(containers))


# ---------------------------------------------------------------------------
# trace validation (the CI telemetry-smoke contract)
# ---------------------------------------------------------------------------
_VALID_PH = {"X", "i", "I", "C", "M", "B", "E"}


def _parse_trace(path: str) -> List[Dict]:
    """The events of a trace file.  A file without its closing ``]`` is a
    crashed run's prefix: a last line cut short by the crash is dropped;
    any other malformed line is an error."""
    with open(path) as f:
        raw = f.read()
    body = raw.strip()
    if body.startswith("["):
        body = body[1:]
    closed = body.rstrip().endswith("]")
    if closed:
        body = body.rstrip()[:-1]
    lines = body.splitlines()
    events = []
    for i, line in enumerate(lines, 1):
        line = line.strip().rstrip(",")
        if not line:
            continue
        try:
            events.append(json.loads(line))
        except ValueError as e:
            if not closed and i == len(lines):
                break
            raise ValueError(f"{path}:{i}: invalid trace event JSON: {e}")
    return events


def validate_trace(path: str, require_stages: Sequence[str] = ()
                   ) -> Tuple[bool, str, Dict]:
    """Validate a trace file against the Chrome trace event schema:
    every event needs ``name``/``ph``/``ts``/``pid``/``tid`` with sane
    types, ``X`` events need a non-negative ``dur``, ``M`` events a
    ``args.name``.  ``require_stages`` additionally demands a complete
    span for each named pipeline stage.  Returns ``(ok, why, info)``."""
    try:
        events = _parse_trace(path)
    except (OSError, ValueError) as e:
        return False, str(e), dict(n_events=0, stages=[])
    if not events:
        return False, "empty trace", dict(n_events=0, stages=[])
    for i, ev in enumerate(events):
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            return False, f"event {i}: missing name", {}
        ph = ev.get("ph")
        if ph not in _VALID_PH:
            return False, f"event {i} ({ev['name']}): bad ph {ph!r}", {}
        if not isinstance(ev.get("ts"), (int, float)) or ev["ts"] < 0:
            return False, f"event {i} ({ev['name']}): bad ts", {}
        for k in ("pid", "tid"):
            if not isinstance(ev.get(k), int):
                return False, f"event {i} ({ev['name']}): bad {k}", {}
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                return False, f"event {i} ({ev['name']}): X needs dur", {}
        if ph == "M" and not (ev.get("args") or {}).get("name"):
            return False, f"event {i}: M needs args.name", {}
    stages = sorted({ev["name"] for ev in events
                     if ev.get("ph") == "X"
                     and ev.get("cat") in ("pipeline", "ckpt")})
    missing = [s for s in require_stages if s not in stages]
    info = dict(n_events=len(events), stages=stages)
    if missing:
        return False, f"missing pipeline stages: {missing}", info
    return True, "ok", info


def stage_summary(path: str) -> List[Dict]:
    """Per-stage duration table from a trace file (count, total, mean,
    p50/p99 in ms) — the ``report.py --trace`` view."""
    events = _parse_trace(path)
    by: Dict[str, List[float]] = {}
    for ev in events:
        if ev.get("ph") == "X":
            by.setdefault(ev["name"], []).append(float(ev["dur"]))
    rows = []
    for name in sorted(by):
        durs = np.asarray(by[name], np.float64) / 1e3   # µs -> ms
        rows.append(dict(
            stage=name, count=int(durs.size),
            total_ms=float(durs.sum()), mean_ms=float(durs.mean()),
            p50_ms=float(np.percentile(durs, 50)),
            p99_ms=float(np.percentile(durs, 99))))
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def _main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        description="validate a Perfetto/Chrome trace emitted by the "
                    "service telemetry plane")
    p.add_argument("trace", help="trace JSONL path")
    p.add_argument("--require-stages", default="",
                   help="comma-separated span names that must be present")
    p.add_argument("--summary", action="store_true",
                   help="print the per-stage duration table")
    args = p.parse_args(argv)
    stages = [s for s in args.require_stages.split(",") if s]
    ok, why, info = validate_trace(args.trace, require_stages=stages)
    print(f"{args.trace}: {'OK' if ok else 'INVALID'} ({why}); "
          f"{info.get('n_events', 0)} events, "
          f"stages={info.get('stages', [])}")
    if ok and args.summary:
        for r in stage_summary(args.trace):
            print(f"  {r['stage']:<20} n={r['count']:>5} "
                  f"total={r['total_ms']:>10.2f}ms p50={r['p50_ms']:.3f}ms "
                  f"p99={r['p99_ms']:.3f}ms")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(_main())
