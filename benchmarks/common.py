"""Benchmark utilities: wall-clock timing + the executor cost model.

Two complementary views reproduce the paper's multicore figures on TPU-
style hardware (DESIGN.md §8.1):

1. **Measured**: actual jitted wall time of each engine on the real
   workload (CPU here; the schedules' *structure* — sequential scan vs
   parallel segmented scan — dominates the comparison).

2. **Modeled width scaling** (the paper's x-axis is cores): Brent's law
   over the *measured schedule structure*:  T(width) ≈ (depth + work/width)
   · t_op + sync.  depth/work come from the engine's EngineStats on the
   actual workload — the model is data-driven, not fabricated; t_op is
   calibrated from the measured sequential (LOCK) wall time.
"""
from __future__ import annotations

import os
import subprocess
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blotter import build_opbatch
from repro.core.engines import evaluate


def cpu_worker(cmd: List[str], *, timeout: float):
    """Run a forced-host-device worker on the CPU backend.

    The sharded workers build an 8-device mesh out of host CPU devices.
    With ``JAX_PLATFORMS=cpu`` in its environment such a child never
    reaches for an accelerator, which on a chip host is already held by
    the parent process (and offers 1 or 4 devices, not 8).  Rows it
    returns are labelled with :func:`cpu_rows`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)


def cpu_rows(rows: List[Dict]) -> List[Dict]:
    """Label rows measured by a :func:`cpu_worker` child."""
    return [dict(r, platform="cpu") for r in rows]


def wall_time(fn: Callable, *args, iters: int = 5) -> float:
    """Median wall seconds of a jitted call (post-warmup)."""
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def stream_wall_time_pair(engine, values, event_stream, interval: int, *,
                          iters: int = 9):
    """((min, median) unfused, (min, median) fused) wall seconds, measured
    **interleaved** so drift in machine load lands on both drivers equally
    — an A/B wall-clock comparison, not two separate absolute
    measurements.  The *minimum* is the headline estimator: external load
    only ever adds time, so min estimates the intrinsic cost (the same
    rationale as ``timeit``; DESIGN.md §8.3).  The median is reported
    alongside for context.
    """
    for fused in (False, True):  # warm both compiles before timing either
        jax.block_until_ready(
            engine.run_stream(values, event_stream, interval, fused=fused))
    tu, tf = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(
            engine.run_stream(values, event_stream, interval, fused=False))
        tu.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(
            engine.run_stream(values, event_stream, interval, fused=True))
        tf.append(time.perf_counter() - t0)
    return ((float(np.min(tu)), float(np.median(tu))),
            (float(np.min(tf)), float(np.median(tf))))


def engine_stats(app, store, events, scheme: str, **kw):
    """Run one interval, return (stats, wall_seconds, results)."""
    ops, _ = build_opbatch(app, store, events, jnp.int32(0))

    def run(values, ops):
        import dataclasses
        st = dataclasses.replace(store, values=values)
        res, vals, stats = evaluate(st, ops, app.funs, scheme,
                                    associative_only=app.associative_only,
                                    has_gates=app.has_gates, **kw)
        return res, vals, stats

    jitted = jax.jit(run)
    secs = wall_time(jitted, store.values, ops)
    res, vals, stats = jitted(store.values, ops)
    return jax.device_get(stats), secs, res


SYNC_OPS = 50.0          # barrier/mode-switch cost in op-units per interval
SORT_FACTOR = 0.15       # sort work per op relative to a state access


def modeled_time(stats, scheme: str, width: int, n_events: int,
                 t_op: float) -> float:
    """Brent's-law executor model over the measured schedule structure."""
    n_ops = float(stats.n_ops)
    depth = float(stats.rounds)
    if scheme in ("tstream", "tstream_scan", "tstream_lockstep", "mvlk"):
        work = n_ops * (1.0 + SORT_FACTOR * np.log2(max(n_ops, 2)) / 10)
    else:
        work = n_ops
    if scheme == "lock":
        # coarse-grained: one txn at a time holds the lock pipeline
        t = depth + 0.25 * work / width
    elif scheme == "nolock":
        t = work / width
    else:
        t = depth + work / width
    t = t + SYNC_OPS
    return t * t_op


def throughput_model(app, store, events, schemes, widths, **kw) -> Dict:
    """events/sec per (scheme, width), calibrated on LOCK's measured time."""
    n_events = len(next(iter(events.values())))
    stats_l, secs_l, _ = engine_stats(app, store, events, "lock")
    t_op = secs_l / max(float(stats_l.rounds), 1.0)
    out = {}
    for scheme in schemes:
        stats, secs, _ = engine_stats(app, store, events, scheme, **kw)
        out[scheme] = dict(
            measured_1dev_s=secs,
            rounds=float(stats.rounds),
            n_chains=float(stats.n_chains),
            max_chain=float(stats.max_chain),
            by_width={w: n_events / modeled_time(stats, scheme, w, n_events,
                                                 t_op)
                      for w in widths},
        )
    return out
