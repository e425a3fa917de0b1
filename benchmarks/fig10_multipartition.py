"""Fig. 10 analogue: PAT vs TStream under multi-partition transactions (GS).

Two views: the modeled single-device PAT-vs-TStream comparison (paper
figure), plus **measured** fused sharded streaming rows across the same
mp_ratio/mp_len grid on an 8-device shared-nothing mesh (CPU subprocess
worker, rows labelled ``platform: cpu``; exchange drops accounted per
row)."""
from __future__ import annotations

import json
import os
import sys

import jax.numpy as jnp
import numpy as np

from repro.apps import ALL_APPS

from .common import cpu_rows, cpu_worker, throughput_model

WIDTH = 40


def _sharded_rows(quick: bool):
    worker = os.path.join(os.path.dirname(__file__), "fig10_worker.py")
    cmd = [sys.executable, worker] + ([] if quick else ["--full"])
    proc = cpu_worker(cmd, timeout=1800)
    if proc.returncode != 0:
        return [dict(fig="fig10", error=proc.stderr[-500:])]
    return cpu_rows(json.loads(proc.stdout.strip().splitlines()[-1]))


def run(quick: bool = True):
    n_events = 300 if quick else 1000
    app = ALL_APPS["gs"]
    rows = []
    n_partitions = 16
    for mp_ratio in [0.0, 0.25, 0.5, 0.75, 1.0]:
        rng = np.random.default_rng(10)
        store = app.make_store()
        events = {k: jnp.asarray(v) for k, v in app.gen_events(
            rng, n_events, n_partitions=n_partitions, mp_ratio=mp_ratio,
            mp_len=6).items()}
        res = throughput_model(app, store, events, ["tstream", "pat"],
                               [WIDTH], n_partitions=n_partitions)
        for scheme, d in res.items():
            rows.append(dict(fig="fig10a", app="gs", scheme=scheme,
                             mp_ratio=mp_ratio,
                             events_per_s=d["by_width"][WIDTH],
                             rounds=d["rounds"]))
    for mp_len in [2, 4, 6, 8, 10]:
        rng = np.random.default_rng(11)
        store = app.make_store()
        events = {k: jnp.asarray(v) for k, v in app.gen_events(
            rng, n_events, n_partitions=n_partitions, mp_ratio=0.5,
            mp_len=mp_len).items()}
        res = throughput_model(app, store, events, ["tstream", "pat"],
                               [WIDTH], n_partitions=n_partitions)
        for scheme, d in res.items():
            rows.append(dict(fig="fig10b", app="gs", scheme=scheme,
                             mp_len=mp_len,
                             events_per_s=d["by_width"][WIDTH],
                             rounds=d["rounds"]))
    rows.extend(_sharded_rows(quick))
    return rows
