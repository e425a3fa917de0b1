"""Sharded fused streaming throughput (DESIGN.md §2.5).

events/sec per chain-shard layout × device count for the owner-routed
fused sharded ``run_stream``, against the single-device fused driver and
the replicate-everything per-batch ``evaluate_sharded`` loop it replaces,
plus per-layout collective bytes and exchange padding/drop accounting.
Runs in a CPU subprocess (needs an 8-device placeholder mesh; rows are
labelled ``platform: cpu``); rows land in ``BENCH_sharded_stream.json``
via ``benchmarks/run.py``.
"""
from __future__ import annotations

import json
import os
import sys

from .common import cpu_rows, cpu_worker


def run(quick: bool = True, smoke: bool = False):
    worker = os.path.join(os.path.dirname(__file__),
                          "sharded_stream_worker.py")
    cmd = [sys.executable, worker]
    if smoke:
        cmd.append("--smoke")
    elif not quick:
        cmd.append("--full")
    proc = cpu_worker(cmd, timeout=3600)
    if proc.returncode != 0:
        return [dict(fig="sharded_stream", error=proc.stderr[-800:])]
    return cpu_rows(json.loads(proc.stdout.strip().splitlines()[-1]))
