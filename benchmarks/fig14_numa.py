"""Fig. 14 analogue: NUMA-aware configurations -> chain-shard layouts.

Runs in a CPU subprocess (the layouts need an 8-device placeholder mesh
while the rest of the suite sees the real device); its rows are labelled
``platform: cpu``."""
from __future__ import annotations

import json
import os
import sys

from .common import cpu_worker


def run_reshard(quick: bool = True, smoke: bool = False):
    """Skew-storm A/B (DESIGN.md §2.10): static provisioning vs elastic
    resharding through a calm -> aligned-Zipf ramp -> theta=2.5 peak ->
    calm storm.  Rows interleave the static and elastic plans per storm
    phase; the elastic peak row carries its speedup over the never-drops
    static-slack8 baseline."""
    worker = os.path.join(os.path.dirname(__file__), "fig14_numa_worker.py")
    size = "smoke" if smoke else ("quick" if quick else "full")
    proc = cpu_worker([sys.executable, worker, "reshard", size],
                      timeout=1800)
    if proc.returncode != 0:
        return [dict(fig="reshard", error=proc.stderr[-500:])]
    raw = json.loads(proc.stdout.strip().splitlines()[-1])
    base = {r["phase"]: r for r in raw if r["plan"] == "static-slack8"}
    rows = []
    # interleave: phase-major, static rows before the elastic row
    order = {p: i for i, p in enumerate(
        ("calm", "ramp", "peak", "cooldown", "all"))}
    for r in sorted(raw, key=lambda r: (order.get(r["phase"], 99),
                                        r["elastic"], -r["slack"])):
        r = dict(r, fig="reshard", app="gs", kind="reshard", size=size,
                 platform="cpu")
        b = base.get(r["phase"])
        if r["elastic"] and b and b["events_per_s"] > 0:
            r["speedup_vs_static"] = r["events_per_s"] / b["events_per_s"]
        rows.append(r)
    return rows


def run(quick: bool = True):
    worker = os.path.join(os.path.dirname(__file__), "fig14_numa_worker.py")
    proc = cpu_worker([sys.executable, worker], timeout=900)
    if proc.returncode != 0:
        return [dict(fig="fig14", error=proc.stderr[-500:])]
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = []
    for layout, d in data.items():
        rows.append(dict(fig="fig14", app="gs", layout=layout,
                         platform="cpu", correct=d["correct"],
                         wall_s=d["wall_s"],
                         wire_bytes_per_device=d["wire_bytes_per_device"],
                         fused_bit_identical=d["fused_bit_identical"],
                         fused_wall_s=d["fused_wall_s"],
                         fused_events_per_s=d["fused_events_per_s"],
                         fused_dropped=d["fused_dropped"],
                         fused_exchange_capacity=d["fused_exchange_capacity"]))
    return rows
