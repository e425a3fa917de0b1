"""Benchmark driver: one module per paper figure + the roofline table.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement), saves
the full JSON to results/bench/, and mirrors each module's rows to a
machine-readable ``BENCH_<name>.json`` at the repo root (perf trajectory
for successive PRs — DESIGN.md §8.3).
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-size workloads (slower)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (bit-rot canary)")
    ap.add_argument("--only", default="",
                    help="comma list: fig8,fig9,fig10,fig11,fig12,fig13,"
                         "fig14,roofline,fused_stream,sharded_stream,"
                         "restructure,service,adaptive,reshard")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()

    from . import (adaptive_storm, fig8_throughput, fig9_breakdown,
                   fig10_multipartition, fig11_workload, fig12_interval,
                   fig13_latency, fig14_numa, fused_stream,
                   reshard_storm, restructure_bench, roofline,
                   service_latency, sharded_stream)
    modules = dict(fig8=fig8_throughput, fig9=fig9_breakdown,
                   fig10=fig10_multipartition, fig11=fig11_workload,
                   fig12=fig12_interval, fig13=fig13_latency,
                   fig14=fig14_numa, roofline=roofline,
                   fused_stream=fused_stream,
                   sharded_stream=sharded_stream,
                   restructure=restructure_bench,
                   service=service_latency,
                   adaptive=adaptive_storm,
                   reshard=reshard_storm)
    only = set(args.only.split(",")) if args.only else set(modules)

    os.makedirs("results/bench", exist_ok=True)
    all_rows = []
    print("name,us_per_call,derived")
    for name, mod in modules.items():
        if name not in only:
            continue
        kwargs = dict(quick=quick)
        if "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = args.smoke
        try:
            rows = mod.run(**kwargs)
        except Exception as e:
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            continue
        all_rows.extend(rows)
        for r in rows:
            us = r.get("wall_s",
                       r.get("median_wall_s",
                             r.get("measured_1dev_s",
                                   r.get("total_s",
                                         r.get("p99_latency_s", 0.0))))) * 1e6
            key = "/".join(str(r[k]) for k in
                           ("fig", "app", "scheme", "layout", "driver",
                            "arch", "shape", "width", "interval",
                            "mp_ratio", "mp_len", "read_ratio", "theta",
                            "mesh", "n_dev", "fused", "scenario", "plan",
                            "phase")
                           if k in r)
            derived = r.get("events_per_s",
                            r.get("roofline_frac",
                                  r.get("wire_bytes_per_device", "")))
            print(f"{key},{us:.1f},{derived}", flush=True)
        with open(f"results/bench/{name}.json", "w") as f:
            json.dump(rows, f, indent=2, default=str)
        with open(f"BENCH_{name}.json", "w") as f:
            json.dump(rows, f, indent=2, default=str)
    with open("results/bench/all.json", "w") as f:
        json.dump(all_rows, f, indent=2, default=str)


if __name__ == "__main__":
    main()
