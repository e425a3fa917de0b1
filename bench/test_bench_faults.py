"""A run whose timed path is broken reads ``correct`` false.

Each case drives the whole harness (``bench/run.py``'s ``run_cell``) on
the CPU, past its look for a chip, for a one-second window, with one
fault planted in the program underneath:

* ``state_unchanged``: each chunk returns the table it was given;
* ``half_batch``: the second half of every interval is replaced by its
  first half before the chunk program sees it;
* ``answer_altered``: one output of every committed chunk is changed
  where the program produces it;
* ``no_exchange``: on four virtual devices, the all-to-all that routes ops
  to their owners leaves them where they are (the 4-chip cell only);
* ``snapshot_skipped``: every other snapshot is not written (the run
  still reports it published);
* ``snapshot_stale``: each snapshot is written with the table of the one
  before it.

The unbroken run must read ``correct`` true.  Cases run in a child
process (``python bench/test_bench_faults.py <cell> <fault>...``), which
keeps JAX's compilation cache settings of the harness out of the test
process.
"""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def plant(fault, cfg):
    """Patch the program for ``fault``; returns the undo callable."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.scheduler import DualModeEngine
    if fault == "none":
        return lambda: None
    if fault == "state_unchanged":
        orig = DualModeEngine.run_stream_chunk

        def stuck(self, values, batched, ts0, variant=None):
            before = jnp.array(values, copy=True)
            res, ebs, _, st = orig(self, values, batched, ts0, variant)
            return res, ebs, before, st
        DualModeEngine.run_stream_chunk = stuck
        return lambda: setattr(DualModeEngine, "run_stream_chunk", orig)
    if fault == "half_batch":
        orig = DualModeEngine.run_stream_chunk

        def half(self, values, batched, ts0, variant=None):
            h = cfg["punct_interval"] // 2
            batched = {k: v.at[:, h:2 * h].set(v[:, :h])
                       for k, v in batched.items()}
            return orig(self, values, batched, ts0, variant)
        DualModeEngine.run_stream_chunk = half
        return lambda: setattr(DualModeEngine, "run_stream_chunk", orig)
    if fault == "answer_altered":
        orig = DualModeEngine.post_outputs

        def altered(self, res_all, ebs_all, n_intervals):
            outs = orig(self, res_all, ebs_all, n_intervals)
            key = cfg["compare"]["outputs"][0]
            col = np.array(outs[0][key], copy=True)
            col.reshape(-1)[0] += 1.0
            outs[0] = dict(outs[0], **{key: col})
            return outs
        DualModeEngine.post_outputs = altered
        return lambda: setattr(DualModeEngine, "post_outputs", orig)
    if fault in ("snapshot_skipped", "snapshot_stale"):
        import repro.runtime.service as service
        orig = service.save_checkpoint
        calls = []

        def broken(ckpt_dir, step, tree, *a, **k):
            calls.append(tree)
            if fault == "snapshot_skipped":
                if len(calls) % 2:
                    return orig(ckpt_dir, step, tree, *a, **k)
                return os.path.join(ckpt_dir, f"step_{step:08d}")
            return orig(ckpt_dir, step, calls[max(0, len(calls) - 2)],
                        *a, **k)
        service.save_checkpoint = broken
        return lambda: setattr(service, "save_checkpoint", orig)
    if fault == "no_exchange":
        orig = jax.lax.all_to_all
        jax.lax.all_to_all = lambda x, *a, **k: x
        return lambda: setattr(jax.lax, "all_to_all", orig)
    raise ValueError(fault)


def extra(config, cell, traffic, chips, why):
    return (dict(name=config, source="https://arxiv.org/abs/1904.03800",
                 file=f"bench/configs/{config}.json", reduced=[], why=why),
            dict(name=cell, config=config, traffic=traffic, chips=chips,
                 why=why))


# cells whose files are ready but that are not in BENCHMARK.json yet
# (PERF.md, Open questions); the harness runs them all the same
EXTRA = {c[1]["name"]: c for c in (
    extra("gs_mp4", "gs_mp4.backlog", "backlog", 4, "4 chips"),
    extra("gs_late64", "gs_late64.jitter64", "backlog_jitter64", 1,
          "out of order"),
    extra("gs_paper", "gs_paper.bursts", "gs_bursts", 1, "bursts"),
    extra("gs_storm4", "gs_storm4.backlog", "backlog", 4, "skew storm"))}


def child(cell, faults):
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import run
    from benchlib import spec
    bench_spec = spec.load_spec()
    if cell in EXTRA:
        config, entry = EXTRA[cell]
        if config["name"] not in {c["name"] for c in bench_spec["configs"]}:
            bench_spec["configs"].append(config)
        bench_spec["workloads"].append(entry)
    cfg = spec.Cell(bench_spec, cell).cfg
    out = {}
    seconds = os.environ.get("BENCH_TEST_SECONDS", "1")
    for i, fault in enumerate(faults):
        undo = plant(fault, cfg)
        try:
            line, info = run.run_cell(
                ["--workload", cell, "--seed", str(2 ** 31 + 17 + i),
                 "--seconds", seconds, "--trace", "0"],
                require_chip=False, bench_spec=bench_spec)
        finally:
            undo()
        out[fault] = dict(correct=line["correct"], checks=line["checks"],
                          info=info)
    print(json.dumps(out, default=str))


def run_child(tmp_path, cell, faults, devices=1, seconds=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_TEST_SECONDS=str(seconds),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.path.join(ROOT, "src"))
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices}").strip()
    p = subprocess.run([sys.executable, os.path.abspath(__file__), cell,
                        *faults], env=env, cwd=ROOT, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_faults_read_not_correct_one_chip(tmp_path):
    faults = ["none", "state_unchanged", "half_batch", "answer_altered",
              "snapshot_skipped", "snapshot_stale"]
    got = run_child(tmp_path, "gs_paper.backlog", faults)
    assert got["none"]["correct"], got["none"]
    for f in faults[1:]:
        assert not got[f]["correct"], (f, got[f])


def test_sl_fault_reads_not_correct(tmp_path):
    got = run_child(tmp_path, "sl_paper.backlog",
                    ["none", "state_unchanged", "snapshot_stale"])
    assert got["none"]["correct"], got["none"]
    assert not got["state_unchanged"]["correct"]
    assert not got["snapshot_stale"]["correct"], got["snapshot_stale"]


def test_exchange_left_out_reads_not_correct(tmp_path):
    got = run_child(tmp_path, "gs_mp4.backlog", ["none", "no_exchange"],
                    devices=4)
    assert got["none"]["correct"], got["none"]
    assert not got["no_exchange"]["correct"], got["no_exchange"]


if __name__ == "__main__":
    child(sys.argv[1], sys.argv[2:])
