"""Autotuned kernel dispatch: cache, keying, disk round-trip, forcing.

The contract under test (DESIGN.md §2.8):
* one microbenchmark per (kernel, shape-bucket, dtype, device_kind) per
  process — cache hits never re-bench;
* ``device_kind`` is part of the key (a decision tuned on one device
  kind never leaks to another);
* decisions round-trip through the on-disk JSON cache, and a warm disk
  cache makes dispatch deterministic with zero benching;
* ``force=`` bypasses the cache entirely (both directions), and
  ``EngineConfig.kernel_block_params`` pins block parameters all the way
  through the fused driver without consulting the autotuner.
"""
import json

import numpy as np
import pytest

from repro.kernels import autotune


@pytest.fixture(autouse=True)
def fresh_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def make_bench(table=None, log=None):
    """bench_fn stub: records calls, returns scripted timings."""
    calls = [] if log is None else log

    def bench(c):
        calls.append(c)
        return float(table.get(c, 1.0)) if table else 1.0

    bench.calls = calls
    return bench


def test_microbench_picks_fastest_candidate():
    bench = make_bench({256: 5e-6, 128: 1e-6, 512: 5e-6, 1024: 5e-6})
    d = autotune.decide("segscan", 1 << 12, bench_fn=bench,
                        interpret=False, device_kind="testkind")
    assert d.source == "microbench"
    assert d.param == 128
    assert set(map(int, d.timings_us)) == set(d.candidates)


def test_cached_decision_reused_without_rebench():
    bench = make_bench()
    d1 = autotune.decide("segscan", 1000, bench_fn=bench,
                         interpret=False, device_kind="testkind")
    assert d1.source == "microbench" and bench.calls
    n_calls = len(bench.calls)
    # 900 and 1000 share the 2^10 shape bucket -> pure cache hit
    d2 = autotune.decide("segscan", 900, bench_fn=bench,
                         interpret=False, device_kind="testkind")
    assert d2 is d1
    assert len(bench.calls) == n_calls
    # a different bucket re-benches once
    autotune.decide("segscan", 5000, bench_fn=bench,
                    interpret=False, device_kind="testkind")
    assert len(bench.calls) > n_calls


def test_device_kind_is_part_of_the_key():
    bench_a = make_bench({256: 1e-6, 128: 5e-6, 512: 5e-6, 1024: 5e-6})
    bench_b = make_bench({256: 5e-6, 128: 5e-6, 512: 1e-6, 1024: 5e-6})
    da = autotune.decide("segscan", 1 << 12, bench_fn=bench_a,
                         interpret=False, device_kind="kind-a")
    db = autotune.decide("segscan", 1 << 12, bench_fn=bench_b,
                         interpret=False, device_kind="kind-b")
    assert da.key != db.key
    assert (da.param, db.param) == (256, 512)
    # both live in the cache simultaneously
    assert autotune.decide("segscan", 1 << 12, interpret=False,
                           device_kind="kind-a").param == 256
    assert autotune.decide("segscan", 1 << 12, interpret=False,
                           device_kind="kind-b").param == 512


def test_interpret_default_is_deterministic_and_matches_shipped_shapes():
    # interpret mode never times anything: the decision is the first
    # candidate == the hand-validated shipped constant, every process
    for kernel, shipped in (("segscan", 256), ("radix_partition", 256),
                            ("hash_probe", 128), ("megakernel", 4096)):
        d = autotune.decide(kernel, 1 << 12, interpret=True,
                            device_kind="testkind")
        assert d.source == "interpret-default"
        assert d.param == shipped
        assert autotune.decide(kernel, 1 << 12, interpret=True,
                               device_kind="testkind").param == shipped


def test_disk_cache_round_trip(tmp_path):
    path = str(tmp_path / "tune.json")
    bench = make_bench({256: 5e-6, 128: 1e-6, 512: 5e-6, 1024: 5e-6})
    d1 = autotune.decide("segscan", 1 << 12, bench_fn=bench,
                         interpret=False, device_kind="testkind",
                         cache_path=path)
    assert d1.param == 128
    with open(path) as f:
        stored = json.load(f)["decisions"]
    assert any(r["param"] == 128 and r["kernel"] == "segscan"
               for r in stored)

    # a fresh process (cleared cache) with the same disk cache must make
    # the SAME decision without benching at all
    autotune.clear_cache()
    bench2 = make_bench({256: 1e-6, 128: 9e-6, 512: 9e-6, 1024: 9e-6})
    d2 = autotune.decide("segscan", 1 << 12, bench_fn=bench2,
                         interpret=False, device_kind="testkind",
                         cache_path=path)
    assert d2.source == "disk"
    assert d2.param == 128
    assert not bench2.calls


def test_disk_cache_ignores_garbage(tmp_path):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        f.write("{not json")
    d = autotune.decide("segscan", 1 << 12, interpret=True,
                        device_kind="testkind", cache_path=path)
    assert d.param == 256  # fell through to the default, no crash


def test_forced_override_beats_cache_and_never_benches():
    d1 = autotune.decide("segscan", 1 << 9, interpret=True,
                         device_kind="testkind")
    bench = make_bench()
    d2 = autotune.decide("segscan", 1 << 9, force=192, bench_fn=bench,
                         interpret=False, device_kind="testkind")
    assert d2.source == "forced" and d2.param == 192
    assert not bench.calls
    # the cache is untouched by the forced call
    d3 = autotune.decide("segscan", 1 << 9, interpret=True,
                         device_kind="testkind")
    assert d3.param == d1.param
    assert autotune.block_rows("segscan", 1 << 9, force=64) == 64


def test_decisions_logged_once_per_key(caplog):
    import logging
    with caplog.at_level(logging.INFO, logger="repro.kernels.autotune"):
        autotune.decide("segscan", 1 << 12, interpret=True,
                        device_kind="testkind")
        autotune.decide("segscan", 1 << 12, interpret=True,
                        device_kind="testkind")
    hits = [r for r in caplog.records if "autotune:" in r.getMessage()]
    assert len(hits) == 1


def test_decision_log_artifact(tmp_path, monkeypatch):
    logp = str(tmp_path / "decisions.jsonl")
    monkeypatch.setenv("REPRO_AUTOTUNE_LOG", logp)
    autotune.decide("segscan", 1 << 12, interpret=True,
                    device_kind="testkind")
    autotune.decide("hash_probe", 1 << 10, dtype="int32", interpret=True,
                    device_kind="testkind")
    with open(logp) as f:
        recs = [json.loads(line) for line in f]
    assert {r["kernel"] for r in recs} == {"segscan", "hash_probe"}


def test_engineconfig_pins_block_params_without_autotune(monkeypatch):
    """The fused driver with every block parameter pinned via
    ``EngineConfig.kernel_block_params`` must never consult the
    autotuner — and pinning the defaults reproduces the default run
    bit for bit."""
    from repro.apps import ALL_APPS
    from repro.core.scheduler import DualModeEngine, EngineConfig

    app = ALL_APPS["gs"]
    rng = np.random.default_rng(3)
    stream = app.gen_events(rng, 64)
    store = app.make_store()

    ref_eng = DualModeEngine(app, store, EngineConfig(use_pallas=True))
    outs_ref, vals_ref = ref_eng.run_stream(store.values, stream, 16,
                                            fused=True)

    def boom(*a, **kw):  # any lookup is a pin violation
        raise AssertionError("autotune consulted despite pinned params")

    monkeypatch.setattr(autotune, "block_rows", boom)
    cfg = EngineConfig(use_pallas=True,
                       kernel_block_params=(("segscan", 256),
                                            ("radix_partition", 256),
                                            ("hash_probe", 128)))
    assert cfg.block_param("segscan") == 256
    assert cfg.block_param("megakernel") is None
    eng = DualModeEngine(app, store, cfg)
    outs, vals = eng.run_stream(store.values, stream, 16, fused=True)

    np.testing.assert_array_equal(np.asarray(vals), np.asarray(vals_ref))
    for a, b in zip(outs, outs_ref):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


def test_decide_under_jit_takes_default_and_never_persists(tmp_path):
    """Inside a trace no kernel can run, so a timing would measure
    tracing: the decision is the default candidate, never written to the
    disk cache."""
    import jax
    import jax.numpy as jnp
    path = tmp_path / "tune.json"
    bench = make_bench({256: 5e-6, 128: 1e-6, 512: 5e-6, 1024: 5e-6})
    seen = []

    def traced(x):
        seen.append(autotune.decide("segscan", 1 << 12, bench_fn=bench,
                                    interpret=False, device_kind="testkind",
                                    cache_path=str(path)))
        return x

    jax.jit(traced)(jnp.ones(3))
    assert seen[0].source == "default"
    assert seen[0].param == seen[0].candidates[0]
    assert not bench.calls
    assert not path.exists()
    assert all(d["source"] != "microbench" for d in autotune.decisions_log())


def test_device_tables_keyed_by_reported_kind():
    # a v5e reports "TPU v5 lite"; prefixes of other kinds never match
    assert (autotune.ladder_bounds("TPU v5 lite")
            == autotune.LADDER_BOUNDS["tpu v5 lite"])
    assert (autotune.mega_bounds("TPU v5 lite")
            == autotune.MEGA_BOUNDS["tpu v5 lite"])
    for kind in ("TPU v5e", "tpu v7x", "gpu"):
        with pytest.raises(KeyError):
            autotune.ladder_bounds(kind)
        with pytest.raises(KeyError):
            autotune.mega_bounds(kind)
