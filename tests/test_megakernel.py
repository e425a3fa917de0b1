"""Fused partition→segscan→commit megakernel (DESIGN.md §2.8).

The megakernel rung replaces the staged ``plan → coefs → execute``
chain-evaluation pipeline with one dispatch.  Its admission contract:

1. **Bit-identical** to the staged partition path — at the unit level
   (``fused_chain_eval`` XLA ref AND Pallas interpret kernel vs the
   staged pipeline on odd shapes: non-multiple-of-lane N, single chain,
   all-pad, skewed buckets, n=1) and at the engine level (all four apps
   × tstream/mvlk × XLA/Pallas, ``restructure_method="megakernel"`` vs
   ``"partition"``), plus the sharded driver (subprocess, 8 host
   devices).
2. Forcing the rung on an ineligible store (max-typed tables) falls back
   to the staged path with a one-time warning — never wrong results.
3. ``mega_kernel_fits`` routes oversized intervals to the XLA ref.
"""
import dataclasses
import json
import logging
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.core.engines import (simple_affine_luts, tstream_scan_coefs,
                                tstream_scan_execute, tstream_scan_plan)
from repro.core.restructure import megakernel_engaged, restructure
from repro.core.scheduler import DualModeEngine, EngineConfig
from repro.core.types import (F_ADD, F_MAX, F_NOP, F_PUT, F_READ, OpBatch,
                              make_store)
from repro.kernels.megakernel import fused_chain_eval, mega_kernel_fits

FUNS = (F_NOP, F_READ, F_PUT, F_ADD)


def mk_batch(uid, valid, n_slots, *, w=2, max_ops=4, seed=None):
    """Row-major (ts, slot) batch over the simple-affine fun family."""
    n = uid.shape[0]
    idx = np.arange(n, dtype=np.int32)
    rng = np.random.default_rng(n if seed is None else seed)
    return OpBatch(
        uid=jnp.asarray(uid.astype(np.int32)),
        ts=jnp.asarray(idx // max_ops), txn=jnp.asarray(idx // max_ops),
        slot=jnp.asarray(idx % max_ops),
        kind=jnp.zeros((n,), jnp.int32),
        fun=jnp.asarray(rng.integers(0, len(FUNS), n).astype(np.int32)),
        gate=jnp.full((n,), -1, jnp.int32),
        operand=jnp.asarray(rng.normal(size=(n, w)).astype(np.float32)),
        valid=jnp.asarray(valid))


def staged_pipeline(store, ops, pad_uid):
    """The rung the megakernel must reproduce bit for bit."""
    pres = restructure(ops, pad_uid, rowmajor_ts=True, light=True,
                       method="partition")
    plan = tstream_scan_plan(store, ops, FUNS, prestructured=pres)
    plan = tstream_scan_coefs(plan, use_pallas=False)
    res, vals, _ = tstream_scan_execute(store.values, plan, pad_uid,
                                        raw=True)
    return res, vals


def assert_fused_matches_staged(uid, valid, n_slots, *, seed=None):
    store = make_store([n_slots], 2)
    pad_uid = store.pad_uid
    ops = mk_batch(uid, valid, n_slots, seed=seed)
    res_ref, vals_ref = staged_pipeline(store, ops, pad_uid)
    a_lut, b_lut = simple_affine_luts(FUNS)
    sops, ch = restructure(ops, pad_uid, rowmajor_ts=True, light=True,
                           method="partition", geometry=False)
    assert ch.seg_id is None and ch.pos is None  # the light mega plan
    for use_pallas in (False, True):
        res, vals, stats = fused_chain_eval(
            store.values, sops, ch, pad_uid, a_lut=a_lut, b_lut=b_lut,
            use_pallas=use_pallas, interpret=True)
        tag = "pallas" if use_pallas else "ref"
        np.testing.assert_array_equal(np.asarray(vals),
                                      np.asarray(vals_ref),
                                      err_msg=f"values ({tag})")
        for k in res_ref:
            np.testing.assert_array_equal(np.asarray(res[k]),
                                          np.asarray(res_ref[k]),
                                          err_msg=f"{k} ({tag})")
        assert stats.path == "megakernel"


# ---------------------------------------------------------------------------
# unit level: odd shapes, both dispatch arms
# ---------------------------------------------------------------------------
def test_fused_odd_n_skewed_buckets():
    rng = np.random.default_rng(7)
    n, s = 160, 37                       # n not a multiple of 128 lanes
    w = 1.0 / np.arange(1, s + 1, dtype=np.float64)
    uid = rng.choice(s, size=n, p=w / w.sum())
    valid = rng.uniform(size=n) > 0.15
    assert_fused_matches_staged(uid, valid, s)


def test_fused_single_chain():
    uid = np.full((40,), 3, np.int64)
    assert_fused_matches_staged(uid, np.ones((40,), bool), 8)


def test_fused_all_pad():
    rng = np.random.default_rng(2)
    uid = rng.integers(0, 8, 24)
    assert_fused_matches_staged(uid, np.zeros((24,), bool), 8)


def test_fused_n1():
    assert_fused_matches_staged(np.zeros((1,), np.int64),
                                np.ones((1,), bool), 4)


def test_fused_mixed_pad_tail():
    rng = np.random.default_rng(11)
    uid = rng.integers(0, 5, 100)
    valid = np.ones((100,), bool)
    valid[60:] = False                    # trailing pad block
    assert_fused_matches_staged(uid, valid, 5)


def test_mega_kernel_fits_bounds():
    from repro.kernels.megakernel.ops import MEGA_MAX_CELLS, MEGA_MAX_ROWS
    assert mega_kernel_fits(160, 38)
    assert not mega_kernel_fits(MEGA_MAX_ROWS + 8, 38)       # row bound
    assert not mega_kernel_fits(4096, MEGA_MAX_CELLS // 4096 + 256)
    # oversized intervals still evaluate — through the XLA ref
    rng = np.random.default_rng(5)
    uid = rng.integers(0, 6, 64)
    store = make_store([6], 2)
    ops = mk_batch(uid, np.ones((64,), bool), 6)
    a_lut, b_lut = simple_affine_luts(FUNS)
    sops, ch = restructure(ops, store.pad_uid, rowmajor_ts=True,
                           light=True, method="partition", geometry=False)
    import repro.kernels.megakernel.ops as mops
    res_p, vals_p, _ = fused_chain_eval(
        store.values, sops, ch, store.pad_uid, a_lut=a_lut, b_lut=b_lut,
        use_pallas=True, interpret=True)
    orig = mops.MEGA_MAX_ROWS
    try:
        mops.MEGA_MAX_ROWS = 8            # force the structural fallback
        res_r, vals_r, _ = fused_chain_eval(
            store.values, sops, ch, store.pad_uid, a_lut=a_lut,
            b_lut=b_lut, use_pallas=True, interpret=True)
    finally:
        mops.MEGA_MAX_ROWS = orig
    np.testing.assert_array_equal(np.asarray(vals_p), np.asarray(vals_r))
    for k in res_p:
        np.testing.assert_array_equal(np.asarray(res_p[k]),
                                      np.asarray(res_r[k]), err_msg=k)


# ---------------------------------------------------------------------------
# engine level: the forced rung vs the staged partition path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("app_name", ["gs", "tp", "sl", "ob"])
@pytest.mark.parametrize("scheme", ["tstream", "mvlk"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_megakernel_rung_bit_identical(app_name, scheme, use_pallas):
    app = ALL_APPS[app_name]
    rng = np.random.default_rng(13)
    stream = app.gen_events(rng, 64)
    store = app.make_store()
    outs = {}
    for method in ("partition", "megakernel"):
        cfg = EngineConfig(scheme=scheme, restructure_method=method,
                           use_pallas=use_pallas)
        eng = DualModeEngine(app, store, cfg)
        outs[method] = eng.run_stream(store.values, stream, 16, fused=True)
    outs_a, vals_a = outs["partition"]
    outs_b, vals_b = outs["megakernel"]
    np.testing.assert_array_equal(np.asarray(vals_a), np.asarray(vals_b))
    for a, b in zip(outs_a, outs_b):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)


def test_auto_band_engages_megakernel():
    """Inside the measured CPU win band "auto" engages the rung; below
    it (or past the bucket bound) the staged path carries."""
    from repro.kernels.autotune import mega_bounds
    band = mega_bounds("cpu")
    assert megakernel_engaged(band["min_rows"], 128, method="auto",
                              has_max=False, funs_simple=True)
    assert not megakernel_engaged(band["min_rows"] - 1, 128, method="auto",
                                  has_max=False, funs_simple=True)
    assert not megakernel_engaged(band["min_rows"],
                                  band["max_buckets"] + 1, method="auto",
                                  has_max=False, funs_simple=True)
    # structural ineligibility always wins
    assert not megakernel_engaged(band["min_rows"], 128, method="auto",
                                  has_max=True, funs_simple=True)
    assert not megakernel_engaged(band["min_rows"], 128, method="auto",
                                  has_max=False, funs_simple=False)


def test_forced_rung_on_max_store_falls_back_with_one_warning(caplog):
    import importlib
    R = importlib.import_module("repro.core.restructure")
    R._MEGA_FALLBACK_WARNED.clear()
    with caplog.at_level(logging.WARNING, logger="repro.core.restructure"):
        assert not megakernel_engaged(64, 16, method="megakernel",
                                      has_max=True, funs_simple=True)
        assert not megakernel_engaged(64, 16, method="megakernel",
                                      has_max=True, funs_simple=True)
    warns = [r for r in caplog.records
             if "method='megakernel' forced but" in r.getMessage()]
    assert len(warns) == 1               # once per process, not per call

    # and the TP engine (max-typed tables) still matches bit for bit
    app = ALL_APPS["tp"]
    rng = np.random.default_rng(4)
    stream = app.gen_events(rng, 32)
    store = app.make_store()
    outs = {}
    for method in ("partition", "megakernel"):
        eng = DualModeEngine(app, store,
                             EngineConfig(restructure_method=method))
        outs[method] = eng.run_stream(store.values, stream, 16, fused=True)
    np.testing.assert_array_equal(np.asarray(outs["partition"][1]),
                                  np.asarray(outs["megakernel"][1]))


def test_simple_affine_luts_gate():
    from repro.core.types import FunSpec
    assert simple_affine_luts(FUNS) is not None
    # max-type funs are non-affine -> identity in the LUT; they are
    # excluded by the drivers' has_max gate, not here
    assert simple_affine_luts(FUNS + (F_MAX,)) is not None
    # a general affine fun (no simple (a, b) shape) disables the rung
    scale2 = FunSpec("scale2", lambda v, o: 2.0 * v + o,
                     affine=lambda o: (2.0 * jnp.ones_like(o), o))
    assert simple_affine_luts(FUNS + (scale2,)) is None
    a_lut, b_lut = simple_affine_luts(FUNS)
    np.testing.assert_array_equal(np.asarray(a_lut), [1.0, 1.0, 0.0, 1.0])
    np.testing.assert_array_equal(np.asarray(b_lut),
                                  [False, False, True, True])


# ---------------------------------------------------------------------------
# sharded driver (subprocess: 8 forced host devices)
# ---------------------------------------------------------------------------
_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, numpy as np
from repro.apps import ALL_APPS
from repro.core.scheduler import DualModeEngine, EngineConfig
from repro.core.sharded_stream import stream_mesh

out = {}
mesh = stream_mesh((8,), ("dev",))
for layout in ("shared_nothing", "shared_everything"):
    app = ALL_APPS["gs"]
    rng = np.random.default_rng(11)
    stream = app.gen_events(rng, 128)
    store = app.make_store()
    ref = DualModeEngine(app, store,
                         EngineConfig(restructure_method="partition"))
    outs_r, vals_r = ref.run_stream(store.values, stream, 32, fused=True)
    eng = DualModeEngine(app, store,
                         EngineConfig(restructure_method="megakernel"),
                         mesh=mesh, layout=layout, exchange_slack=8.0)
    outs_s, vals_s = eng.run_stream(store.values, stream, 32)
    ok = (int(np.sum(eng.last_exchange_stats["dropped"])) == 0
          and np.array_equal(np.asarray(vals_s), np.asarray(vals_r))
          and all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                  for a, b in zip(outs_s, outs_r) for k in a))
    out[layout] = ok
print(json.dumps(out))
"""


def test_sharded_megakernel_bit_identical():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict == {"shared_nothing": True, "shared_everything": True}


def test_auto_band_needs_kernel_fit_under_use_pallas():
    """With use_pallas, "auto" never engages a rung whose kernel would
    give way to the XLA reference (the band's row count is past the
    kernel's single-block bound on this device)."""
    from repro.kernels.autotune import mega_bounds
    band = mega_bounds()
    rows = band["min_rows"]
    assert megakernel_engaged(rows, 128, method="auto", has_max=False,
                              funs_simple=True)
    assert (megakernel_engaged(rows, 128, method="auto", has_max=False,
                               funs_simple=True, use_pallas=True)
            == mega_kernel_fits(rows, 128))


def test_forced_megakernel_fallback_is_counted_and_reported():
    """A forced megakernel dispatch too large for the kernel runs the XLA
    reference: counted in telemetry, and the rung's stats say so."""
    import jax.numpy as jnp
    from repro.kernels.megakernel.ops import FALLBACK_PATH
    from repro.runtime.telemetry import get_default
    app = ALL_APPS["gs"]
    store = app.make_store()
    interval = 48            # 480 rows x 10,001 slots > MEGA_MAX_CELLS
    assert not mega_kernel_fits(interval * app.max_ops,
                                store.values.shape[0])

    def count():
        return sum(e["count"] for e in get_default().snapshot()["events"]
                   if e["name"] == "kernels.mega_fallback")

    before = count()
    eng = DualModeEngine(app, store, EngineConfig(
        restructure_method="megakernel", use_pallas=True))
    assert "fused_chain" not in eng.pallas_kernels(interval)
    rng = np.random.default_rng(5)
    batched = {k: jnp.asarray(v.reshape((2, interval) + v.shape[1:]))
               for k, v in app.gen_events(rng, 2 * interval).items()}
    _, _, _, st = eng.run_stream_chunk(jnp.array(store.values), batched, 0)
    assert st["engine"].path == FALLBACK_PATH
    assert count() > before


@pytest.mark.parametrize("app_name,cfg,want", [
    ("gs", dict(), ()),
    ("gs", dict(use_pallas=True), ("segscan_affine",)),
    ("tp", dict(use_pallas=True), ("segscan_affine", "segscan_max")),
    ("tp", dict(use_pallas=True, restructure_method="partition"),
     ("radix_partition", "segscan_affine", "segscan_max")),
    ("sl", dict(use_pallas=True), ()),
])
def test_pallas_kernels_of_the_resolved_rung(app_name, cfg, want):
    app = ALL_APPS[app_name]
    eng = DualModeEngine(app, app.make_store(), EngineConfig(**cfg))
    assert eng.pallas_kernels(500) == want
