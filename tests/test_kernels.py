"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes/segment patterns, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels.segscan import ops as segops
from repro.kernels.segscan import ref as segref
from repro.kernels.hash_probe import kernel as hpk
from repro.kernels.hash_probe import ops as hpops
from repro.kernels.hash_probe import ref as hpref

# radix_partition kernel tests are deterministic and live in the ungated
# tests/test_restructure_parity.py so coverage survives without hypothesis


def _mk_segments(rng, n, avg_seg):
    flags = rng.random(n) < (1.0 / avg_seg)
    flags[0] = True
    return flags


@pytest.mark.parametrize("n", [1, 7, 256, 300, 1024, 2500])
@pytest.mark.parametrize("w", [1, 2, 32, 128])
@pytest.mark.parametrize("avg_seg", [1.5, 8, 1000])
def test_segscan_affine_matches_ref(n, w, avg_seg):
    rng = np.random.default_rng(n * 1000 + w)
    a = jnp.asarray(rng.uniform(0.0, 1.5, (n, w)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-2.0, 2.0, (n, w)).astype(np.float32))
    f = jnp.asarray(_mk_segments(rng, n, avg_seg))
    A0, B0 = segref.segscan_affine_ref(f, a, b)
    A1, B1 = segops.segscan_affine(a, b, f, interpret=True)
    np.testing.assert_allclose(np.asarray(A1), np.asarray(A0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(B1), np.asarray(B0), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("n", [5, 256, 777, 2048])
@pytest.mark.parametrize("w", [1, 32])
def test_segscan_max_matches_ref(n, w):
    rng = np.random.default_rng(n + w)
    m = jnp.asarray(rng.uniform(-5, 5, (n, w)).astype(np.float32))
    f = jnp.asarray(_mk_segments(rng, n, 6))
    M0 = segref.segscan_max_ref(f, m)
    M1 = segops.segscan_max(m, f, interpret=True)
    np.testing.assert_allclose(np.asarray(M1), np.asarray(M0), rtol=1e-6,
                               atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 600),
       avg=st.sampled_from([1.0, 3.0, 50.0]))
def test_segscan_affine_property(seed, n, avg):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.uniform(0, 2, (n, 3)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-1, 1, (n, 3)).astype(np.float32))
    f = jnp.asarray(_mk_segments(rng, n, avg))
    A0, B0 = segref.segscan_affine_ref(f, a, b)
    A1, B1 = segops.segscan_affine(a, b, f, interpret=True)
    np.testing.assert_allclose(np.asarray(A1), np.asarray(A0), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(B1), np.asarray(B0), rtol=2e-5,
                               atol=2e-5)


def test_segscan_engine_integration():
    """Engine fast path with use_pallas=True equals the oracle on GS."""
    from repro.apps import GS
    from repro.core.blotter import build_opbatch
    from repro.core.engines import evaluate
    rng = np.random.default_rng(0)
    store = GS.make_store()
    events = {k: jnp.asarray(v) for k, v in GS.gen_events(rng, 48).items()}
    ops, _ = build_opbatch(GS, store, events, jnp.int32(0))
    r1, v1, _ = evaluate(store, ops, GS.funs, "tstream_scan", use_pallas=True)
    r0, v0, _ = evaluate(store, ops, GS.funs, "lock")
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(r1["pre"]), np.asarray(r0["pre"]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_keys,n_buckets", [(50, 64), (500, 256),
                                              (4000, 2048)])
def test_hash_probe_matches_ref_and_truth(n_keys, n_buckets):
    rng = np.random.default_rng(n_keys)
    keys = rng.choice(2**31 - 1, size=n_keys, replace=False).astype(np.int32)
    lo, hi = hpref.build_table(keys, n_buckets)
    lo, hi = jnp.asarray(lo), jnp.asarray(hi)
    # present keys resolve to a slot holding the key
    q = jnp.asarray(keys[: min(n_keys, 300)])
    s_ref = np.asarray(hpref.hash_probe_ref(q, lo, hi))
    s_ker = np.asarray(hpops.hash_probe(q, lo, hi, interpret=True))
    np.testing.assert_array_equal(s_ker, s_ref)
    assert np.all(s_ker >= 0)
    flat = np.asarray(lo).reshape(-1).astype(np.int64) \
        + np.asarray(hi).reshape(-1).astype(np.int64) * 65536
    np.testing.assert_array_equal(flat[s_ker], np.asarray(q, np.int64))
    # absent keys return -1
    absent = rng.choice(2**31 - 1, size=200).astype(np.int32)
    absent = np.setdiff1d(absent, keys)[:100]
    s_abs = np.asarray(hpops.hash_probe(jnp.asarray(absent), lo, hi,
                                        interpret=True))
    assert np.all(s_abs == -1)


def test_interpret_forced_on_tpu_backend_warns(monkeypatch, caplog):
    import logging

    from repro.kernels import runtime as R
    monkeypatch.setenv(R.INTERPRET_ENV, "1")
    monkeypatch.setattr(R.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(R, "_warned", [])
    with caplog.at_level(logging.WARNING, logger=R.__name__):
        assert R.default_interpret() is True
    assert "forces Pallas interpret mode on a TPU" in caplog.text
    monkeypatch.delenv(R.INTERPRET_ENV)
    assert R.default_interpret() is False


def test_tpu_kernels_in_reads_named_custom_calls():
    from repro.kernels.runtime import tpu_kernels_in
    hlo = "\n".join([
        '  %segscan_affine.3 = (f32[8,128]) custom-call(%a), '
        'custom_call_target="tpu_custom_call", backend_config="x"',
        '  ROOT %fused_chain = f32[8,128] custom-call(%b), '
        'custom_call_target="tpu_custom_call"',
        '  %sort.1 = f32[8] custom-call(%c), custom_call_target="Sort"',
    ])
    assert tpu_kernels_in(hlo) == {"segscan_affine": 1, "fused_chain": 1}
