"""The lockstep path's cycle fallback runs only over the ops that need it.

``engines._sequential_sweep`` loops over the valid masked ops alone, in
global (ts, slot) order.  Each case compares it bit for bit with the
full-length masked sweep it replaced (kept below as the reference), on
Streaming Ledger intervals at the paper's settings, with the dependency
level cap forcing more or less residue, on an abort-repass batch, on a
batch with a planted two-chain gate cycle, and on batches whose rows are
not in timestamp order.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.core import engines
from repro.core.blotter import build_opbatch
from repro.core.restructure import restructure

SL = ALL_APPS["sl"]
N_EVENTS = 500          # one paper-sized punctuation interval: 2,000 ops


def _reference_sweep(values, ops, funs, results, *, mask_flat, pad_uid):
    """A scan over all n ops in (ts, slot) order; masked-out steps write
    only the pad row and the sink row n."""
    n = ops.n_ops
    order = jnp.lexsort((ops.slot, ops.ts))

    def step(carry, i):
        values, res = carry
        j = order[i]
        run = mask_flat[j] & ops.valid[j]
        uid = jnp.where(run, ops.uid[j], pad_uid)
        cur = values[uid]
        gate = ops.gate[j]
        gate_ok = jnp.where(gate >= 0, res["success"][jnp.maximum(gate, 0)],
                            True)
        post, ok = engines.funs_apply_single(funs, ops.fun[j], cur,
                                             ops.operand[j])
        post = jnp.where(gate_ok, post, cur)
        ok = ok & gate_ok
        values = values.at[uid].set(jnp.where(run, post, values[pad_uid]))
        values = values.at[pad_uid].set(0.0)
        sink = jnp.where(run, j, n)
        res = dict(pre=res["pre"].at[sink].set(cur),
                   post=res["post"].at[sink].set(post),
                   success=res["success"].at[sink].set(ok))
        return (values, res), None

    (values, results), _ = jax.lax.scan(step, (values, results),
                                        jnp.arange(n))
    return values, results, jnp.sum(mask_flat & ops.valid, dtype=jnp.int32)


def _sl_batch(seed):
    store = SL.make_store()
    rng = np.random.default_rng(seed)
    events = {k: jnp.asarray(v) for k, v in
              SL.gen_events(rng, N_EVENTS).items()}
    ops, _ = build_opbatch(SL, store, events, jnp.int32(0))
    return store, ops


def _plant_cycle(ops):
    """Gate two transfers' account credits on each other: their chains
    wait on each other's level, which never resolves."""
    m = SL.max_ops
    gate = np.asarray(ops.gate).copy()
    valid = np.asarray(ops.valid)
    uid = np.asarray(ops.uid)
    credits = [t * m + 2 for t in range(ops.n_ops // m)
               if valid[t * m + 2]]
    a = credits[0]
    b = next(c for c in credits[1:] if uid[c] != uid[a])
    gate[a], gate[b] = b, a
    return dataclasses.replace(ops, gate=jnp.asarray(gate))


def _shuffle(ops, seed):
    """The same batch with its rows permuted (gates remapped), so flat
    order is not (ts, slot) order."""
    perm = np.random.default_rng(seed).permutation(ops.n_ops)
    inv = np.argsort(perm)
    ops = jax.tree_util.tree_map(lambda x: jnp.take(x, perm, axis=0), ops)
    gate = np.asarray(ops.gate)
    gate = np.where(gate >= 0, inv[np.maximum(gate, 0)], gate)
    return dataclasses.replace(ops, gate=jnp.asarray(gate, jnp.int32))


def _abort_repass(store, ops):
    """The scheduler's repass batch: transactions with a failed op masked."""
    res, _, _ = engines.evaluate(store, ops, SL.funs, "tstream",
                                 has_gates=True)
    succ = np.asarray(res["success"]).reshape(-1, SL.max_ops)
    valid = np.asarray(ops.valid).reshape(-1, SL.max_ops)
    keep = np.repeat(np.all(succ | ~valid, axis=1), SL.max_ops)
    return dataclasses.replace(ops, valid=ops.valid & jnp.asarray(keep))


def _unresolved_count(ops, max_levels):
    """Valid ops in chains whose gate level does not resolve within
    ``max_levels`` sweeps (chains are the ops of one state)."""
    uid = np.asarray(ops.uid)
    gate = np.asarray(ops.gate)
    valid = np.asarray(ops.valid)
    gated = valid & (gate >= 0)
    inf = np.iinfo(np.int64).max // 2
    has_gate = {int(u) for u in uid[gated]}
    lvl = {u: inf for u in has_gate}
    for _ in range(max_levels):
        need = {u: 0 for u in has_gate}
        for i in np.flatnonzero(gated):
            u, mate = int(uid[i]), int(uid[gate[i]])
            need[u] = max(need[u], min(lvl.get(mate, 0) + 1, inf))
        lvl = need
    stuck = {u for u, v in lvl.items() if v >= inf}
    return int(sum(1 for i in np.flatnonzero(valid) if int(uid[i]) in stuck))


CASES = [
    ("sl", 0, 3), ("sl", 1, 3), ("sl", 2, 3), ("sl", 3, 3),
    ("sl", 4, 0), ("sl", 5, 1),
    ("repass", 6, 3),
    ("cycle", 7, 3), ("cycle", 8, 1),
    ("shuffled", 9, 1), ("shuffled", 10, 0),
]


@pytest.mark.parametrize("kind,seed,max_levels", CASES,
                         ids=[f"{k}-seed{s}-levels{m}" for k, s, m in CASES])
def test_fallback_matches_full_length_sweep(monkeypatch, kind, seed,
                                            max_levels):
    store, ops = _sl_batch(seed)
    if kind == "cycle":
        ops = _plant_cycle(ops)
    elif kind == "repass":
        ops = _abort_repass(store, ops)
    elif kind == "shuffled":
        ops = _shuffle(ops, seed)
    pres = restructure(ops, store.pad_uid)

    def lockstep():
        return jax.device_get(engines.eval_tstream_lockstep(
            store, ops, SL.funs, has_gates=True, max_dep_levels=max_levels,
            prestructured=pres))

    def lock():
        return jax.device_get(engines.eval_lock(store, ops, SL.funs))

    got = lockstep()
    got_lock = lock()
    with monkeypatch.context() as mp:
        mp.setattr(engines, "_sequential_sweep", _reference_sweep)
        want = lockstep()
        want_lock = lock()

    for (res, values, stats), (res_r, values_r, stats_r), name in (
            (got, want, "lockstep"), (got_lock, want_lock, "lock")):
        np.testing.assert_array_equal(values, values_r, f"{name} table")
        for k in ("pre", "post", "success"):
            np.testing.assert_array_equal(res[k], res_r[k], f"{name} {k}")
        assert int(stats.rounds) == int(stats_r.rounds), name

    n_unresolved = _unresolved_count(ops, max_levels)
    assert int(got[2].fallback_ops) == n_unresolved
    assert int(got_lock[2].fallback_ops) == 0
    if kind == "cycle":
        assert n_unresolved >= 2, "the planted cycle must fall back"
    if max_levels == 0:
        # every gated chain falls back
        assert n_unresolved > 0


@pytest.mark.parametrize("max_levels", [0, 3])
def test_chunk_records_and_telemetry_count_fallback_ops(max_levels):
    """Each chunk record carries the ops its intervals' fallback applied
    (both passes of the abort repass), and the run's counter sums them."""
    from repro.core.intervals import ReplaySource
    from repro.core.scheduler import DualModeEngine, EngineConfig
    from repro.runtime.service import ServiceConfig, StreamService

    interval = 64
    eng = DualModeEngine(SL, SL.make_store(), EngineConfig(
        abort_repass=True, max_dep_levels=max_levels))
    svc = StreamService(eng, ServiceConfig(punct_interval=interval,
                                           chunk_intervals=2))
    rec = svc.run(ReplaySource(SL.gen_events, interval * 6, seed=3,
                               arrival_batch=32, jitter=0))
    chunks = rec.stats["chunks"]
    counted = sum(r["fallback_ops"] for r in chunks)
    assert rec.telemetry.counter_value("engine.fallback_ops") == counted
    assert all(0 <= r["fallback_ops"] <= 2 * r["ops"] for r in chunks)
    if max_levels == 0:
        # every gated chain falls back, in both passes of every interval
        assert counted > 0
