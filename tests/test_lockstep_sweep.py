"""The lockstep level sweep walks chains in sorted layout.

``engines._lockstep_sweep`` reads the table once per pass, hands each
chain's value from op to op by a shift, reads gates once per level and
writes the table once.  Each case compares ``eval_tstream_lockstep`` (and
``eval_mvlk``, which runs it) bit for bit with the round-by-round scatter
sweep it replaced, kept below as the reference: Streaming Ledger
intervals at several dependency level caps, abort-repass batches with
invalid ops in mid-chain and at chain ends, a planted gate cycle, rows
out of timestamp order, the non-gated Online Bidding app (width 2) and
Toll Processing's 32-lane sketches.
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
from test_lockstep_fallback import (_abort_repass, _plant_cycle, _sl_batch,
                                    _shuffle)

SL, OB, TP = ALL_APPS["sl"], ALL_APPS["ob"], ALL_APPS["tp"]


def _reference_sweep(values, sops, ch, funs, chain_mask, results, n,
                     pad_uid, rounds):
    """Round r applies each masked chain's r-th op: a gather from the
    table, and scatters into the table and the flat results."""
    def round_body(r, carry):
        values, res = carry
        active = (ch.pos == r) & jnp.take(chain_mask, ch.seg_id) & sops.valid
        cur = jnp.take(values, sops.uid, axis=0)
        gate = sops.gate
        gate_ok = jnp.where(gate >= 0, jnp.take(res["success"][:-1],
                                                jnp.maximum(gate, 0)), True)
        post, ok = engines.apply_funs(funs, sops.fun, cur, sops.operand)
        post = jnp.where(gate_ok[:, None], post, cur)
        ok = ok & gate_ok
        scat = jnp.where(active, sops.uid, pad_uid)
        values = values.at[scat].set(jnp.where(active[:, None], post, 0.0))
        values = values.at[pad_uid].set(0.0)
        sink = jnp.where(active, ch.order, n)
        res = dict(pre=res["pre"].at[sink].set(cur),
                   post=res["post"].at[sink].set(post),
                   success=res["success"].at[sink].set(ok))
        return values, res

    return jax.lax.fori_loop(0, rounds, round_body, (values, results))


def _reference_lockstep(store, ops, funs, *, max_dep_levels=3,
                        has_gates=False, prestructured=None):
    """``eval_tstream_lockstep`` over the reference sweep."""
    sops, ch = (restructure(ops, store.pad_uid) if prestructured is None
                else prestructured)
    n = ops.n_ops
    values = store.values
    results = dict(pre=jnp.zeros((n + 1, ops.width)),
                   post=jnp.zeros((n + 1, ops.width)),
                   success=jnp.zeros((n + 1,), bool))
    if not has_gates:
        values, results = _reference_sweep(
            values, sops, ch, funs, jnp.ones((n,), bool), results, n,
            store.pad_uid, ch.max_len)
        rounds, fallback_ops = ch.max_len, jnp.int32(0)
    else:
        lvl, unresolved = engines._chain_levels(sops, ch, n, max_dep_levels)
        rounds = jnp.int32(0)
        for level in range(max_dep_levels + 1):
            mask = lvl == level
            in_level = jnp.take(mask, ch.seg_id) & sops.valid
            lvl_rounds = jnp.max(jnp.where(in_level, ch.pos, -1)) + 1
            values, results = _reference_sweep(
                values, sops, ch, funs, mask, results, n, store.pad_uid,
                lvl_rounds)
            rounds = rounds + lvl_rounds
        unresolved_ops = ch.untake(jnp.take(unresolved, ch.seg_id)
                                   & sops.valid)
        values, results, fallback_ops = engines._sequential_sweep(
            values, ops, funs, results, mask_flat=unresolved_ops,
            pad_uid=store.pad_uid)
        rounds = rounds + fallback_ops
    res = {k: v[:n] for k, v in results.items()}
    stats = engines.EngineStats(rounds=rounds, n_chains=ch.n_chains,
                                max_chain=ch.max_len,
                                fallback_ops=fallback_ops, n_ops=n,
                                scheme="tstream", path="lockstep")
    return res, values, stats


def _mask_txns(ops, app, seed, share=0.3):
    """Mask a share of the transactions the way the abort repass does:
    in both layouts' ``valid``, after the sort."""
    n_txn = ops.n_ops // app.max_ops
    drop = np.random.default_rng(seed).random(n_txn) < share
    keep = jnp.asarray(np.repeat(~drop, app.max_ops))
    return dataclasses.replace(ops, valid=ops.valid & keep)


def _app_batch(app, seed, n_events=200):
    store = app.make_store()
    rng = np.random.default_rng(seed)
    events = {k: jnp.asarray(v)
              for k, v in app.gen_events(rng, n_events).items()}
    ops, _ = build_opbatch(app, store, events, jnp.int32(0))
    return store, ops


def _trailing_and_inner_invalid(ops, pres):
    """(chains ending in an invalid op after a valid one, chains with an
    invalid op between two valid ones) of a masked batch."""
    sops, ch = pres
    valid = np.asarray(sops.valid)
    real = np.asarray(ops.valid)[np.asarray(ch.order)]  # before masking
    seg = np.asarray(ch.seg_id)
    trailing = inner = 0
    for c in np.unique(seg[real]):
        rows = np.flatnonzero((seg == c) & real)
        v = valid[rows]
        if v.any() and not v[-1]:
            trailing += 1
        idx = np.flatnonzero(v)
        if idx.size and not v[idx[0]:idx[-1] + 1].all():
            inner += 1
    return trailing, inner


# (batch, seed, max_dep_levels)
CASES = ([("sl", s, m) for m in (3, 1, 0) for s in range(4)]
         + [("repass", 4, 1), ("repass", 5, 3), ("masked", 6, 1),
            ("masked", 7, 3), ("cycle", 8, 3), ("shuffled", 9, 1),
            ("mvlk", 10, 3), ("mvlk_masked", 11, 1),
            ("ob", 12, 3), ("ob_masked", 13, 3), ("ob_mvlk", 14, 3),
            ("tp", 15, 3)])


@pytest.mark.parametrize("kind,seed,max_levels", CASES,
                         ids=[f"{k}-seed{s}-levels{m}" for k, s, m in CASES])
def test_sorted_sweep_matches_scatter_sweep(monkeypatch, kind, seed,
                                            max_levels):
    app = {"ob": OB, "tp": TP}.get(kind.split("_")[0], SL)
    store, ops = _sl_batch(seed) if app is SL else _app_batch(app, seed)
    full = ops
    if kind == "cycle":
        ops = _plant_cycle(ops)
    elif kind == "shuffled":
        ops = _shuffle(ops, seed)
    pres = restructure(ops, store.pad_uid)
    if kind == "repass":
        # balances a twentieth of the generator's: many debits fail
        store = dataclasses.replace(store, values=store.values * 0.05)
        ops = _abort_repass(store, ops)
    elif kind.endswith("masked"):
        ops = _mask_txns(ops, app, seed)
    if ops is not full and kind not in ("cycle", "shuffled"):
        # tighten the sorted view's mask, as the scheduler's repass does
        sops, ch = pres
        pres = (dataclasses.replace(sops, valid=ch.take(ops.valid)), ch)
        trailing, inner = _trailing_and_inner_invalid(full, pres)
        assert trailing > 0 and inner > 0, (trailing, inner)

    def run():
        fn = (engines.eval_mvlk if "mvlk" in kind
              else engines.eval_tstream_lockstep)
        return jax.device_get(fn(store, ops, app.funs,
                                 has_gates=app.has_gates,
                                 max_dep_levels=max_levels,
                                 prestructured=pres))

    got = run()
    with monkeypatch.context() as mp:
        mp.setattr(engines, "eval_tstream_lockstep", _reference_lockstep)
        want = run()

    (res, values, stats), (res_r, values_r, stats_r) = got, want
    np.testing.assert_array_equal(values, values_r, "table")
    for k in ("pre", "post", "success"):
        np.testing.assert_array_equal(res[k], res_r[k], k)
    for k in ("rounds", "fallback_ops", "n_chains", "max_chain"):
        np.testing.assert_array_equal(getattr(stats, k),
                                      getattr(stats_r, k), k)
    assert res["pre"].shape == (ops.n_ops, ops.width)
    if app is SL:
        assert int(np.sum(res["success"])) > 0
