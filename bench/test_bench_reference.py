"""The plain references agree with the program's sequential ``lock``
oracle, and the bfloat16 control fails the comparison that decides
``correct``.

At a small size on the CPU: GS, GS with the multi-partition mix, and SL
with abort repass from a table with some low balances, so that debits
fail and the repass masks transactions.
"""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(BENCH, "gen"))

import gs as gen_gs  # noqa: E402
import sl as gen_sl  # noqa: E402
from benchlib import compare  # noqa: E402
from benchlib.spec import load_module  # noqa: E402

ml_dtypes = pytest.importorskip("ml_dtypes")

ref_gs = load_module(os.path.join(BENCH, "reference", "gs.py"), "ref_gs")
ref_sl = load_module(os.path.join(BENCH, "reference", "sl.py"), "ref_sl")

INTERVAL = 500
N = 3000
GS_CFG = dict(tables=[10000], width=1, txn_len=10, theta=0.6,
              read_ratio=0.5, punct_interval=INTERVAL,
              compare=dict(outputs=["sum", "ok"]),
              limits=dict(output_gap=1e-4, table_gap=1e-4,
                          snapshot_steps=0, snapshot_gap=1e-4))
MP_CFG = dict(GS_CFG, n_partitions=4, mp_ratio=0.5, mp_len=4)
SL_CFG = dict(tables=[10000, 10000], width=1, theta=0.6,
              transfer_ratio=0.5, punct_interval=INTERVAL,
              compare=dict(outputs=["src_balance", "ok", "rejected"]),
              limits=dict(output_gap=1e-4, table_gap=1e-4,
                          snapshot_steps=0, snapshot_gap=1e-4))
NO_SNAPSHOTS = dict(expected=[], recorded=[], kept={}, ref={})


def lock_oracle(app, tables, init, events, **engine):
    import jax
    import jax.numpy as jnp
    from repro.core.scheduler import DualModeEngine, EngineConfig
    from repro.core.types import make_store
    store = make_store(tables, 1, init=jnp.asarray(init))
    eng = DualModeEngine(app, store, EngineConfig(scheme="lock", **engine))
    outs, vals = eng.run_stream(store.values, events, INTERVAL)
    return outs, np.asarray(jax.device_get(vals))


def gs_case(cfg, seed):
    rng = np.random.default_rng(seed)
    init = gen_gs.initial_table(rng, cfg)
    return init, gen_gs.Sampler(cfg).events(rng, N)


def sl_case(seed):
    rng = np.random.default_rng(seed)
    init = gen_sl.initial_table(rng, SL_CFG)
    # low balances on the hottest keys of both tables: debits fail
    init[:40, 0] = rng.uniform(0.0, 30.0, 40)
    init[10000:10040, 0] = rng.uniform(0.0, 30.0, 40)
    return init, gen_sl.Sampler(SL_CFG).events(rng, N)


@pytest.mark.parametrize("cfg,block", [(GS_CFG, 1 << 19), (GS_CFG, 700),
                                       (MP_CFG, 1 << 19)],
                         ids=["gs", "gs_chained_blocks", "gs_mp"])
def test_gs_reference_matches_lock_oracle(cfg, block, monkeypatch):
    from repro.apps import GS
    monkeypatch.setattr(ref_gs, "BLOCK", block)
    init, ev = gs_case(cfg, 11)
    outs, vals = lock_oracle(GS, cfg["tables"], init, ev)
    ref_out, ref_table, _ = ref_gs.run(init, ev, N, cfg)
    assert np.array_equal(vals[:, 0], ref_table)
    checks = compare.compare(cfg, outs, vals, ref_out, ref_table, N,
                             NO_SNAPSHOTS)
    assert checks["table_gap"]["value"] == 0.0
    # float32 sums of 10 values against the float64 sum
    assert checks["output_gap"]["value"] < 1e-6


def test_sl_reference_matches_lock_oracle():
    from repro.apps import SL
    init, ev = sl_case(12)
    outs, vals = lock_oracle(SL, SL_CFG["tables"], init, ev,
                             abort_repass=True)
    ref_out, ref_table, _ = ref_sl.run(init, ev, N, SL_CFG)
    rejected = sum(int(o["rejected"].sum()) for o in outs)
    assert rejected > 0, "the case must exercise failed debits"
    checks = compare.compare(SL_CFG, outs, vals, ref_out, ref_table, N,
                             NO_SNAPSHOTS)
    assert checks["output_gap"]["value"] == 0.0
    assert checks["table_gap"]["value"] == 0.0


@pytest.mark.parametrize("app", ["gs", "sl"])
def test_reference_tables_at_match_the_lock_oracle(app):
    """The reference's table after a prefix of the events, which the
    snapshots are compared with, is the oracle's table after that prefix."""
    from repro.apps import GS, SL
    if app == "gs":
        cfg, (init, ev), ref, a, kw = GS_CFG, gs_case(GS_CFG, 15), ref_gs, \
            GS, {}
    else:
        cfg, (init, ev), ref, a, kw = SL_CFG, sl_case(16), ref_sl, SL, \
            dict(abort_repass=True)
    at = [0, 2 * INTERVAL, 5 * INTERVAL, N]
    _, table, tables_at = ref.run(init, ev, N, cfg, at=at)
    assert sorted(tables_at) == at
    assert np.array_equal(tables_at[N], table)
    assert np.array_equal(tables_at[0], np.asarray(init, np.float32)[:, 0])
    for c in at[1:]:
        head = {k: v[:c] for k, v in ev.items()}
        _, vals = lock_oracle(a, cfg["tables"], init, head, **kw)
        assert np.array_equal(vals[:, 0], tables_at[c]), c


@pytest.mark.parametrize("lateness", [0, 16, 64])
def test_emission_order_matches_the_assembler(lateness):
    """The plain reference of interval assembly cuts the same intervals, in
    the same order, as the program's assembler, late rows included."""
    from benchlib.assembly import emission_order
    from benchlib.traffic import block_times
    from repro.core.intervals import IntervalAssembler, WatermarkPolicy
    n, batch = 6000, 64
    times = np.concatenate([block_times(7, b, 2000, 64) for b in range(3)])
    ends = list(range(batch, n, batch)) + [n]
    asm = IntervalAssembler(INTERVAL, WatermarkPolicy(
        allowed_lateness=lateness))
    seqs = []
    lo = 0
    for hi in ends:
        asm.push({"i": np.arange(lo, hi)}, times[lo:hi])
        seqs += [c["i"] for c, _ in asm.pop_ready()]
        lo = hi
    asm.close()
    seqs += [c["i"] for c, _ in asm.pop_ready()]
    got = np.concatenate(seqs)
    assert got.size == n
    if lateness < 64:
        assert asm.late_rerouted > 0, "the case must exercise late rows"
    assert np.array_equal(got, emission_order(times, ends, lateness))


def test_snapshot_numbers():
    cfg = dict(GS_CFG, keep_last=2)
    table = np.arange(5, dtype=np.float32)
    good = dict(expected=[16, 32, 48], recorded=[16, 32, 48],
                kept={32: table[:, None], 48: table[:, None]},
                ref={32: table, 48: table})
    assert compare.snapshot_checks(cfg, good) == (0, 0.0)
    skipped = dict(good, recorded=[16, 48], kept={48: table[:, None]})
    assert compare.snapshot_checks(cfg, skipped)[0] == 2
    stale = dict(good, kept={32: table[:, None], 48: table[:, None] + 1})
    assert compare.snapshot_checks(cfg, stale)[1] > 0.1
    damaged = dict(good, kept={32: None, 48: table[:, None]})
    assert compare.snapshot_checks(cfg, damaged)[1] == float("inf")
    not_pruned = dict(good, kept={**good["kept"], 16: table[:, None]})
    assert compare.snapshot_checks(cfg, not_pruned)[0] == 1


def as_outputs(ref_out):
    """A reference's outputs split into the program's per-interval list."""
    return [{k: np.asarray(v[i:i + INTERVAL]) for k, v in ref_out.items()}
            for i in range(0, N, INTERVAL)]


@pytest.mark.parametrize("app", ["gs", "sl"])
def test_bfloat16_control_fails(app):
    bf16 = ml_dtypes.bfloat16
    if app == "gs":
        cfg, (init, ev), ref = GS_CFG, gs_case(GS_CFG, 13), ref_gs
    else:
        cfg, (init, ev), ref = SL_CFG, sl_case(14), ref_sl
    steps = [2, 4]
    at = [s * INTERVAL for s in steps]
    ref_out, ref_table, ref_at = ref.run(init, ev, N, cfg, at=at)
    low_out, low_table, low_at = ref.run(init, ev, N, cfg, dtype=bf16,
                                         at=at)

    def snaps(tables):
        return dict(expected=steps, recorded=steps,
                    kept={c // INTERVAL: np.asarray(t, np.float32)[:, None]
                          for c, t in tables.items()},
                    ref={c // INTERVAL: ref_at[c] for c in at})
    low_table = np.asarray(low_table, np.float32)[:, None]
    checks = compare.compare(cfg, as_outputs(low_out), low_table, ref_out,
                             ref_table, N, snaps(low_at))
    assert not compare.passed(checks)
    assert checks["output_gap"]["value"] > 1e-3
    assert checks["table_gap"]["value"] > 1e-3
    assert checks["snapshot_gap"]["value"] > 1e-3
    same = compare.compare(cfg, as_outputs(ref_out),
                           np.asarray(ref_table, np.float32)[:, None],
                           ref_out, ref_table, N, snaps(ref_at))
    assert compare.passed(same)
