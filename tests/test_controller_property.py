"""Adaptive control plane (DESIGN.md §2.9, ``runtime/controller.py``).

Contracts pinned here:

1. **Purity**: ``decide`` is a pure function of (config, plan, record
   window, boundary, cool-down state) — same inputs, same decisions, and
   it never mutates its arguments.
2. **Hysteresis**: a knob never switches twice within ``cooldown``
   global intervals, whatever the record stream does.
3. **Legal lattice**: the folded plan never leaves the configured
   lattice — scheme ∈ {base, degrade}, slack a bounded geometric ladder,
   chunk on the (snapshot-tiling, queue-bounded) ladder, rung on the
   rung ladder.
4. **Replay**: the decision trace is the whole story —
   ``replay_plan(init, trace)`` equals the live plan after any number of
   steps, and ``restore(trace)`` rebuilds an equivalent controller
   (plan, escalation count, cool-down state).
5. **Integration**: a run whose controller *grows K mid-stream* is still
   bit-identical to one monolithic ``run_stream`` over the same events
   (chunk boundaries are punctuation boundaries whatever K does), the
   per-chunk time series ``stats["chunks"]`` is ring-bounded with a
   stable schema, and ``escalate_overflow`` now composes with snapshots
   instead of being statically excluded.
"""
import copy

import numpy as np
import pytest

from repro.apps import ALL_APPS
from repro.core.intervals import PhasedReplaySource, ReplaySource, \
    WatermarkPolicy
from repro.core.scheduler import DualModeEngine, EngineConfig
from repro.runtime.controller import (KNOBS, ControllerConfig, Plan,
                                      PlanController, decide, replay_plan)
from repro.runtime.service import ServiceConfig, StreamService

from test_service import assert_outputs_identical

BASE = Plan(scheme="tstream", rung="auto", slack=1.0, chunk=2)


def mk_record(i, *, scheme="tstream", fail=0, ops=64, max_chain=1,
              qfill=0, x_drop=0, x_fill=0, x_cap=20, k=2, lat_s=0.01):
    return dict(i=i, g0=i * k, k=k, events=k * 16, lat_s=lat_s,
                qfill=qfill, scheme=scheme, fail=fail, ops=ops,
                max_chain=max_chain, n_chains=1, rounds=1, x_drop=x_drop,
                x_ship=10, x_fill=x_fill, x_cap=x_cap)


# ---------------------------------------------------------------------------
# hypothesis property suite (guarded import, same pattern as test_faults)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
except ImportError:     # pragma: no cover - hypothesis is in requirements-dev
    st = None

if st is not None:
    record_st = st.builds(
        lambda scheme, fail, chain, qfill, drop, fill: dict(
            scheme=scheme, fail=fail, max_chain=chain, qfill=qfill,
            x_drop=drop, x_fill=fill),
        st.sampled_from(["tstream", "lock"]), st.integers(0, 64),
        st.integers(0, 32), st.integers(0, 16), st.integers(0, 8),
        st.integers(0, 30))

    cfg_st = st.builds(
        lambda sustain, cooldown, snap: dict(sustain=sustain,
                                             cooldown=cooldown, snap=snap),
        st.integers(1, 3), st.integers(1, 6), st.sampled_from([0, 4]))

    def _mk_cfg(p):
        return ControllerConfig(
            window=3, sustain=p["sustain"], cooldown=p["cooldown"],
            degrade_scheme="lock", degrade_chain_frac=0.5,
            degrade_fail_frac=0.25, slack_widen=True, slack_factor=2.0,
            slack_max=16.0, fill_widen=0.9, max_escalations=3,
            chunk_ladder=(1, 2, 4, 8, 16), backlog_grow=2.0,
            rung_ladder=("auto", "safe"), rung_chain_frac=0.6)

    def _drive(cfg, records, sharded, snap):
        """Fold a synthetic record stream through a controller, one
        boundary per record, returning the controller."""
        ctl = PlanController(cfg, BASE, sharded=sharded, snap_align=snap,
                             queue_cap=8)
        window = []
        for j, r in enumerate(records):
            window.append(mk_record(j, **r))
            ctl.step(j * 2, window[-cfg.window:])
        return ctl

    @settings(max_examples=60, deadline=None)
    @given(params=cfg_st, records=st.lists(record_st, min_size=1,
                                           max_size=16),
           sharded=st.booleans())
    def test_controller_pure_lattice_cooldown_replay(params, records,
                                                     sharded):
        cfg = _mk_cfg(params)
        snap = params["snap"]
        # purity: decide() twice on deep copies -> identical decisions,
        # arguments unmutated
        window = [mk_record(j, **r) for j, r in enumerate(records)]
        frozen = copy.deepcopy(window)
        last = {"scheme": 0} if len(records) > 3 else {}
        a = decide(cfg, BASE, window, 10, dict(last), init_plan=BASE,
                   sharded=sharded, esc_done=0, snap_align=snap,
                   queue_cap=8)
        b = decide(cfg, copy.deepcopy(BASE), copy.deepcopy(window), 10,
                   dict(last), init_plan=BASE, sharded=sharded, esc_done=0,
                   snap_align=snap, queue_cap=8)
        assert a == b, "decide is not a pure function of its inputs"
        assert window == frozen, "decide mutated the record window"
        assert len({d["knob"] for d in a}) == len(a), \
            "more than one decision per knob at one boundary"

        # fold the whole stream; then check lattice + hysteresis + replay
        ctl = _drive(cfg, records, sharded, snap)
        seen = {}
        for d in ctl.trace:
            assert d["knob"] in KNOBS
            if d["knob"] in seen:
                assert d["g"] - seen[d["knob"]] >= cfg.cooldown, \
                    f"{d['knob']} switched inside its cool-down"
            seen[d["knob"]] = d["g"]
        plan = ctl.plan
        assert plan.scheme in ("tstream", "lock")
        assert plan.rung in cfg.rung_ladder
        assert plan.chunk == BASE.chunk or plan.chunk in cfg.chunk_ladder
        if snap:
            assert snap % plan.chunk == 0, \
                "chunk switch broke snapshot tiling"
        n_esc = round(np.log2(plan.slack / BASE.slack))
        assert plan.slack <= cfg.slack_max
        assert plan.slack == BASE.slack * 2.0 ** n_esc
        assert ctl.esc_done <= cfg.max_escalations
        if sharded:
            # the sharded engine has no scheme/rung variants; its lattice
            # is {exchange slack, chunk size} (reshard is off in this cfg)
            assert all(d["knob"] in ("slack", "chunk") for d in ctl.trace), \
                "sharded lattice is {slack, chunk}"
        else:
            assert all(d["knob"] != "slack" for d in ctl.trace)

        # replay: the trace is the whole story
        assert replay_plan(BASE, ctl.trace) == plan
        gs = [d["g"] for d in ctl.trace]
        assert gs == sorted(gs), "trace not monotone in g"
        clone = PlanController(cfg, BASE, sharded=sharded, snap_align=snap,
                               queue_cap=8)
        clone.restore(ctl.trace, plan_check=plan.as_dict())
        assert (clone.plan, clone.esc_done, clone.last_switch) == \
            (plan, ctl.esc_done, ctl.last_switch)


# ---------------------------------------------------------------------------
# deterministic unit cases for each knob's trigger
# ---------------------------------------------------------------------------
def test_degrade_requires_sustained_storm_and_probes_back():
    cfg = ControllerConfig(window=4, sustain=2, cooldown=4,
                           degrade_scheme="lock", degrade_chain_frac=0.5)
    ctl = PlanController(cfg, BASE, sharded=False, snap_align=0,
                         queue_cap=8)
    storm = lambda i: mk_record(i, max_chain=16)          # frac 1.0
    calm = lambda i: mk_record(i, max_chain=1)
    assert ctl.step(0, [storm(0)]) == []                  # 1 < sustain
    assert ctl.step(2, [storm(0), calm(1)]) == []         # not consecutive
    d = ctl.step(4, [calm(0), storm(1), storm(2)])
    assert [x["new"] for x in d] == ["lock"]
    assert d[0]["reason"] == "conflict-storm"
    # degraded records never count as storm evidence; recovery is an
    # unconditional probe once the cool-down expires
    assert ctl.step(6, [mk_record(3, scheme="lock", max_chain=64)]) == []
    d = ctl.step(8, [mk_record(4, scheme="lock", max_chain=64)])
    assert d[0]["reason"] == "probe" and ctl.plan.scheme == "tstream"


def test_slack_widens_before_drop_on_fill_crowding():
    cfg = ControllerConfig(window=2, sustain=1, cooldown=1,
                           fill_widen=0.9, slack_factor=2.0, slack_max=4.0,
                           max_escalations=0)
    ctl = PlanController(cfg, BASE, sharded=True, snap_align=0, queue_cap=8)
    assert ctl.step(0, [mk_record(0, x_fill=17, x_cap=20)]) == []
    d = ctl.step(2, [mk_record(1, x_fill=19, x_cap=20)])   # 95% full, 0 drops
    assert d[0]["reason"] == "fill-crowding" and ctl.plan.slack == 2.0
    d = ctl.step(4, [mk_record(2, x_drop=3)])
    assert d[0]["reason"] == "overflow-drops" and ctl.plan.slack == 4.0
    assert ctl.step(6, [mk_record(3, x_drop=3)]) == [], "slack_max ceiling"


def test_chunk_switch_waits_for_snapshot_boundary():
    cfg = ControllerConfig(window=2, sustain=1, cooldown=1,
                           chunk_ladder=(2, 4, 8), backlog_grow=2.0)
    ctl = PlanController(cfg, BASE, sharded=False, snap_align=4,
                         queue_cap=8)
    backlog = lambda i: mk_record(i, qfill=8)
    assert ctl.step(2, [backlog(0)]) == [], "g=2 is not snapshot-aligned"
    d = ctl.step(4, [backlog(1)])
    assert d[0]["knob"] == "chunk" and ctl.plan.chunk == 4
    # 8 does not tile snap_align=4: the ladder is clipped to legal rungs
    assert ctl.step(8, [backlog(2, )]) == []
    assert ctl.plan.chunk == 4


# ---------------------------------------------------------------------------
# integration: adaptation composes with the service's exactness contracts
# ---------------------------------------------------------------------------
def test_chunk_adaptation_matches_monolithic_bitwise():
    """K grows mid-stream under backlog; the run stays bit-identical to
    one monolithic run_stream (chunk boundaries are punctuation
    boundaries whatever K the controller picks)."""
    app = ALL_APPS["gs"]
    interval, n_iv = 16, 24
    src = lambda: ReplaySource(app.gen_events, interval * n_iv, seed=4,
                               arrival_batch=interval * n_iv, jitter=0)
    eng = DualModeEngine(app, app.make_store(), EngineConfig())
    ref, vals_ref = eng.run_stream(app.make_store().values,
                                   src().in_order_events, interval,
                                   fused=True)
    ctl_cfg = ControllerConfig(window=2, sustain=1, cooldown=2,
                               chunk_ladder=(2, 4, 8), backlog_grow=1.0)
    svc = StreamService(eng, ServiceConfig(
        punct_interval=interval, chunk_intervals=2, queue_intervals=16,
        controller=ctl_cfg))
    rec = svc.run(src())
    grown = [d for d in rec.decisions if d["knob"] == "chunk"]
    assert grown and grown[0]["reason"] == "backlog", rec.decisions
    ks = {r["k"] for r in rec.stats["chunks"]}
    assert len(ks) > 1, f"K never actually changed: {ks}"
    np.testing.assert_array_equal(rec.final_values, np.asarray(vals_ref))
    assert_outputs_identical(rec.outputs, ref)
    # the published controller record round-trips
    cstats = rec.stats["controller"]
    assert replay_plan(Plan.from_dict(cstats["init_plan"]),
                       cstats["decisions"]).as_dict() == cstats["plan"]


def test_chunk_record_ring_schema_and_bound():
    app = ALL_APPS["gs"]
    eng = DualModeEngine(app, app.make_store(), EngineConfig())
    svc = StreamService(eng, ServiceConfig(
        punct_interval=16, chunk_intervals=1, chunk_record_ring=3))
    rec = svc.run(ReplaySource(app.gen_events, 16 * 8, seed=1,
                               arrival_batch=32, jitter=0))
    chunks = rec.stats["chunks"]
    assert len(chunks) == 3, "ring bound not enforced"
    keys = {"i", "g0", "k", "events", "lat_s", "qfill", "scheme", "fail",
            "ops", "max_chain", "n_chains", "rounds", "x_drop", "x_ship",
            "x_fill", "x_cap"}
    assert all(keys <= set(r) for r in chunks)
    assert [r["i"] for r in chunks] == [5, 6, 7], "newest-last ordering"
    assert all(r["max_chain"] >= 1 and r["ops"] >= 16 for r in chunks), \
        "single-device records must carry engine chain stats"


def test_escalation_now_composes_with_snapshots(tmp_path):
    """PR 5 statically excluded escalate_overflow + snapshot_every; the
    decision trace made the combination legal (DESIGN.md §2.9)."""
    ServiceConfig(punct_interval=16, chunk_intervals=2, snapshot_every=4,
                  ckpt_dir=str(tmp_path), escalate_overflow=2)


def test_adaptive_storm_degrades_and_recovers():
    """End-to-end single-device storm: calm -> hot-key skew -> calm.  The
    controller degrades tstream -> lock under the sustained storm, probes
    back, and the decision trace tells that story in order."""
    app = ALL_APPS["gs"]
    interval = 64
    src = PhasedReplaySource(app.gen_events, [
        (4 * interval, dict(theta=0.2)),
        (8 * interval, dict(theta=2.5)),
        (8 * interval, dict(theta=0.2)),
    ], seed=7, arrival_batch=2 * interval)
    eng = DualModeEngine(app, app.make_store(), EngineConfig())
    ctl_cfg = ControllerConfig(window=2, sustain=2, cooldown=2,
                               degrade_scheme="lock",
                               degrade_chain_frac=0.6)
    rec = StreamService(eng, ServiceConfig(
        punct_interval=interval, chunk_intervals=2,
        controller=ctl_cfg)).run(src)
    schemes = [(d["old"], d["new"]) for d in rec.decisions
               if d["knob"] == "scheme"]
    assert ("tstream", "lock") in schemes, rec.decisions
    assert ("lock", "tstream") in schemes, "probe-back never fired"
    assert {r["scheme"] for r in rec.stats["chunks"]} == \
        {"tstream", "lock"}
    assert rec.stats["controller"]["plan"]["scheme"] == "tstream", \
        "run should end probed back to the base scheme"
