"""Transaction-processing engines: TStream (D2) + re-implemented baselines.

All engines share one contract::

    evaluate(store, ops, funs, ...) -> (OpResults_flat, new_values, stats)

``OpResults_flat`` is in *pre-sort* flat layout ([N] rows aligned with
(txn, slot)), so the scheduler can reshape it straight back into per-event
blotters.  ``stats`` carries structural parallelism counters (rounds, chain
counts) consumed by the benchmark harness's executor model.

The O(N log N) ``restructure`` lexsort runs **exactly once per evaluated
batch**: callers that already hold the sorted view pass it via
``prestructured=(sops, chains)`` and every chain-based scheme (tstream
variants + mvlk) threads it through instead of re-sorting.  A batch whose
``valid`` mask was tightened *after* sorting (the scheduler's abort repass)
is still legal input: chain geometry only depends on uids, and all paths
neutralize invalid mid-chain ops.

Schemes (see DESIGN.md §2 for the multicore->TPU schedule mapping):

* ``tstream``   — D2 dynamic restructuring.  Associative-only apps take the
                  segmented-scan fast path (log-depth chains); otherwise the
                  lockstep path walks all chains in parallel, one op per chain
                  per round (the paper's one-thread-per-chain walk).  Gated
                  ops (cross-chain CFun deps) are scheduled level-wise like
                  the paper's iterative process; unresolved residue (cycles)
                  falls back to the sequential oracle for affected ops.
* ``lock``      — S2PL + lockAhead schedule: conflict-equivalent global ts
                  order, one transaction at a time (depth N).  Doubles as the
                  correctness oracle.
* ``mvlk``      — multiversion locking: writes serialize per state, reads are
                  served from versions in parallel.
* ``pat``       — S-Store partition-level locking: partitions advance their
                  ts-ordered fronts; a multi-partition transaction fires only
                  when it is at the front of *all* its partitions.
* ``nolock``    — no ordering (upper bound, deliberately incorrect).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .restructure import (Chains, commit_from_histogram, commit_index,
                          restructure, segmented_scan_affine,
                          segmented_scan_max)
from .types import FunSpec, OpBatch, OpKind, StateStore

Prestructured = Tuple[OpBatch, Chains]


# ---------------------------------------------------------------------------
# Fun application
# ---------------------------------------------------------------------------
def apply_funs(funs: Tuple[FunSpec, ...], fun_id: jnp.ndarray,
               pre: jnp.ndarray, operand: jnp.ndarray):
    """Vectorized lax.switch over the app's fun family.

    pre, operand: [N, W] -> (post [N, W], success bool[N]).
    """
    branches = [f.apply for f in funs]

    def one(fid, p, o):
        return jax.lax.switch(fid, branches, p, o)

    return jax.vmap(one)(fun_id, pre, operand)


def affine_coeffs(funs: Tuple[FunSpec, ...], fun_id: jnp.ndarray,
                  operand: jnp.ndarray):
    """Per-op (a, b) affine coefficients; identity for non-affine funs.

    When every fun declares a simple affine shape (``affine_simple``:
    a ∈ {0, 1}, b ∈ {0, operand} — true for the whole core family), the
    vmapped 5-branch switch collapses to two tiny LUT gathers + a select,
    with bit-identical outputs.
    """
    simple = [f.affine_simple if f.affine is not None else (1.0, False)
              for f in funs]
    if all(s is not None for s in simple):
        a_lut = jnp.asarray([s[0] for s in simple], operand.dtype)
        b_lut = jnp.asarray([s[1] for s in simple])
        a = jnp.broadcast_to(jnp.take(a_lut, fun_id)[:, None], operand.shape)
        b = jnp.where(jnp.take(b_lut, fun_id)[:, None], operand,
                      jnp.zeros_like(operand))
        return a, b

    branches = [(f.affine if f.affine is not None else (lambda o: (jnp.ones_like(o), jnp.zeros_like(o))))
                for f in funs]

    def one(fid, o):
        return jax.lax.switch(fid, branches, o)

    return jax.vmap(one)(fun_id, operand)


def simple_affine_luts(funs: Tuple[FunSpec, ...]):
    """(a_lut f32[n_funs], b_lut bool[n_funs]) when EVERY fun declares a
    simple affine shape (a ∈ {0, 1}, b ∈ {0, operand}; non-affine funs
    count as identity) — the whole-app precondition for the fused
    megakernel, whose in-VMEM coefficient expansion is these two gathers.
    Returns None when any fun is not simple-affine.
    """
    simple = [f.affine_simple if f.affine is not None else (1.0, False)
              for f in funs]
    if not all(s is not None for s in simple):
        return None
    return (jnp.asarray([s[0] for s in simple], jnp.float32),
            jnp.asarray([s[1] for s in simple]))


def _gate_open(gate: jnp.ndarray, success_flat: jnp.ndarray) -> jnp.ndarray:
    """CFun gating: open when ungated, else the mate op's recorded success."""
    return jnp.where(gate >= 0, jnp.take(success_flat, jnp.maximum(gate, 0)), True)


@dataclasses.dataclass
class EngineStats:
    """Structural parallelism counters for the executor cost model."""
    rounds: jnp.ndarray          # sequential depth of the schedule
    n_chains: jnp.ndarray        # parallel width available
    max_chain: jnp.ndarray       # longest chain
    fallback_ops: jnp.ndarray    # ops the cycle fallback applied (else 0)
    n_ops: int                   # total decomposed ops (incl. padding)
    scheme: str = ""
    path: str = ""               # "segscan" | "lockstep" | ...


jax.tree_util.register_dataclass(
    EngineStats, data_fields=["rounds", "n_chains", "max_chain",
                              "fallback_ops"],
    meta_fields=["n_ops", "scheme", "path"])


def _empty_results(n: int, w: int):
    return dict(pre=jnp.zeros((n + 1, w)), post=jnp.zeros((n + 1, w)),
                success=jnp.zeros((n + 1,), bool))


# ---------------------------------------------------------------------------
# TStream fast path: segmented-scan chain evaluation (associative funs only)
#
# Split into three stages so the fused stream driver can hoist everything
# values-independent out of its sequential interval scan (DESIGN.md §2.4):
#
#   plan    = tstream_scan_plan(...)        restructure + coefficients +
#                                           commit gather map (per batch)
#   plan    = tstream_scan_coefs(plan)      exclusive segmented scans
#   results = tstream_scan_execute(values, plan)   the only values-dependent
#                                           part: v0 gather, Fun application,
#                                           commit — O(N) elementwise+gather
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScanPlan:
    """Values-independent plan for one batch on the segmented-scan path.

    ``af``/``bf``/``mx`` hold the per-op affine / max *coefficients* after
    ``tstream_scan_plan`` and the *exclusive segmented scans* of those
    coefficients after ``tstream_scan_coefs``; ``afi``/``bfi``/``mxi`` are
    the *inclusive* scans (None until coefs run).  With every fun on this
    path associative-affine (or max-on-max-table), pre/post are pure
    coefficient applications — no Fun dispatch in the values-dependent
    stage.  ``commit_pos``/``commit_ok`` are the [S+1] per-state commit
    gather map (see ``commit_index``).
    """

    sops: OpBatch
    ch: Chains
    af: jnp.ndarray
    bf: jnp.ndarray
    afi: Optional[jnp.ndarray]
    bfi: Optional[jnp.ndarray]
    mx: Optional[jnp.ndarray]        # None when the store has no max tables
    mxi: Optional[jnp.ndarray]
    is_max_s: Optional[jnp.ndarray]  # (statically elided — saves a scan)
    commit_pos: jnp.ndarray
    commit_ok: jnp.ndarray


def tstream_scan_plan(store: StateStore, ops: OpBatch,
                      funs: Tuple[FunSpec, ...], *,
                      prestructured: Optional[Prestructured] = None,
                      rowmajor_ts: bool = False,
                      restructure_method: str = "auto",
                      use_pallas: bool = False) -> ScanPlan:
    # the scan path evaluates ops purely from (scanned) coefficients: every
    # fun must be associative (affine family or max) — conditional funs
    # like TAKE belong on the lockstep path and would silently mis-evaluate
    # here (identity post, success always True)
    bad = [f.name for f in funs if not f.associative]
    if bad:
        raise ValueError(
            f"tstream_scan requires associative funs; got {bad} — use the "
            "lockstep path (scheme='tstream'/'tstream_lockstep') instead")
    sops, ch = (restructure(ops, store.pad_uid, rowmajor_ts=rowmajor_ts,
                            light=True, method=restructure_method,
                            use_pallas=use_pallas)
                if prestructured is None else prestructured)
    has_max = any(store.table_is_max)

    # affine family coefficients (non-affine, max-table and invalid ops
    # become identity — invalid ops can sit mid-chain when a prestructured
    # batch had its valid mask tightened after sorting)
    a, b = affine_coeffs(funs, sops.fun, sops.operand)
    if has_max:
        is_max_s = jnp.take(store.uid_is_max(), sops.uid)  # [N]
        neutralize = (is_max_s | ~sops.valid)[:, None]
    else:
        is_max_s = None
        neutralize = (~sops.valid)[:, None]
    a = jnp.where(neutralize, jnp.ones_like(a), a)
    b = jnp.where(neutralize, jnp.zeros_like(b), b)

    # max family (ops on non-max tables, READs and invalid ops -> -inf);
    # statically elided when no table is max-typed
    m = None
    if has_max:
        is_max_fun = jnp.asarray([f.is_max for f in funs])[sops.fun]
        m = jnp.where((is_max_s & is_max_fun & sops.valid)[:, None],
                      sops.operand, -jnp.inf)

    # commit map: free from the partition path's histogram; otherwise two
    # binary-search passes over the sorted uid column
    if (ch.counts is not None
            and ch.counts.shape[-1] == store.values.shape[0]):
        commit_pos, commit_ok = commit_from_histogram(ch.counts, ch.starts)
    else:
        commit_pos, commit_ok = commit_index(sops.uid, store.values.shape[0])
    return ScanPlan(sops=sops, ch=ch, af=a, bf=b, afi=None, bfi=None,
                    mx=m, mxi=None, is_max_s=is_max_s,
                    commit_pos=commit_pos, commit_ok=commit_ok)


def tstream_scan_coefs(plan: ScanPlan, *, use_pallas: bool = False,
                       block_rows: Optional[int] = None) -> ScanPlan:
    """Segmented scans of the planned coefficients.

    Exclusive scans give each op's ``pre``; composing the op's own raw
    coefficient on top gives the *inclusive* scans and thereby ``post``
    without any per-op Fun dispatch at execution time.  ``block_rows``
    forces the Pallas kernel's block shape (None -> autotuned).
    """
    if use_pallas:
        from repro.kernels.segscan import ops as segscan_ops
        A, B = segscan_ops.segscan_affine(plan.af, plan.bf,
                                          plan.ch.seg_start, exclusive=True,
                                          block_rows=block_rows)
        M = (segscan_ops.segscan_max(plan.mx, plan.ch.seg_start,
                                     exclusive=True, block_rows=block_rows)
             if plan.mx is not None else None)
    else:
        A, B = segmented_scan_affine(plan.af, plan.bf, plan.ch.seg_start,
                                     exclusive=True)
        M = (segmented_scan_max(plan.mx, plan.ch.seg_start, exclusive=True)
             if plan.mx is not None else None)
    return _compose_inclusive(plan, A, B, M)


def _compose_inclusive(plan: ScanPlan, A, B, M) -> ScanPlan:
    """inclusive = raw ∘ exclusive (the op applied on top of its pre)."""
    Ai = plan.af * A
    Bi = plan.af * B + plan.bf
    Mi = jnp.maximum(M, plan.mx) if M is not None else None
    return dataclasses.replace(plan, af=A, bf=B, afi=Ai, bfi=Bi,
                               mx=M, mxi=Mi)


def tstream_scan_coefs_stream(plan_all: ScanPlan, *,
                              use_pallas: bool = False,
                              block_rows: Optional[int] = None) -> ScanPlan:
    """Coefficient scans for a whole stream of stacked [n_intervals, N]
    plans.  Non-Pallas: vmapped per-interval scans (bit-identical to the
    per-interval driver).  Pallas: ONE kernel dispatch over the flattened
    stream — per-interval seg_start flags isolate the scans.
    ``block_rows`` forces the kernel block shape (None -> autotuned).
    """
    if not use_pallas:
        return jax.vmap(tstream_scan_coefs)(plan_all)
    from repro.kernels.segscan import ops as segscan_ops
    bn, n, w = plan_all.af.shape
    flags = plan_all.ch.seg_start.reshape(bn * n)
    A, B = segscan_ops.segscan_affine(plan_all.af.reshape(bn * n, w),
                                      plan_all.bf.reshape(bn * n, w),
                                      flags, exclusive=True,
                                      block_rows=block_rows)
    A, B = A.reshape(bn, n, w), B.reshape(bn, n, w)
    M = None
    if plan_all.mx is not None:
        M = segscan_ops.segscan_max(plan_all.mx.reshape(bn * n, w), flags,
                                    exclusive=True,
                                    block_rows=block_rows).reshape(bn, n, w)
    return _compose_inclusive(plan_all, A, B, M)


def tstream_scan_execute(values: jnp.ndarray, plan: ScanPlan,
                         pad_uid: int, *, raw: bool = False):
    """Values-dependent stage: O(N) gathers/elementwise + one [S+1] select.

    ``raw=True`` returns results in *sorted* chain layout (the fused driver
    gathers back to flat layout in one batched pass after its scan).
    """
    sops, ch = plan.sops, plan.ch
    n = sops.uid.shape[0]
    with jax.named_scope("evaluate"):
        v0 = jnp.take(values, sops.uid, axis=0)                # [N, W]
        pre = plan.af * v0 + plan.bf
        post = plan.afi * v0 + plan.bfi
        if plan.mx is not None:
            mmask = plan.is_max_s[:, None]
            pre = jnp.where(mmask, jnp.maximum(v0, plan.mx), pre)
            post = jnp.where(mmask, jnp.maximum(v0, plan.mxi), post)
        # every fun on this path is associative -> unconditionally
        # successful; invalid ops were neutralized to identity, so their
        # post == pre
        success = sops.valid

    # commit: last op of each chain defines the new state value.  The
    # update is a per-state gather + select, not an [N] scatter.
    with jax.named_scope("commit"):
        committed = jnp.take(post, plan.commit_pos, axis=0)     # [S+1, W]
        new_values = jnp.where(plan.commit_ok[:, None], committed, values)
        new_values = new_values.at[pad_uid].set(0.0)

    with jax.named_scope("evaluate"):
        # invalid (padding) ops record nothing — match the oracle's layout
        vmask = sops.valid
        pre = jnp.where(vmask[:, None], pre, 0.0)
        post = jnp.where(vmask[:, None], post, 0.0)
        success = success & vmask
        res = dict(pre=pre, post=post, success=success)
        if not raw:
            res = {k: ch.untake(v) for k, v in res.items()}
    stats = EngineStats(
        rounds=jnp.ceil(jnp.log2(ch.max_len.astype(jnp.float32) + 1)),
        n_chains=ch.n_chains, max_chain=ch.max_len,
        fallback_ops=jnp.int32(0), n_ops=n, scheme="tstream",
        path="segscan")
    return res, new_values, stats


def eval_tstream_scan(store: StateStore, ops: OpBatch,
                      funs: Tuple[FunSpec, ...], *, use_pallas: bool = False,
                      prestructured: Optional[Prestructured] = None,
                      rowmajor_ts: bool = False,
                      restructure_method: str = "auto"):
    plan = tstream_scan_plan(store, ops, funs, prestructured=prestructured,
                             rowmajor_ts=rowmajor_ts,
                             restructure_method=restructure_method,
                             use_pallas=use_pallas)
    plan = tstream_scan_coefs(plan, use_pallas=use_pallas)
    return tstream_scan_execute(store.values, plan, store.pad_uid)


# ---------------------------------------------------------------------------
# TStream lockstep path: parallel chains, sequential within chain, level-wise
# dependency resolution (paper §IV-C2 Case 2).
# ---------------------------------------------------------------------------
def _chain_levels(sops: OpBatch, ch: Chains, n: int, max_levels: int):
    """Level-wise chain schedule for cross-chain CFun dependencies.

    level(C) = 0 if C has no gated ops, else 1 + max(level(mate chain)).
    Chains whose level does not resolve within ``max_levels`` (dependency
    cycles inside the batch) are flagged for the sequential fallback.
    """
    INF = jnp.int32(10 ** 6)
    # seg id of each op in pre-sort layout, so mate (flat idx) -> chain id
    seg_flat = ch.untake(ch.seg_id)
    gated = (sops.gate >= 0) & sops.valid
    mate_chain = seg_flat[jnp.maximum(sops.gate, 0)]
    chain_has_gate = jax.ops.segment_max(gated.astype(jnp.int32), ch.seg_id,
                                         num_segments=n) > 0
    lvl = jnp.where(chain_has_gate, INF, 0)

    def body(_, lvl):
        pred_lvl = jnp.where(gated, lvl[mate_chain], -1)
        need = jax.ops.segment_max(
            jnp.where(gated, jnp.minimum(pred_lvl + 1, INF), 0),
            ch.seg_id, num_segments=n)
        return jnp.where(chain_has_gate, jnp.minimum(need, INF), 0)

    lvl = jax.lax.fori_loop(0, max_levels, body, lvl)
    unresolved = lvl >= INF
    return lvl, unresolved


def _lockstep_sweep(values, sops: OpBatch, ch: Chains,
                    funs: Tuple[FunSpec, ...], chain_mask, results, n, pad_uid,
                    rounds):
    """Walk masked chains in lockstep: round r applies each chain's r-th op.

    Exactly one op per state per round -> conflict-free scatters, no locks.
    """
    def round_body(r, carry):
        values, res = carry
        active = (ch.pos == r) & jnp.take(chain_mask, ch.seg_id) & sops.valid
        cur = jnp.take(values, sops.uid, axis=0)
        # sops.gate holds the mate's *pre-sort* flat index; success is
        # recorded in pre-sort layout, so this gather is layout-consistent.
        gate_ok_s = _gate_open(sops.gate, res["success"][:-1])
        post, ok = apply_funs(funs, sops.fun, cur, sops.operand)
        post = jnp.where(gate_ok_s[:, None], post, cur)
        ok = ok & gate_ok_s
        scat = jnp.where(active, sops.uid, pad_uid)
        values = values.at[scat].set(jnp.where(active[:, None], post, 0.0))
        values = values.at[pad_uid].set(0.0)
        sink = jnp.where(active, ch.order, n)
        res = dict(
            pre=res["pre"].at[sink].set(cur),
            post=res["post"].at[sink].set(post),
            success=res["success"].at[sink].set(ok),
        )
        return values, res

    return jax.lax.fori_loop(0, rounds, round_body, (values, results))


def eval_tstream_lockstep(store: StateStore, ops: OpBatch,
                          funs: Tuple[FunSpec, ...], *, max_dep_levels: int = 3,
                          has_gates: bool = False,
                          prestructured: Optional[Prestructured] = None):
    sops, ch = (restructure(ops, store.pad_uid) if prestructured is None
                else prestructured)
    n = ops.n_ops
    values = store.values
    results = _empty_results(n, ops.width)

    if not has_gates:
        values, results = _lockstep_sweep(
            values, sops, ch, funs, jnp.ones((n,), bool), results, n,
            store.pad_uid, ch.max_len)
        rounds = ch.max_len
        fallback_ops = jnp.int32(0)
    else:
        lvl, unresolved = _chain_levels(sops, ch, n, max_dep_levels)
        rounds = jnp.int32(0)
        for L in range(max_dep_levels + 1):
            mask = (lvl == L)
            # this level's sweep only needs the longest level-L chain
            in_level = jnp.take(mask, ch.seg_id) & sops.valid
            lvl_rounds = jnp.max(jnp.where(in_level, ch.pos, -1)) + 1
            values, results = _lockstep_sweep(
                values, sops, ch, funs, mask, results, n, store.pad_uid,
                lvl_rounds)
            rounds = rounds + lvl_rounds
        # sequential fallback for ops in unresolved chains (cycles)
        unresolved_ops_sorted = jnp.take(unresolved, ch.seg_id) & sops.valid
        unresolved_ops = ch.untake(unresolved_ops_sorted)
        values, results, fallback_ops = _sequential_sweep(
            values, ops, funs, results, mask_flat=unresolved_ops,
            pad_uid=store.pad_uid)
        rounds = rounds + fallback_ops

    res = {k: v[:n] for k, v in results.items()}
    stats = EngineStats(rounds=rounds, n_chains=ch.n_chains,
                        max_chain=ch.max_len, fallback_ops=fallback_ops,
                        n_ops=n, scheme="tstream", path="lockstep")
    return res, values, stats


# ---------------------------------------------------------------------------
# Sequential oracle / LOCK schedule
# ---------------------------------------------------------------------------
def sweep_selected(step, carry, order, selected):
    """``carry = step(j, carry)`` for each ``j = order[k]`` with
    ``selected[j]``, in ``order``'s order.

    The trip count is the number of selected ops, not ``len(order)``: a
    sweep over a batch's few dependency-cycle ops runs a few steps.
    Returns ``(carry, count)``.
    """
    sel = jnp.take(selected, order)
    count = jnp.sum(sel, dtype=jnp.int32)
    (pos,) = jnp.nonzero(sel, size=order.shape[0], fill_value=0)
    picked = jnp.take(order, pos)
    carry = jax.lax.fori_loop(0, count,
                              lambda k, c: step(picked[k], c), carry)
    return carry, count


def _sequential_sweep(values, ops: OpBatch, funs, results, *, mask_flat,
                      pad_uid):
    """Apply the valid masked ops one at a time in global (ts, slot) order
    (S2PL schedule).  Returns ``(values, results, ops applied)``."""
    order = jnp.lexsort((ops.slot, ops.ts))  # global timestamp order

    def step(j, carry):
        values, res = carry
        uid = ops.uid[j]
        cur = values[uid]
        gate = ops.gate[j]
        gate_ok = jnp.where(gate >= 0, res["success"][jnp.maximum(gate, 0)],
                            True)
        post, ok = funs_apply_single(funs, ops.fun[j], cur, ops.operand[j])
        post = jnp.where(gate_ok, post, cur)
        ok = ok & gate_ok
        values = values.at[uid].set(post)
        values = values.at[pad_uid].set(0.0)
        res = dict(
            pre=res["pre"].at[j].set(cur),
            post=res["post"].at[j].set(post),
            success=res["success"].at[j].set(ok),
        )
        return values, res

    (values, results), count = sweep_selected(
        step, (values, results), order, mask_flat & ops.valid)
    # the pad row reads 0 after the sweep even when no op ran
    return values.at[pad_uid].set(0.0), results, count


def funs_apply_single(funs, fid, pre, operand):
    return jax.lax.switch(fid, [f.apply for f in funs], pre, operand)


def eval_lock(store: StateStore, ops: OpBatch, funs):
    """LOCK baseline == sequential oracle (conflict-equivalent ts order)."""
    n = ops.n_ops
    results = _empty_results(n, ops.width)
    values, results, _ = _sequential_sweep(
        store.values, ops, funs, results,
        mask_flat=jnp.ones((n,), bool), pad_uid=store.pad_uid)
    results = {k: v[:n] for k, v in results.items()}
    stats = EngineStats(rounds=jnp.sum(ops.valid), n_chains=jnp.int32(1),
                        max_chain=jnp.sum(ops.valid),
                        fallback_ops=jnp.int32(0), n_ops=n,
                        scheme="lock", path="sequential")
    return results, values, stats


# ---------------------------------------------------------------------------
# MVLK: multiversion — writes serialize per chain, reads resolve in parallel
# ---------------------------------------------------------------------------
def eval_mvlk(store: StateStore, ops: OpBatch, funs,
              *, has_gates: bool = False, max_dep_levels: int = 3,
              prestructured: Optional[Prestructured] = None):
    """Writes run as (lockstep) chains; READs are version lookups.

    Structurally: read ops are identity within chains (their ``pre`` is the
    version with the largest ts' < ts — exactly the paper's lwm-guarded
    multiversion read), so we can reuse the lockstep machinery; the *cost
    model* difference (reads don't occupy chain rounds) is reflected in the
    stats: rounds count only write-chain depth.
    """
    if prestructured is None:
        prestructured = restructure(ops, store.pad_uid)
    sops, ch = prestructured
    is_write = sops.kind != int(OpKind.READ)
    write_pos = _masked_positions(is_write, ch)
    write_depth = jnp.max(jnp.where(is_write, write_pos, -1)) + 1
    res, values, st = eval_tstream_lockstep(
        store, ops, funs, has_gates=has_gates, max_dep_levels=max_dep_levels,
        prestructured=prestructured)
    stats = EngineStats(rounds=write_depth, n_chains=ch.n_chains,
                        max_chain=st.max_chain,
                        fallback_ops=st.fallback_ops, n_ops=ops.n_ops,
                        scheme="mvlk", path="mv")
    return res, values, stats


def _masked_positions(mask, ch: Chains):
    """Position of each op among *masked* ops of its chain."""
    inc = jnp.cumsum(mask.astype(jnp.int32))
    seg_base = jax.lax.cummax(jnp.where(ch.seg_start,
                                        inc - mask.astype(jnp.int32), 0))
    return inc - seg_base - mask.astype(jnp.int32)


# ---------------------------------------------------------------------------
# PAT: partition-level locking (S-Store)
# ---------------------------------------------------------------------------
def eval_pat(store: StateStore, ops: OpBatch, funs, *, n_partitions: int = 16):
    """Partitions advance ts-ordered fronts; a transaction fires only when it
    holds the front of *every* partition it touches (S-Store's counter-guarded
    partition-lock acquisition).  A txn's ops within one partition are
    contiguous after the (partition, ts, slot) sort, so readiness reduces to:
    each of the txn's per-partition blocks starts at that partition's front.
    """
    n = ops.n_ops
    part = jnp.where(ops.valid, ops.uid % n_partitions, n_partitions)
    order = jnp.lexsort((ops.slot, ops.ts, part))
    part_s = jnp.take(part, order)
    seg_start = jnp.concatenate([jnp.ones((1,), bool),
                                 part_s[1:] != part_s[:-1]])
    idx = jnp.arange(n, dtype=jnp.int32)
    start_idx = jax.lax.cummax(jnp.where(seg_start, idx, 0))
    pos = idx - start_idx

    sop = jax.tree_util.tree_map(lambda x: jnp.take(x, order, axis=0), ops)
    # (txn, partition) block structure — a txn's ops in one partition are
    # contiguous (same ts) and are executed under one lock acquisition.
    blk_start = seg_start | jnp.concatenate(
        [jnp.ones((1,), bool), sop.txn[1:] != sop.txn[:-1]])
    blk_start_idx = jax.lax.cummax(jnp.where(blk_start, idx, 0))
    blk_front_pos = jnp.take(pos, blk_start_idx)  # pos of block's first op
    blk_id = jnp.cumsum(blk_start.astype(jnp.int32)) - 1
    blk_len = jnp.take(
        jax.ops.segment_sum(jnp.ones((n,), jnp.int32), blk_id,
                            num_segments=n), blk_id)
    # same-uid runs inside a block execute sequentially (slot order)
    uidrun_start = blk_start | jnp.concatenate(
        [jnp.ones((1,), bool), sop.uid[1:] != sop.uid[:-1]])

    txn_total = jax.ops.segment_sum(ops.valid.astype(jnp.int32), ops.txn,
                                    num_segments=n)
    results = _empty_results(n, ops.width)
    values = store.values
    front = jnp.zeros((n_partitions + 1,), jnp.int32)
    part_len = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), part_s,
                                   num_segments=n_partitions + 1)
    fired = jnp.zeros((n,), bool)

    def cond(carry):
        values, res, front, fired, rounds = carry
        return (rounds < n) & jnp.any(~fired & sop.valid)

    def body(carry):
        values, res, front, fired, rounds = carry
        # the op's block holds its partition's lock: the front pointer lies
        # inside the block (a partially executed block keeps the lock).
        fr = jnp.take(front, part_s)
        block_at_front = (fr >= blk_front_pos) & (fr < blk_front_pos + blk_len)
        candidate = (block_at_front | fired) & sop.valid
        txn_cand = jax.ops.segment_sum(candidate.astype(jnp.int32), sop.txn,
                                       num_segments=n)
        ready = (txn_cand >= txn_total) & (txn_total > 0)
        prev_fired = jnp.concatenate([jnp.zeros((1,), bool), fired[:-1]])
        fire = block_at_front & ~fired & sop.valid \
            & jnp.take(ready, sop.txn) & (uidrun_start | prev_fired)
        cur = jnp.take(values, sop.uid, axis=0)
        # intra-txn gates: mates fire in the same round — resolve ungated first
        post0, ok0 = apply_funs(funs, sop.fun, cur, sop.operand)
        sink_now = jnp.where(fire & (sop.gate < 0), order, n)
        succ_now = jnp.zeros((n + 1,), bool).at[sink_now].set(ok0)
        succ_known = succ_now[:-1] | res["success"][:-1]
        gate_ok = jnp.where(sop.gate >= 0,
                            jnp.take(succ_known, jnp.maximum(sop.gate, 0)),
                            True)
        post = jnp.where(gate_ok[:, None], post0, cur)
        ok = ok0 & gate_ok
        scat = jnp.where(fire, sop.uid, store.pad_uid)
        values = values.at[scat].set(jnp.where(fire[:, None], post, 0.0))
        values = values.at[store.pad_uid].set(0.0)
        sink = jnp.where(fire, order, n)
        res = dict(pre=res["pre"].at[sink].set(cur),
                   post=res["post"].at[sink].set(post),
                   success=res["success"].at[sink].set(ok))
        fired = fired | fire
        adv = jax.ops.segment_sum(fire.astype(jnp.int32), part_s,
                                  num_segments=n_partitions + 1)
        front = front + adv
        return values, res, front, fired, rounds + 1

    values, results, front, fired, rounds = jax.lax.while_loop(
        cond, body, (values, results, front, fired, jnp.int32(0)))
    results = {k: v[:n] for k, v in results.items()}
    stats = EngineStats(rounds=rounds, n_chains=jnp.int32(n_partitions),
                        max_chain=jnp.max(part_len[:n_partitions]),
                        fallback_ops=jnp.int32(0), n_ops=n,
                        scheme="pat", path="partition")
    return results, values, stats


# ---------------------------------------------------------------------------
# No-Lock upper bound (incorrect by design)
# ---------------------------------------------------------------------------
def eval_nolock(store: StateStore, ops: OpBatch, funs):
    pre = jnp.take(store.values, jnp.where(ops.valid, ops.uid, store.pad_uid),
                   axis=0)
    post, ok = apply_funs(funs, ops.fun, pre, ops.operand)
    scat = jnp.where(ops.valid & (ops.kind != int(OpKind.READ)), ops.uid,
                     store.pad_uid)
    values = store.values.at[scat].set(post)
    values = values.at[store.pad_uid].set(0.0)
    res = dict(pre=jnp.concatenate([pre, pre[:1]]),
               post=jnp.concatenate([post, post[:1]]),
               success=jnp.concatenate([ok, ok[:1]]))
    res = {k: v[: ops.n_ops] for k, v in res.items()}
    stats = EngineStats(rounds=jnp.int32(1), n_chains=jnp.int32(ops.n_ops),
                        max_chain=jnp.int32(1), fallback_ops=jnp.int32(0),
                        n_ops=ops.n_ops, scheme="nolock", path="parallel")
    return res, values, stats


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------
SCHEMES = ("tstream", "tstream_scan", "tstream_lockstep", "lock", "mvlk",
           "pat", "nolock")

# schemes whose evaluation consumes the restructured (chain-sorted) view —
# for these, ``evaluate`` lexsorts exactly once and threads the result down.
CHAIN_SCHEMES = frozenset(
    {"tstream", "tstream_scan", "tstream_lockstep", "mvlk"})


def evaluate(store: StateStore, ops: OpBatch, funs: Tuple[FunSpec, ...],
             scheme: str = "tstream", *, associative_only: bool = False,
             has_gates: bool = False, n_partitions: int = 16,
             max_dep_levels: int = 3, use_pallas: bool = False,
             prestructured: Optional[Prestructured] = None,
             rowmajor_ts: bool = False, restructure_method: str = "auto"):
    if scheme in CHAIN_SCHEMES and prestructured is None:
        prestructured = restructure(ops, store.pad_uid,
                                    rowmajor_ts=rowmajor_ts,
                                    method=restructure_method,
                                    use_pallas=use_pallas)
    if scheme == "tstream":
        if associative_only and not has_gates:
            return eval_tstream_scan(store, ops, funs, use_pallas=use_pallas,
                                     prestructured=prestructured)
        return eval_tstream_lockstep(store, ops, funs, has_gates=has_gates,
                                     max_dep_levels=max_dep_levels,
                                     prestructured=prestructured)
    if scheme == "tstream_scan":
        return eval_tstream_scan(store, ops, funs, use_pallas=use_pallas,
                                 prestructured=prestructured)
    if scheme == "tstream_lockstep":
        return eval_tstream_lockstep(store, ops, funs, has_gates=has_gates,
                                     max_dep_levels=max_dep_levels,
                                     prestructured=prestructured)
    if scheme == "lock":
        return eval_lock(store, ops, funs)
    if scheme == "mvlk":
        return eval_mvlk(store, ops, funs, has_gates=has_gates,
                         max_dep_levels=max_dep_levels,
                         prestructured=prestructured)
    if scheme == "pat":
        return eval_pat(store, ops, funs, n_partitions=n_partitions)
    if scheme == "nolock":
        return eval_nolock(store, ops, funs)
    raise ValueError(f"unknown scheme {scheme!r}; choose from {SCHEMES}")
