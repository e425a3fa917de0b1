"""Dual-mode scheduling (paper §IV-B, D1).

One engine *step* processes exactly one punctuation interval:

  compute mode      vmapped PRE_PROCESS + op registration into blotters
  (TXN_START)       punctuation boundary — barrier analogue is the data
                    dependence between phases inside one jitted function
  state-access mode restructure + evaluate the postponed transaction batch
  compute mode      vmapped POST_PROCESS over stored events + access results

The punctuation interval is the leading batch axis; the progress controller
assigns monotonically increasing timestamps (the paper's fetch&add counter
becomes ``ts_base + arange``: SPMD-deterministic and contention-free).

Two drivers share the per-interval logic (DESIGN.md §2.4):

* ``run_stream(fused=False)`` — the host-side loop: one jit dispatch, one
  store rebuild and one host<->device round-trip *per interval*.  Kept as
  the reference / debugging path.
* ``run_stream(fused=True)``  — the device-resident path: the stream is
  reshaped to ``[n_intervals, interval, ...]`` and the whole run executes
  as a single ``jax.lax.scan`` inside one jitted call with the state
  buffer donated.  Compute mode (pre-process + op registration) is
  intrinsically interval-parallel, so it is vmapped over *all* intervals
  up front; only state-access mode is sequential across punctuations.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blotter import AppSpec, build_opbatch
from .engines import (CHAIN_SCHEMES, EngineStats, evaluate,
                      simple_affine_luts, tstream_scan_coefs_stream,
                      tstream_scan_execute, tstream_scan_plan)
from .restructure import (megakernel_engaged, restructure, restructure_path,
                          restructure_stream)
from .types import OpResults, StateStore


def named(name: str, fn):
    """``fn`` (a ``functools.partial``) under a stable name: ``jax.jit``
    names its program ``jit_<name>``, which is how a profiler's
    ``XLA Modules`` rows and a compiled module's ``HloModule`` line tell
    the service's programs apart."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    scheme: str = "tstream"
    n_partitions: int = 16
    max_dep_levels: int = 3
    use_pallas: bool = False
    abort_repass: bool = False   # re-run with aborted txns masked (§IV-C2)
    # sharded streaming: resolve uid -> owner through the hash-probe
    # kernel instead of the direct-addressed gather (DESIGN.md §2.5)
    use_hash_probe_route: bool = False
    # restructure backbone: "auto" resolves the partition -> packed-sort ->
    # lexsort -> megakernel ladder (DESIGN.md §2.1/§2.8); force a rung for
    # parity tests/benches ("megakernel" forces the fused chain-eval rung)
    restructure_method: str = "auto"
    # force kernel block parameters in the fused drivers' dispatches,
    # overriding the autotune cache: a tuple of (kernel, value) pairs,
    # e.g. (("segscan", 128), ("radix_partition", 512)).  Empty () defers
    # to kernels/autotune.  (Tuple-of-pairs, not dict: EngineConfig must
    # stay hashable for jit closure.)
    kernel_block_params: tuple = ()

    def block_param(self, kernel: str):
        return dict(self.kernel_block_params).get(kernel)


class DualModeEngine:
    """The TStream engine bound to one application.

    With ``mesh``/``layout`` the engine becomes device-parallel: the
    ownership permutation and routing tables are built once here, and
    ``run_stream`` dispatches the whole stream as one sharded fused
    program (``core/sharded_stream``).
    """

    def __init__(self, app: AppSpec, store: StateStore,
                 cfg: EngineConfig = EngineConfig(), *,
                 mesh=None, layout: str = "shared_nothing",
                 exchange_slack: float = 2.0):
        self.app = app
        self.cfg = cfg
        self.init_store = store
        self._step = jax.jit(named("tstream_step",
                                   partial(_step_impl, app=app, cfg=cfg)))
        self._fused = jax.jit(
            named("tstream_chunk",
                  partial(_fused_impl, app=app, cfg=cfg, store=store)),
            donate_argnums=0)
        # plan variants (adaptive control plane, DESIGN.md §2.9): extra
        # jitted builds of the SAME fused program with scheme/rung
        # overrides, selectable per chunk via run_stream_chunk(variant=)
        self._variants: Dict[Tuple[str, str], object] = {}
        # THE output program: all drivers post-process through this one
        # jitted function on identical shapes (see _post_stream)
        self._post = jax.jit(named("tstream_outputs",
                                   partial(_post_stream, app=app)))
        self._sharded = None
        if mesh is not None:
            from .sharded_stream import ShardedStream
            self._sharded = ShardedStream(app, store, cfg, mesh, layout,
                                          exchange_slack=exchange_slack)

    def step(self, values: jnp.ndarray, events: Dict[str, jnp.ndarray],
             ts_base) -> Tuple[Dict, jnp.ndarray, EngineStats]:
        """Process one punctuation interval. Returns (outputs, values', stats)."""
        store = dataclasses.replace(self.init_store, values=values)
        res, ebs, values, stats = self._step(store, events,
                                             jnp.asarray(ts_base, jnp.int32))
        lift = jax.tree_util.tree_map(lambda x: x[None], (res, ebs))
        outs = self._post(*lift)
        return jax.tree_util.tree_map(lambda x: x[0], outs), values, stats

    def run_stream(self, values, event_stream, punct_interval: int,
                   fused: bool = True):
        """Drive an event stream punctuation by punctuation.

        ``fused=True`` (default) runs every interval inside one jitted
        ``lax.scan`` with the state buffer donated — no per-interval host
        round-trips.  ``fused=False`` is the host-side per-interval loop;
        both produce identical outputs and final state.

        Engines built with a ``mesh`` run the sharded fused driver
        (fused-only); exchange statistics land in
        ``self.last_exchange_stats`` and overflow drops are logged.
        """
        if self._sharded is not None:
            assert fused, "sharded run_stream has no unfused host loop"
            outs, values = self._sharded.run_stream(values, event_stream,
                                                    punct_interval)
            self.last_exchange_stats = self._sharded.last_stats
            return outs, values
        if not fused:
            res_l, ebs_l = [], []
            ts = 0
            for batch in _batches(event_stream, punct_interval):
                store = dataclasses.replace(self.init_store, values=values)
                res, ebs, values, stats = self._step(store, batch,
                                                     jnp.int32(ts))
                ts += punct_interval
                res_l.append(res)
                ebs_l.append(ebs)
            if not res_l:
                return [], values
            stack = lambda *xs: jnp.stack(xs)
            res_all = jax.tree_util.tree_map(stack, *res_l)
            ebs_all = jax.tree_util.tree_map(stack, *ebs_l)
            return self._outs(res_all, ebs_all, len(res_l)), values

        n = len(next(iter(event_stream.values())))
        n_intervals = n // punct_interval
        if n_intervals == 0:
            return [], values
        batched = {}
        for k, v in event_stream.items():
            v = np.asarray(v)[: n_intervals * punct_interval]
            batched[k] = jnp.asarray(
                v.reshape((n_intervals, punct_interval) + v.shape[1:]))
        # the jitted call donates its values argument (in-place carry on
        # device); hand it a private copy so the caller's buffer survives
        res_all, ebs_all, values, _ = self._fused(
            jnp.array(values, copy=True), batched, jnp.int32(0))
        return self._outs(res_all, ebs_all, n_intervals), values

    def _outs(self, res_all, ebs_all, n_intervals: int):
        """Shared output program + one bulk D2H, split per interval."""
        outs = jax.device_get(self._post(res_all, ebs_all))
        return [jax.tree_util.tree_map(lambda x, i=i: x[i], outs)
                for i in range(n_intervals)]

    # -- chunked service API (runtime/service.py; DESIGN.md §2.6/§2.9) -----
    def ensure_variant(self, scheme: str | None = None,
                       restructure_method: str | None = None):
        """Pre-build a jitted plan variant with scheme/rung overridden.

        Returns the variant key to pass to :meth:`run_stream_chunk`, or
        ``None`` when the requested plan IS the construction plan (the
        base ``_fused`` program).  Building is idempotent and lazy —
        compilation itself still happens at the variant's first dispatch
        per chunk shape.  Single-device only: the sharded driver's
        adaptive lattice is {exchange slack, chunk size}, both handled
        elsewhere (``ShardedStream.set_exchange_slack`` / the service's
        chunking loop).
        """
        sch = scheme or self.cfg.scheme
        rung = restructure_method or self.cfg.restructure_method
        if (sch, rung) == (self.cfg.scheme, self.cfg.restructure_method):
            return None
        assert self._sharded is None, \
            "sharded driver has no scheme/rung plan variants"
        key = (sch, rung)
        if key not in self._variants:
            cfg = dataclasses.replace(self.cfg, scheme=sch,
                                      restructure_method=rung)
            self._variants[key] = jax.jit(
                named(f"tstream_chunk_{sch}_{rung}",
                      partial(_fused_impl, app=self.app, cfg=cfg,
                              store=self.init_store)),
                donate_argnums=0)
        return key

    def run_stream_chunk(self, values, batched, ts0: int, variant=None):
        """One device-resident chunk of a continuous run.

        ``batched`` leaves are ``[K, interval, ...]`` **device** arrays and
        ``values`` is DONATED: the caller owns the buffer and threads the
        returned carry into the next chunk, so K-chunked execution scans
        the same per-interval schedule as one monolithic ``run_stream``
        over the concatenated events (bit-identity pinned in
        tests/test_service.py).  ``ts0`` is the global timestamp base of
        the chunk's first interval (= global interval index × interval).
        ``variant`` selects a pre-built plan variant (``ensure_variant``);
        ``None`` runs the construction plan.

        Returns ``(res_all, ebs_all, values', stats)`` as *unmaterialized*
        device arrays — nothing blocks, so the caller can stage and
        dispatch chunk *i+1* while chunk *i* still runs.  ``stats`` is
        ``dict(engine=EngineStats)`` ([K]-stacked scan leaves) on the
        single-device driver and ``dict(exchange=...)`` (dropped/shipped/
        max_fill per interval + capacity) on the sharded one.  Materialize
        per-interval outputs later via :meth:`post_outputs`.
        """
        if self._sharded is not None:
            assert variant is None, \
                "sharded driver has no scheme/rung plan variants"
            res_all, ebs_all, values, xst = self._sharded.run_chunk(
                values, batched, ts0)
            return res_all, ebs_all, values, dict(exchange=xst)
        fn = self._fused if variant is None else self._variants[variant]
        res_all, ebs_all, values, est = fn(values, batched, jnp.int32(ts0))
        return res_all, ebs_all, values, dict(engine=est)

    def pallas_kernels(self, interval: int) -> Tuple[str, ...]:
        """Names of the Pallas kernels (each ``pallas_call``'s ``name``)
        the single-device fused chunk program dispatches for
        ``interval``-event intervals: the restructure rung that
        ``cfg.restructure_method`` resolves to, and which of its stages
        run a kernel rather than the XLA reference.  Empty without
        ``use_pallas``.  Mirrors ``_fused_impl``'s dispatch, so a compiled
        chunk program can be checked against it."""
        from repro.kernels.megakernel import mega_kernel_fits
        from repro.kernels.radix_partition.ops import kernel_fits
        app, cfg, store = self.app, self.cfg, self.init_store
        if not cfg.use_pallas or cfg.scheme not in CHAIN_SCHEMES:
            return ()
        n = interval * app.max_ops
        n_slots = store.values.shape[0]
        radix = ("radix_partition",) if kernel_fits(n_slots, n) else ()
        rung, path = self._chunk_route(interval, None)
        if rung == "mega":
            return radix + (("fused_chain",)
                            if mega_kernel_fits(n, n_slots) else ())
        kernels = radix if rung == "partition" else ()
        # engines.evaluate's segmented-scan path (the fast path, or the
        # per-interval scan under abort repass)
        if path == "segscan":
            kernels += ("segscan_affine",) + (
                ("segscan_max",) if any(store.table_is_max) else ())
        return kernels

    def post_outputs(self, res_all, ebs_all, n_intervals: int):
        """Materialize a chunk's per-interval outputs (blocks on D2H)."""
        return self._outs(res_all, ebs_all, n_intervals)

    def chunk_program(self, variant=None):
        """The jitted chunk program :meth:`run_stream_chunk` dispatches."""
        if self._sharded is not None:
            return self._sharded._impl
        return self._fused if variant is None else self._variants[variant]

    def chunk_route(self, interval: int, variant=None) -> Tuple[str, str]:
        """``(rung, path)`` of the chunk program for ``interval``-event
        intervals: the restructure rung (``partition``, ``packed``,
        ``lexsort``, ``mega``; ``none`` for a scheme without chains) and
        the evaluation path (``segscan`` or ``lockstep``; the scheme's
        name for a scheme without chains).  Mirrors ``_fused_impl``'s and
        the sharded driver's dispatch: a host-side function of the
        configuration and shapes, with nothing compiled or run."""
        if self._sharded is not None:
            return self._sharded.chunk_route(interval)
        return self._chunk_route(interval, variant)

    def _chunk_route(self, interval: int, variant) -> Tuple[str, str]:
        cfg = self.cfg
        if variant is not None:
            cfg = dataclasses.replace(cfg, scheme=variant[0],
                                      restructure_method=variant[1])
        app, store = self.app, self.init_store
        if cfg.scheme not in CHAIN_SCHEMES:
            return "none", cfg.scheme
        n = interval * app.max_ops
        if _assoc_fast(app, cfg) and megakernel_engaged(
                n, store.values.shape[0], method=cfg.restructure_method,
                has_max=any(store.table_is_max),
                funs_simple=simple_affine_luts(app.funs) is not None,
                use_pallas=cfg.use_pallas):
            return "mega", "segscan"
        rung = restructure_path(n, store.pad_uid, rowmajor_ts=True,
                                method=cfg.restructure_method)
        return rung, _chain_path(app, cfg)

    def chunk_lowered_text(self, values, batched, variant=None) -> str:
        """Compiled (post-SPMD) HLO text of the chunk program that runs
        these carry/batch shapes.  Only shapes, dtypes and shardings are
        read from ``values``/``batched`` (``jax.ShapeDtypeStruct`` leaves
        will do), never data.  Once the program has run at these shapes
        the text comes from the executable the call compiled, with no
        compilation of its own."""
        spec = jax.tree_util.tree_map(shape_spec, (values, batched))
        ts = jax.ShapeDtypeStruct((), jnp.int32)
        return self.chunk_program(variant).lower(
            spec[0], spec[1], ts).compile().as_text()

    # -- elastic resharding / carry API (DESIGN.md §2.10) -----------------
    # The service's chunk loop threads an OPAQUE carry: canonical [S+1, W]
    # values on the single-device driver, the resident ownership-block
    # layout on the sharded one.  Snapshots and final stats always go
    # through carry_out so checkpoints stay canonical (restorable onto
    # any ownership/layout).
    def carry_in(self, values):
        """Canonical [S+1, W] values -> the driver's resident carry."""
        if self._sharded is not None:
            return self._sharded.carry_in(values)
        return values

    def carry_out(self, carry):
        """Resident carry -> canonical [S+1, W] values (no donation)."""
        if self._sharded is not None:
            return self._sharded.carry_out(carry)
        return carry

    @property
    def owners(self):
        """Current ownership overrides (() = pure striping)."""
        return self._sharded.owners if self._sharded is not None else ()

    @property
    def reshardable(self) -> bool:
        return self._sharded is not None and self._sharded.reshardable

    def rebind_ownership(self, overrides) -> None:
        """Rebind the sharded plan to ``overrides`` WITHOUT moving data —
        for restores onto a migrated layout (the snapshot's canonical
        values re-enter through ``carry_in`` under the new binding).
        Identity on the single-device driver (ownership is a no-op
        there, so replayed ``reshard`` decisions stay harmless)."""
        if self._sharded is not None and overrides != self._sharded.owners:
            self._sharded.set_ownership(overrides)

    def apply_resharding(self, carry, overrides):
        """Live migration of the resident carry onto ``overrides``
        (sharded driver; see ``ShardedStream.reshard``).  Returns
        ``(carry, moved_rows)``; identity on single-device."""
        if self._sharded is None:
            return carry, 0
        return self._sharded.reshard(carry, overrides)


def shape_spec(x):
    """``x``'s shape, dtype and (for a committed array) sharding."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    sharding = (x.sharding if isinstance(x, jax.Array)
                and getattr(x, "committed", False) else None)
    return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=sharding)


def _chain_path(app: AppSpec, cfg: EngineConfig) -> str:
    """The evaluation path of a chain scheme: ``segscan`` (associative
    chains as segmented scans) or ``lockstep`` (chains walked op by op)."""
    if _assoc_fast(app, cfg) or cfg.scheme == "tstream_scan" or (
            cfg.scheme == "tstream" and app.associative_only
            and not app.has_gates):
        return "segscan"
    return "lockstep"


def _batches(stream: Dict[str, np.ndarray], interval: int):
    n = len(next(iter(stream.values())))
    for i in range(0, n - n % interval, interval):
        yield {k: jnp.asarray(v[i : i + interval]) for k, v in stream.items()}


def _eval_interval(store: StateStore, ops, *, app: AppSpec,
                   cfg: EngineConfig, prestructured=None):
    """State-access mode for one interval: restructure exactly once,
    evaluate, optionally re-pass with aborted txns masked (reusing the
    same sort).  Returns materialized per-op results; post-processing
    happens in the shared output program (``_post_stream``)."""
    pres = prestructured
    if pres is None and cfg.scheme in CHAIN_SCHEMES:
        # the segmented-scan path reads only 4 sorted columns — skip the rest
        light = (cfg.scheme in ("tstream", "tstream_scan")
                 and app.associative_only)
        with jax.named_scope("restructure"):
            pres = restructure(ops, store.pad_uid, rowmajor_ts=True,
                               light=light, method=cfg.restructure_method,
                               use_pallas=cfg.use_pallas)
    with jax.named_scope("evaluate"):
        res, values, stats = evaluate(
            store, ops, app.funs, cfg.scheme,
            associative_only=app.associative_only, has_gates=app.has_gates,
            n_partitions=cfg.n_partitions,
            max_dep_levels=cfg.max_dep_levels,
            use_pallas=cfg.use_pallas, prestructured=pres)

    batch = ops.n_ops // app.max_ops
    if cfg.abort_repass and app.may_abort:
        # Abort handling without rollback: a transaction whose ops failed is
        # masked out and the batch is re-evaluated from the pre-batch values.
        # (Addresses the paper's §IV-F multi-write rollback limitation.)
        # Chain geometry only depends on uids, so the repass tightens the
        # ``valid`` mask in both layouts instead of re-sorting.
        with jax.named_scope("abort_repass"):
            succ = res["success"].reshape(batch, app.max_ops)
            valid = ops.valid.reshape(batch, app.max_ops)
            txn_ok = jnp.all(succ | ~valid, axis=1)
            keep = jnp.repeat(txn_ok, app.max_ops)
            ops2 = dataclasses.replace(ops, valid=ops.valid & keep)
            pres2 = None
            if pres is not None:
                sops, ch = pres
                pres2 = (dataclasses.replace(
                    sops, valid=sops.valid & ch.take(keep)), ch)
            first = stats
            res, values, stats = evaluate(
                store, ops2, app.funs, cfg.scheme,
                associative_only=app.associative_only,
                has_gates=app.has_gates, n_partitions=cfg.n_partitions,
                max_dep_levels=cfg.max_dep_levels,
                use_pallas=cfg.use_pallas, prestructured=pres2)
            # the interval's fallback work is both passes'
            stats = dataclasses.replace(
                stats, fallback_ops=stats.fallback_ops + first.fallback_ops)

    return res, values, stats


def _post_stream(res_all, ebs_all, *, app: AppSpec):
    """Post-process a whole stream's stacked per-op results.

    This is THE output program: every driver (host loop, fused scan,
    sharded fused) evaluates to *materialized* per-op results and feeds
    them through this one jitted function on identical ``[n_intervals,
    N, ...]`` shapes.  Keeping the app-level reductions in a single
    compilation context is what makes the drivers' outputs bit-identical:
    XLA CPU lowers a reduction fused into a producer loop with a
    different float association than a standalone reduction (~1-ulp
    drift), so post-processing must never compile inside one driver's
    evaluation fusion but not another's.
    """
    return jax.vmap(lambda r, e: _post_interval(r, e, app=app))(res_all,
                                                                ebs_all)


def _post_interval(res, ebs, *, app: AppSpec):
    """Compute mode resumes: post-process one interval's stored events.

    (Results may carry kernel-padded lanes in the fused Pallas path —
    sliced here.)  Drivers do not call this directly; outputs go through
    ``_post_stream`` so every driver shares one compilation context.
    """
    batch = res["success"].shape[0] // app.max_ops
    shaped = OpResults(
        pre=res["pre"].reshape(batch, app.max_ops, -1)[..., : app.width],
        post=res["post"].reshape(batch, app.max_ops, -1)[..., : app.width],
        success=res["success"].reshape(batch, app.max_ops),
    )
    return jax.vmap(app.post_process)(ebs, shaped)


def _step_impl(store: StateStore, events, ts_base, *, app: AppSpec,
               cfg: EngineConfig):
    # -- compute mode: pre-process + postpone state access (D1) ------------
    with jax.named_scope("register"):
        ops, ebs = build_opbatch(app, store, events, ts_base)
    # -- state access mode: dynamic restructuring execution (D2) -----------
    res, values, stats = _eval_interval(store, ops, app=app, cfg=cfg)
    return res, ebs, values, stats


def _assoc_fast(app: AppSpec, cfg: EngineConfig) -> bool:
    """Whether the fused chunk program takes the associative fast path."""
    return (cfg.scheme in ("tstream", "tstream_scan")
            and app.associative_only
            and not (cfg.abort_repass and app.may_abort))


def _fused_impl(values, events_b, ts0, *, app: AppSpec, cfg: EngineConfig,
                store: StateStore):
    """Whole-stream driver: one jitted call, ``lax.scan`` over intervals.

    ``events_b`` leaves are [n_intervals, interval, ...]; ``values`` is the
    donated state buffer.  Everything values-independent — op registration,
    the restructure sort, and (on the associative path) the coefficient
    scans and commit gather maps — is hoisted out of the sequential scan
    and batched over all intervals; the scan body carries only the
    values-dependent evaluation.
    """
    some = jax.tree_util.tree_leaves(events_b)[0]
    n_intervals, interval = some.shape[0], some.shape[1]
    store = dataclasses.replace(store, values=values)

    # compute mode for ALL intervals at once (interval-parallel)
    with jax.named_scope("register"):
        ts_bases = ts0 + jnp.arange(n_intervals, dtype=jnp.int32) * interval
        ops_all, ebs_all = jax.vmap(
            lambda ev, tb: build_opbatch(app, store, ev, tb))(events_b,
                                                              ts_bases)

    assoc_fast = _assoc_fast(app, cfg)

    # Pallas fast path: lane-pad operands & state to the kernel width ONCE
    # per stream, so per-interval kernel dispatch does no lane padding.
    padded = False
    if cfg.use_pallas and assoc_fast:
        from repro.kernels.segscan import kernel as K
        if app.width < K.LANES:
            lane_pad = K.LANES - app.width
            ops_all = dataclasses.replace(
                ops_all, operand=jnp.pad(
                    ops_all.operand, ((0, 0), (0, 0), (0, lane_pad))))
            store = dataclasses.replace(
                store, values=jnp.pad(store.values, ((0, 0), (0, lane_pad))))
            padded = True

    if assoc_fast:
        res_all, values, stats = _fused_assoc(store, ops_all, app=app,
                                              cfg=cfg)
        if padded:
            values = values[:, : app.width]
        return res_all, ebs_all, values, stats

    # generic path: hoist the restructure pass for chain schemes; the scan
    # body evaluates one interval from its prestructured batch
    pres_all = None
    if cfg.scheme in CHAIN_SCHEMES:
        with jax.named_scope("restructure"):
            pres_all = restructure_stream(
                ops_all, store.pad_uid, rowmajor_ts=True,
                method=cfg.restructure_method, use_pallas=cfg.use_pallas,
                block_rows=cfg.block_param("radix_partition"))

    def body(values, xs):
        ops, pres = xs
        st = dataclasses.replace(store, values=values)
        res, values, stats = _eval_interval(st, ops, app=app, cfg=cfg,
                                            prestructured=pres)
        return values, (res, stats)

    values, (res_all, stats) = jax.lax.scan(body, store.values,
                                            (ops_all, pres_all))
    return res_all, ebs_all, values, stats


def _fused_assoc(store: StateStore, ops_all, *, app: AppSpec,
                 cfg: EngineConfig):
    """Associative fast path: the scan body is O(N) gathers + elementwise.

    The one-pass restructure plan (partition ranks + histograms, ONE
    kernel dispatch under ``use_pallas``), coefficient scans and commit
    gather maps for ALL intervals run batched before the scan; results
    return to flat layout inside the body and stack as scan outputs
    (post-processing happens in the shared output program,
    ``_post_stream``).
    """
    luts = simple_affine_luts(app.funs)
    if megakernel_engaged(ops_all.uid.shape[-1], store.values.shape[0],
                          method=cfg.restructure_method,
                          has_max=any(store.table_is_max),
                          funs_simple=luts is not None,
                          use_pallas=cfg.use_pallas):
        return _fused_assoc_mega(store, ops_all, luts=luts, cfg=cfg)

    with jax.named_scope("restructure"):
        pres_all = restructure_stream(
            ops_all, store.pad_uid, rowmajor_ts=True, light=True,
            method=cfg.restructure_method, use_pallas=cfg.use_pallas,
            block_rows=cfg.block_param("radix_partition"))
    with jax.named_scope("evaluate"):
        plan_all = jax.vmap(
            lambda o, p: tstream_scan_plan(store, o, app.funs,
                                           prestructured=p))(ops_all,
                                                             pres_all)
        plan_all = tstream_scan_coefs_stream(
            plan_all, use_pallas=cfg.use_pallas,
            block_rows=cfg.block_param("segscan"))

    def body(values, plan):
        # tstream_scan_execute names its own evaluate and commit parts
        res, new_values, stats = tstream_scan_execute(
            values, plan, store.pad_uid)
        return new_values, (res, stats)

    values, (res_all, stats) = jax.lax.scan(body, store.values, plan_all)
    return res_all, values, stats


def _fused_assoc_mega(store: StateStore, ops_all, *, luts,
                      cfg: EngineConfig):
    """Megakernel rung of the associative fast path (DESIGN.md §2.8).

    The hoisted plan shrinks to the partition permutation + histograms
    (``geometry=False`` — no per-row seg_id/pos/seg_end, no materialized
    [N, W] coefficient arrays); the scan body evaluates each interval's
    chains through ONE fused partition→segscan→commit dispatch
    (``kernels/megakernel``), bit-identical to the staged rungs.
    """
    from repro.kernels.megakernel import fused_chain_eval

    a_lut, b_lut = luts
    with jax.named_scope("restructure"):
        sops_all, ch_all = restructure_stream(
            ops_all, store.pad_uid, rowmajor_ts=True, light=True,
            method="partition", use_pallas=cfg.use_pallas, geometry=False,
            block_rows=cfg.block_param("radix_partition"))

    def body(values, xs):
        sops, ch = xs
        # one fused partition -> segscan -> commit dispatch: all evaluate
        with jax.named_scope("evaluate"):
            res, new_values, stats = fused_chain_eval(
                values, sops, ch, store.pad_uid, a_lut=a_lut, b_lut=b_lut,
                use_pallas=cfg.use_pallas)
            res = {k: ch.untake(v) for k, v in res.items()}
        return new_values, (res, stats)

    values, (res_all, stats) = jax.lax.scan(body, store.values,
                                            (sops_all, ch_all))
    return res_all, values, stats
