"""Device-parallel fused streaming: sharded ``run_stream`` (DESIGN.md §2.5).

The whole event stream runs as ONE jitted ``shard_map`` whose interior is
the same hoist-then-scan schedule as the single-device fused driver
(``scheduler._fused_impl``), with state partitioned by ownership:

* **compute mode is event-parallel**: each device pre-processes and
  registers ops for its contiguous slice of every punctuation interval;
* **ops are owner-routed, not replicated**: each device buckets its ops
  by ``owner(uid)`` with the capacity-padded one-pass counting partition
  (``core/ownership`` over ``kernels/radix_partition``) and ships them
  with a single ``all_to_all``
  covering *every interval at once* — O(N + padding) exchanged rows per
  interval instead of the per-batch path's O(n_dev · N) replication;
* **each device restructures and evaluates only its local chains**; the
  restructure sort, affine/max coefficient scans and per-state commit
  maps are hoisted out of the interval scan exactly as in
  ``scheduler._fused_assoc``.  The segment-relative segmented scans
  (``restructure.py``) make chain results independent of where a chain
  lands in a device's buffer, so the sharded schedule is bit-identical
  to the single-device fused driver;
* **results are returned by the reverse exchange** (same buckets,
  mirrored ``all_to_all``) and post-processing stays event-parallel.

Layouts (paper §IV-E / Fig. 14):

  shared_nothing    state blocks per device; zero collectives inside the
                    interval scan (the exchange is hoisted)
  shared_per_socket state blocks per socket, replicated across that
                    socket's cores; ops routed to the owning socket then
                    all-gathered intra-socket; chains split across cores;
                    one intra-socket merge per interval
  shared_everything state replicated; chains routed round-robin across
                    all devices; one global merge per interval

State merges use an ownership-masked ``pmax`` select (every slot has
exactly one writer), not delta addition, so all layouts stay bit-exact.

Non-associative / gated apps (SL, OB) run the lockstep schedule sharded
under ``shared_nothing`` on a 1-D mesh: chains walk locally; cross-chain
CFun gates resolve level-wise with the per-level success frontier merged
across devices ([N+1] bool ``pmax`` on global op indices); dependency-
cycle residue falls back to a replicated sequential sweep over the
gathered residue ops (all devices compute it identically, then retake
their shard).  Exchange-capacity overflow *drops* ops; drops are counted
per interval and surfaced in the engine stats — never silent.
"""
from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from .blotter import AppSpec, build_opbatch
from .engines import (apply_funs, funs_apply_single, simple_affine_luts,
                      sweep_selected, tstream_scan_coefs_stream,
                      tstream_scan_execute, tstream_scan_plan)
from .ownership import (LAYOUTS, bucket_by_owner, build_ownership,
                        build_probe_route, chunk_shard_output,
                        exchange_capacity, make_local_store, migration_plan,
                        permute_values, route_gather, unchunk_output,
                        unpermute_values, unroute_gather)
from .restructure import (Chains, megakernel_engaged, restructure_path,
                          restructure_stream)
from .scheduler import named
from .types import OpBatch, StateStore

log = logging.getLogger(__name__)

_INF = jnp.int32(10 ** 6)


def stream_mesh(shape, axis_names, *, devices=None):
    """The device mesh ``ShardedStream`` runs on.

    Every axis is ``Auto``: ``ShardedStream`` places data itself through
    ``shard_map`` specs, and its host-side gathers (``_sharded_blocks_impl``,
    the ownership tables) leave sharding propagation to XLA.
    ``jax.make_mesh`` without ``axis_types`` makes *Explicit* axes, under
    which those gathers are refused at trace time.
    """
    shape, axis_names = tuple(shape), tuple(axis_names)
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def _bool_pmax(x: jnp.ndarray, axes) -> jnp.ndarray:
    return jax.lax.pmax(x.astype(jnp.int32), axes) > 0


class ShardedStream:
    """Sharded fused streaming driver bound to one (app, mesh, layout).

    The ownership permutation, routing tables and the jitted whole-stream
    program are built ONCE here — per-call work is limited to reshaping
    the host stream and one dispatch.
    """

    def __init__(self, app: AppSpec, store: StateStore, cfg, mesh,
                 layout: str = "shared_nothing", exchange_slack: float = 2.0):
        assert layout in LAYOUTS, layout
        if any(t != AxisType.Auto for t in mesh.axis_types):
            raise ValueError(
                f"sharded run_stream needs a mesh with Auto axes (got "
                f"{mesh.axis_types}); build it with "
                f"core.sharded_stream.stream_mesh")
        if cfg.scheme not in ("tstream", "tstream_scan", "tstream_lockstep",
                              "mvlk"):
            raise ValueError(
                f"sharded run_stream implements the TStream/mvlk engines "
                f"only (got scheme={cfg.scheme!r})")
        self.app, self.cfg, self.mesh, self.layout = app, cfg, mesh, layout
        self.store = store
        self.exchange_slack = float(exchange_slack)
        self.axes = tuple(mesh.axis_names)
        self.n_dev = mesh.size

        self.assoc = (app.associative_only
                      and cfg.scheme in ("tstream", "tstream_scan")
                      and not (cfg.abort_repass and app.may_abort))
        if layout == "shared_per_socket":
            assert len(self.axes) == 2, \
                "shared_per_socket needs a (socket, core) mesh"
            self.n_sockets = mesh.shape[self.axes[0]]
            self.n_core = mesh.shape[self.axes[1]]
            n_owners, self.n_route = self.n_sockets, self.n_sockets
            self.route_axes = (self.axes[0],)
        else:
            n_owners = self.n_dev if layout == "shared_nothing" else 1
            self.n_route = self.n_dev
            self.route_axes = self.axes
        if not self.assoc:
            # lockstep sharding (mvlk included: eval_mvlk IS the lockstep
            # schedule) exchanges gate successes on global op ids; state
            # must be device-resident and the mesh flat
            assert layout == "shared_nothing" and len(self.axes) == 1, \
                ("non-associative/gated apps shard under shared_nothing "
                 "on a 1-D mesh")

        self._n_owners = n_owners
        self._bind_ownership(())
        # same output program as the single-device drivers (_post_stream):
        # identical function + identical [n_intervals, N, ...] shapes =>
        # identical compilation => bit-identical outputs
        from .scheduler import _post_stream
        self._post = jax.jit(named("tstream_outputs",
                                   partial(_post_stream, app=app)))
        self.last_stats: Optional[Dict] = None

    def _bind_ownership(self, overrides) -> None:
        """(Re)build the ownership permutation, routing tables and every
        jitted entry against ``overrides`` — the one place the sharded
        plan binds to a placement (construction, restore, migration)."""
        self.own = build_ownership(self.store, self._n_owners, overrides)
        self.probe = None
        if getattr(self.cfg, "use_hash_probe_route", False):
            fwd = np.asarray(self.own.fwd)[:-1]
            if self.layout == "shared_everything":
                owner = fwd % self.n_dev
            else:
                owner = fwd // self.own.per
            self.probe = build_probe_route(self.store.n_slots, owner,
                                           miss_owner=self.n_route)
        self._impl = jax.jit(_chunk_program(self), donate_argnums=0)
        self._to_blocks = jax.jit(named("tstream_carry_in",
                                        partial(_to_blocks_impl, eng=self)))
        # NO donation: snapshots read the carry mid-run and keep using it
        self._from_blocks = jax.jit(named(
            "tstream_carry_out", partial(_from_blocks_impl, eng=self)))

    @property
    def owners(self):
        """Current ownership overrides (sorted ``((uid, owner), ...)``)."""
        return self.own.overrides

    @property
    def reshardable(self) -> bool:
        """Live migration needs one state block per device (the moved-rows
        exchange is a device-level all_to_all) and >1 owner to move to."""
        return (self.layout == "shared_nothing" and self.n_dev > 1
                and self.probe is None)

    def set_ownership(self, overrides) -> None:
        """Rebind the pre-jitted plan to a new placement WITHOUT touching
        data — for restoring a snapshot taken on a migrated layout (the
        snapshot stores canonical-order values; ``carry_in`` lays them
        out under whatever ownership is bound here)."""
        overrides = tuple(sorted((int(u), int(o)) for u, o in overrides))
        if overrides != self.own.overrides:
            self._bind_ownership(overrides)

    def reshard(self, blocks, overrides):
        """Live migration: move the block carry onto a new placement.

        Ships ONLY moved rows via the owner-routed ``all_to_all`` (exact
        capacity from the host-side :func:`migration_plan` — migrations
        never drop rows), then rebinds the jitted plan to the new
        ownership.  Returns ``(blocks, moved_rows)``.  Must run at a
        punctuation boundary with the pipeline drained (the service's
        snapshot barrier).
        """
        assert self.reshardable, (self.layout, self.n_dev)
        overrides = tuple(sorted((int(u), int(o)) for u, o in overrides))
        if overrides == self.own.overrides:
            return blocks, 0
        new_own = build_ownership(self.store, self._n_owners, overrides)
        dst, nidx, cap = migration_plan(self.own, new_own)
        fn = jax.jit(named("tstream_migrate",
                           partial(_migrate_impl, eng=self, cap=cap)),
                     donate_argnums=0)
        blocks, moved = fn(blocks, jnp.asarray(dst), jnp.asarray(nidx))
        self._bind_ownership(overrides)
        return blocks, int(jax.device_get(moved))

    # -- block carry <-> canonical values ---------------------------------
    def carry_in(self, values):
        """[S+1, W] canonical values -> the resident block carry."""
        return self._to_blocks(values)

    def carry_out(self, blocks):
        """Block carry -> [S+1, W] canonical values (no donation)."""
        return self._from_blocks(blocks)

    # -- host driver ------------------------------------------------------
    def run_stream(self, values, event_stream, punct_interval: int):
        n = len(next(iter(event_stream.values())))
        interval = int(punct_interval)
        assert interval % self.n_dev == 0, \
            (f"punct_interval={interval} must divide evenly across "
             f"{self.n_dev} devices")
        n_intervals = n // interval
        if n_intervals == 0:
            # publish empty (not stale) exchange stats for this call
            self.last_stats = dict(
                dropped=np.zeros((0,), np.int32),
                shipped=np.zeros((0,), np.int32),
                max_fill=np.zeros((0,), np.int32),
                capacity=np.int32(0),
                exchanged_rows_per_device=np.int32(0))
            return [], values
        batched = {}
        for k, v in event_stream.items():
            v = np.asarray(v)[: n_intervals * interval]
            batched[k] = jnp.asarray(
                v.reshape((n_intervals, interval) + v.shape[1:]))
        blocks = self._to_blocks(jnp.asarray(values))
        res_all, ebs_all, blocks, stats = self._impl(
            blocks, batched, jnp.int32(0))
        values = self._from_blocks(blocks)
        stats = jax.device_get(stats)
        self.last_stats = stats
        total_dropped = int(np.sum(stats["dropped"]))
        if total_dropped:
            # overflow accounting goes through the process-wide telemetry
            # registry (DESIGN.md §2.11): counted always, logged as a
            # rate-unlimited structured event with the exact legacy
            # message.  Imported lazily so core never pulls the runtime
            # package at module-import time (layering).
            from repro.runtime.telemetry import get_default
            tele = get_default()
            tele.count("exchange.dropped", total_dropped,
                       driver="run_stream")
            tele.count("exchange.shipped", int(np.sum(stats["shipped"])),
                       driver="run_stream")
            tele.event(
                "exchange.overflow",
                "sharded exchange overflow: %d ops dropped across %d "
                "intervals (capacity=%d/bucket, slack=%.2f); results "
                "exclude dropped ops — raise exchange_slack",
                total_dropped, n_intervals, stats["capacity"],
                self.exchange_slack, logger=log, limit=-1)
        outs = jax.device_get(self._post(res_all, ebs_all))
        return ([jax.tree_util.tree_map(lambda x, i=i: x[i], outs)
                 for i in range(n_intervals)], values)

    def set_exchange_slack(self, slack: float) -> None:
        """Graceful degradation under repeated exchange overflow: widen
        the per-bucket capacity at a punctuation boundary.

        The capacity is a *python* value baked into the jitted program's
        trace, so changing the slack must rebind the jit wrapper — the
        next dispatch recompiles with the new capacity (the caller logs
        the escalation; results for shipped ops are unaffected, only the
        padding widens)."""
        self.exchange_slack = float(slack)
        self._impl = jax.jit(_chunk_program(self), donate_argnums=0)

    def run_chunk(self, blocks, batched, ts0: int):
        """Chunked service entry (see ``DualModeEngine.run_stream_chunk``).

        ``blocks`` is the resident block carry (``carry_in`` of the
        canonical values — the per-chunk permute/unpermute round-trip of
        the pre-elastic driver is gone) and is donated; ``batched``
        leaves are ``[K, interval, ...]``.  Returns unmaterialized device
        arrays plus the per-chunk exchange stats ``dict`` for the caller
        to aggregate — overflow is NOT logged here: the service logs each
        drop category once per run.
        """
        return self._impl(blocks, batched, jnp.int32(ts0))

    def chunk_route(self, interval: int):
        """``(rung, path)`` of the chunk program at the bound exchange
        slack (see ``DualModeEngine.chunk_route``): each device restructures
        the ``n_dev × cap`` rows it receives over its local block."""
        n_loc = (int(interval) // self.n_dev) * self.app.max_ops
        rows = self.n_dev * exchange_capacity(n_loc, self.n_route,
                                              self.exchange_slack)
        lpad = (self.own.s_pad if self.layout == "shared_everything"
                else self.own.per)
        if self.assoc and megakernel_engaged(
                rows, lpad + 1, method=self.cfg.restructure_method,
                has_max=any(self.store.table_is_max),
                funs_simple=simple_affine_luts(self.app.funs) is not None,
                use_pallas=self.cfg.use_pallas):
            return "mega", "segscan"
        rung = restructure_path(rows, lpad, rowmajor_ts=True,
                                method=self.cfg.restructure_method)
        return rung, ("segscan" if self.assoc else "lockstep")


# ---------------------------------------------------------------------------
# the jitted whole-stream program (block-carry form)
# ---------------------------------------------------------------------------
def _chunk_program(eng: ShardedStream):
    return named("tstream_chunk_sharded",
                 partial(_sharded_blocks_impl, eng=eng))


def _lane_width(eng: ShardedStream) -> int:
    """Pallas fast path: lane-pad state once per stream (operands pad
    after the exchange so wire bytes stay at W lanes)."""
    W = eng.app.width
    if eng.cfg.use_pallas and eng.assoc:
        from repro.kernels.segscan import kernel as K
        return max(W, K.LANES)
    return W


def _n_blocks(eng: ShardedStream) -> int:
    return eng.n_dev if eng.layout == "shared_nothing" else eng.n_sockets


def _to_blocks_impl(values, *, eng: ShardedStream):
    """[S+1, W] canonical values -> the resident block carry.

    The carry IS the per-device state layout — ``[n_blocks*(per+1), Wp]``
    (one ``[per+1, Wp]`` block per owner, pad chain last) for the
    partitioned layouts, the full ``[s_pad+1, Wp]`` permuted buffer for
    shared_everything — so chunks chain block-to-block with NO per-chunk
    permute/unpermute round-trip.
    """
    own, layout = eng.own, eng.layout
    per, s_pad, W = own.per, own.s_pad, eng.app.width
    Wp = _lane_width(eng)
    vperm = permute_values(own, values)                       # [s_pad+1, W]
    if Wp > W:
        vperm = jnp.pad(vperm, ((0, 0), (0, Wp - W)))
    if layout == "shared_everything":
        return vperm
    nb = _n_blocks(eng)
    return jnp.concatenate(
        [vperm[:-1].reshape(nb, per, Wp),
         jnp.zeros((nb, 1, Wp), vperm.dtype)],
        axis=1).reshape(nb * (per + 1), Wp)


def _from_blocks_impl(blocks, *, eng: ShardedStream):
    """Block carry -> [S+1, W] canonical values (exact gathers only)."""
    own, layout = eng.own, eng.layout
    per, s_pad, W = own.per, own.s_pad, eng.app.width
    Wp = _lane_width(eng)
    if layout == "shared_everything":
        vperm_out = blocks[:s_pad]
    else:
        vperm_out = blocks.reshape(_n_blocks(eng), per + 1, Wp)[:, :per]
        vperm_out = vperm_out.reshape(s_pad, Wp)
    vperm_out = vperm_out[:, :W]
    return unpermute_values(
        own, jnp.concatenate([vperm_out, jnp.zeros((1, W),
                                                   vperm_out.dtype)]))


def _sharded_blocks_impl(blocks, events_b, ts0, *, eng: ShardedStream):
    app, cfg, own, layout = eng.app, eng.cfg, eng.own, eng.layout
    mesh, axes = eng.mesh, eng.axes
    n_dev, n_route = eng.n_dev, eng.n_route
    some = jax.tree_util.tree_leaves(events_b)[0]
    n_intervals, interval = some.shape[0], some.shape[1]
    E_loc = interval // n_dev
    N_loc = E_loc * app.max_ops
    N_glob = interval * app.max_ops
    cap = exchange_capacity(N_loc, n_route, eng.exchange_slack)
    per, s_pad = own.per, own.s_pad
    W = app.width
    has_max = any(eng.store.table_is_max)
    lpad = s_pad if layout == "shared_everything" else per
    Wp = _lane_width(eng)

    # ---- per-slot max flags in carry layout (values-independent) --------
    sim = own.slot_is_max if has_max else jnp.zeros((s_pad + 1,), bool)
    if layout == "shared_everything":
        sim_b = sim
        state_spec = P()
    else:
        nb = _n_blocks(eng)
        sim_b = jnp.concatenate(
            [sim[:-1].reshape(nb, per),
             jnp.zeros((nb, 1), bool)], axis=1).reshape(-1)
        state_spec = P(axes) if layout == "shared_nothing" else P(axes[0])

    body = partial(_stream_body, eng=eng, dims=dict(
        n_intervals=n_intervals, interval=interval, E_loc=E_loc,
        N_loc=N_loc, N_glob=N_glob, cap=cap, lpad=lpad, Wp=Wp),
        has_max=has_max, ts0=ts0)
    # specs are pytree prefixes: one spec covers a whole output subtree;
    # every spec mentions every mesh axis (see the chunk-sharding note at
    # the end of _stream_body)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, state_spec, P(None, axes)),
        out_specs=(P(None, axes), P(None, axes), P(axes), P(axes), P(axes),
                   P(axes), P(axes)),
        check_vma=False)
    (res_all, ebs_all, blocks_out, dropped, shipped, fills,
     loads) = fn(blocks, sim_b, events_b)
    dropped = jnp.sum(dropped, axis=0)                    # [n_intervals]
    shipped = jnp.sum(shipped, axis=0)
    fills = jnp.max(fills, axis=0)                        # [n_intervals]

    # ---- carry out: reassemble the canonical block layout ---------------
    if layout == "shared_nothing":
        # the body's [per+1, Wp] outputs concatenate under P(axes) into
        # exactly the carry layout — chunks chain with zero data movement
        carry = blocks_out
    elif layout == "shared_per_socket":
        vperm_out = unchunk_output(blocks_out, eng.n_sockets, per)
        nb = eng.n_sockets
        carry = jnp.concatenate(
            [vperm_out, jnp.zeros((nb, 1, Wp), vperm_out.dtype)],
            axis=1).reshape(nb * (per + 1), Wp)
    else:  # shared_everything: chunks concatenate back to the full buffer
        vperm_out = unchunk_output(blocks_out, 1, s_pad).reshape(s_pad, Wp)
        carry = jnp.concatenate(
            [vperm_out, jnp.zeros((1, Wp), vperm_out.dtype)])

    # ---- per-shard / per-slot access histogram (skew observability) -----
    # loads: [n_dev, lpad+1] valid routed ops served per local slot
    if layout == "shared_nothing":
        l2 = loads.reshape(n_dev, per + 1)[:, :per]
        shard_load = jnp.sum(l2, axis=1)                      # [n_dev]
        slot_perm = l2.reshape(s_pad)
    elif layout == "shared_per_socket":
        l3 = jnp.sum(loads.reshape(eng.n_sockets, eng.n_core, per + 1),
                     axis=1)[:, :per]
        shard_load = jnp.sum(l3, axis=1)                      # [n_sockets]
        slot_perm = l3.reshape(s_pad)
    else:  # shared_everything: owner(slot) = slot % n_dev
        slot_perm = jnp.sum(loads.reshape(n_dev, s_pad + 1), axis=0)[:s_pad]
        shard_load = jax.ops.segment_sum(
            slot_perm, jnp.arange(s_pad) % n_dev, num_segments=n_dev)
    slot_load = jnp.take(slot_perm, own.fwd[:-1])             # original uids

    stats = dict(dropped=dropped, shipped=shipped, max_fill=fills,
                 capacity=jnp.int32(cap),
                 exchanged_rows_per_device=jnp.int32(n_dev * cap),
                 shard_load=shard_load, slot_load=slot_load)
    return res_all, ebs_all, carry, stats


# ---------------------------------------------------------------------------
# live migration: moved rows only, via the owner-routed all_to_all
# ---------------------------------------------------------------------------
def _migrate_impl(blocks, dstv, nidxv, *, eng: ShardedStream, cap: int):
    """Move the block carry onto a new ownership (shared_nothing only).

    ``dstv``/``nidxv`` come from :func:`ownership.migration_plan`: per
    (device, block row) the new owner and the row's index in the new
    owner's block.  ``cap`` is the exact max moved-rows count between any
    device pair, so the exchange never drops (zero loss by construction).
    """
    axes, n_dev, per = eng.axes, eng.n_dev, eng.own.per
    body = partial(_migrate_body, axes=axes, n_dev=n_dev, per=per, cap=cap)
    fn = jax.shard_map(body, mesh=eng.mesh,
                       in_specs=(P(axes), P(axes), P(axes)),
                       out_specs=(P(axes), P(axes)), check_vma=False)
    blocks, moved = fn(blocks, dstv, nidxv)
    return blocks, jnp.sum(moved)


def _migrate_body(block, dstv, nidxv, *, axes, n_dev, per, cap):
    """Per-device migration: local stay-scatter + moved-rows exchange."""
    dev = jax.lax.axis_index(axes[0])
    dstv = dstv.reshape(per)
    nidxv = nidxv.reshape(per)
    rows = block[:per]
    stay = dstv == dev
    out = jnp.zeros_like(block)
    # rows that stay scatter straight to their new index (dead padding
    # rows carry nidx == per and land on the pad chain, zeroed below)
    out = out.at[jnp.where(stay, nidxv, per)].set(
        jnp.where(stay[:, None], rows, 0.0))
    # moved rows bucket by new owner and ship with ONE all_to_all; cells
    # beyond a pair's move count are ok=False -> value 0.0 at index per
    dst = jnp.where(stay, n_dev, dstv).astype(jnp.int32)
    plan = bucket_by_owner(dst, n_dev, cap)
    srows = route_gather(plan, rows, 0.0)                 # [n_dev, cap, Wp]
    sidx = route_gather(plan, nidxv, per)                 # [n_dev, cap]
    rrows = jax.lax.all_to_all(srows, axes, split_axis=0, concat_axis=0)
    ridx = jax.lax.all_to_all(sidx, axes, split_axis=0, concat_axis=0)
    out = out.at[ridx.reshape(-1)].set(rrows.reshape(-1, rrows.shape[-1]))
    out = out.at[per].set(0.0)
    moved = jnp.sum(plan.ok.astype(jnp.int32))
    return out, moved[None]


def _stream_body(blocks, sim_b, events_loc, *, eng: ShardedStream, dims,
                 has_max, ts0):
    """shard_map body: the per-device program for the whole stream."""
    app, cfg, own, layout = eng.app, eng.cfg, eng.own, eng.layout
    axes, mesh = eng.axes, eng.mesh
    n_dev, n_route = eng.n_dev, eng.n_route
    n_intervals, interval = dims["n_intervals"], dims["interval"]
    E_loc, N_loc, N_glob = dims["E_loc"], dims["N_loc"], dims["N_glob"]
    cap, lpad, Wp = dims["cap"], dims["lpad"], dims["Wp"]
    per, s_pad = own.per, own.s_pad
    W = app.width

    dev = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        dev = dev * mesh.shape[a] + jax.lax.axis_index(a)
    if layout == "shared_per_socket":
        sock = jax.lax.axis_index(axes[0])
        core = jax.lax.axis_index(axes[1])

    # ---- compute mode: event-parallel op registration (all intervals) ---
    with jax.named_scope("register"):
        ts_bases = (ts0 + jnp.arange(n_intervals, dtype=jnp.int32) * interval
                    + dev * E_loc)
        ops_all, ebs_all = jax.vmap(
            lambda ev, tb: build_opbatch(app, eng.store, ev, tb))(
                events_loc, ts_bases)
        base = dev * N_loc
        gflat = jnp.broadcast_to(base + jnp.arange(N_loc, dtype=jnp.int32),
                                 (n_intervals, N_loc))
        gate_all = jnp.where(ops_all.gate >= 0, ops_all.gate + base, -1)

    # ---- owner routing (values-independent, hoisted) --------------------
    with jax.named_scope("exchange"):
        uid_perm = jnp.take(own.fwd, ops_all.uid)            # [n_i, N_loc]
        if eng.probe is not None:
            dst_v = eng.probe.owners_of(
                ops_all.uid.reshape(-1)).reshape(n_intervals, N_loc)
        elif layout == "shared_everything":
            dst_v = uid_perm % n_dev
        else:
            dst_v = uid_perm // per
        dst = jnp.where(ops_all.valid, dst_v, n_route).astype(jnp.int32)
        plans = jax.vmap(lambda d: bucket_by_owner(d, n_route, cap))(dst)

        if layout == "shared_everything":
            uid_send = jnp.where(ops_all.valid, uid_perm, lpad)
        else:
            uid_send = jnp.where(
                ops_all.valid,
                uid_perm - jnp.minimum(dst_v, n_route - 1) * per, lpad)
        rg = jax.vmap(route_gather, in_axes=(0, 0, None))
        send = dict(
            uid=rg(plans, uid_send, lpad),
            fun=rg(plans, ops_all.fun, 0),
            operand=rg(plans, ops_all.operand, 0.0),
            valid=rg(plans, ops_all.valid, False),
            ts=rg(plans, ops_all.ts, 0),
            slot=rg(plans, ops_all.slot, 0),
        )
        if not eng.assoc:
            send["gate"] = rg(plans, gate_all, -1)
            send["gflat"] = rg(plans, gflat, N_glob)

        # ---- THE exchange: one all_to_all for the whole stream ----------
        recv = {k: jax.lax.all_to_all(v, eng.route_axes, split_axis=1,
                                      concat_axis=1)
                for k, v in send.items()}
        if layout == "shared_per_socket":
            # intra-socket: every core sees the socket's full routed set,
            # in flat source-device order (socket-major) so rows stay
            # ts-sorted
            recv = {k: jax.lax.all_gather(v, axes[1], axis=1)
                    for k, v in recv.items()}
            recv = {k: jnp.moveaxis(v, 1, 2) for k, v in recv.items()}
        R = n_dev * dims["cap"]
        recv = {k: v.reshape((n_intervals, R) + v.shape[4 if layout ==
                             "shared_per_socket" else 3:])
                for k, v in recv.items()}

        rvalid = recv["valid"]
        ruid = recv["uid"]
        if layout == "shared_per_socket":
            rvalid = rvalid & ((ruid % eng.n_core) == core)
        operand = recv["operand"]
        if Wp > W:
            operand = jnp.pad(operand, ((0, 0), (0, 0), (0, Wp - W)))
        zeros_i = jnp.zeros((n_intervals, R), jnp.int32)
        rops = OpBatch(
            uid=ruid, ts=recv["ts"], txn=zeros_i, slot=recv["slot"],
            kind=zeros_i, fun=recv["fun"],
            gate=recv.get("gate",
                          jnp.full((n_intervals, R), -1, jnp.int32)),
            operand=operand, valid=rvalid)

    # ---- local state / store ------------------------------------------
    if layout == "shared_everything":
        vals0 = blocks                                  # [s_pad+1, Wp]
        sim_loc = sim_b
    else:
        vals0 = blocks.reshape(per + 1, Wp)
        sim_loc = sim_b.reshape(per + 1)
    lstore = make_local_store(vals0, sim_loc if has_max else None)

    # ---- evaluate -------------------------------------------------------
    if eng.assoc:
        merge_axes = None
        own_mask = None
        if layout == "shared_per_socket":
            merge_axes = (axes[1],)
            own_mask = jnp.concatenate(
                [(jnp.arange(per) % eng.n_core) == core,
                 jnp.zeros((1,), bool)])
        elif layout == "shared_everything":
            merge_axes = axes
            own_mask = jnp.concatenate(
                [(jnp.arange(s_pad) % n_dev) == dev,
                 jnp.zeros((1,), bool)])
        mega_luts = simple_affine_luts(app.funs)
        if megakernel_engaged(R, lpad + 1, method=cfg.restructure_method,
                              has_max=has_max,
                              funs_simple=mega_luts is not None,
                              use_pallas=cfg.use_pallas):
            # megakernel rung: a light geometry-free partition plan, then
            # ONE fused dispatch per interval replaces the staged
            # plan → coefs → execute pipeline (bit-identical — see
            # kernels/megakernel).  The ownership merge is unchanged.
            from repro.kernels.megakernel import fused_chain_eval
            a_lut, b_lut = mega_luts
            with jax.named_scope("restructure"):
                sops_all, ch_all = restructure_stream(
                    rops, lpad, rowmajor_ts=True, light=True,
                    method="partition", use_pallas=cfg.use_pallas,
                    geometry=False,
                    block_rows=cfg.block_param("radix_partition"))

            def sbody(vals, xs):
                sops, ch = xs
                with jax.named_scope("evaluate"):
                    res, new_vals, _ = fused_chain_eval(
                        vals, sops, ch, lpad, a_lut=a_lut, b_lut=b_lut,
                        use_pallas=cfg.use_pallas)
                if own_mask is not None:
                    with jax.named_scope("commit"):
                        new_vals = jax.lax.pmax(
                            jnp.where(own_mask[:, None], new_vals,
                                      -jnp.inf), merge_axes)
                        new_vals = new_vals.at[lpad].set(0.0)
                return new_vals, res

            vals_fin, res_sorted = jax.lax.scan(sbody, vals0,
                                                (sops_all, ch_all))
            with jax.named_scope("evaluate"):
                res_routed = {k: jax.vmap(Chains.untake)(ch_all, v)
                              for k, v in res_sorted.items()}
        else:
            with jax.named_scope("restructure"):
                pres_all = restructure_stream(
                    rops, lpad, rowmajor_ts=True, light=True,
                    method=cfg.restructure_method,
                    use_pallas=cfg.use_pallas,
                    block_rows=cfg.block_param("radix_partition"))
            with jax.named_scope("evaluate"):
                plan_all = jax.vmap(
                    lambda o, p: tstream_scan_plan(
                        lstore, o, app.funs, prestructured=p))(rops,
                                                               pres_all)
                plan_all = tstream_scan_coefs_stream(
                    plan_all, use_pallas=cfg.use_pallas,
                    block_rows=cfg.block_param("segscan"))

            def sbody(vals, plan):
                # tstream_scan_execute names its evaluate and commit parts
                res, new_vals, _ = tstream_scan_execute(vals, plan, lpad,
                                                        raw=True)
                if own_mask is not None:
                    # ownership-masked SELECT (one writer per slot) —
                    # exact, unlike delta summation
                    with jax.named_scope("commit"):
                        new_vals = jax.lax.pmax(
                            jnp.where(own_mask[:, None], new_vals,
                                      -jnp.inf), merge_axes)
                        new_vals = new_vals.at[lpad].set(0.0)
                return new_vals, res

            vals_fin, res_sorted = jax.lax.scan(sbody, vals0, plan_all)
            with jax.named_scope("evaluate"):
                res_routed = {k: jax.vmap(Chains.untake)(plan_all.ch, v)
                              for k, v in res_sorted.items()}
    else:
        with jax.named_scope("restructure"):
            pres_all = restructure_stream(
                rops, lpad, rowmajor_ts=True,
                method=cfg.restructure_method, use_pallas=cfg.use_pallas,
                block_rows=cfg.block_param("radix_partition"))
        lk = partial(
            _lockstep_interval, eng=eng, R=R, N_glob=N_glob,
            pad_uid=lpad, Wq=Wp, axis=axes[0], per=per, s_pad=s_pad,
            max_ops=app.max_ops)

        def sbody(vals, xs):
            (sops, ch), gfr = xs
            with jax.named_scope("evaluate"):
                vals2, res = lk(vals, sops, ch, gfr, dev=dev)
            return vals2, res

        vals_fin, res_routed = jax.lax.scan(
            sbody, vals0, (pres_all, recv["gflat"]))

    # ---- reverse exchange: results home to their source device ----------
    with jax.named_scope("exchange"):
        if layout == "shared_per_socket":
            # socket-complete results (each op evaluated on exactly one core)
            pp = {k: (jax.lax.psum(v.astype(jnp.int32), axes[1]) > 0
                      if v.dtype == jnp.bool_ else jax.lax.psum(v, axes[1]))
                  for k, v in res_routed.items()}
            back = {k: v.reshape((n_intervals, eng.n_sockets, eng.n_core, cap)
                                 + v.shape[2:])
                    for k, v in pp.items()}
            back = {k: jax.lax.dynamic_index_in_dim(v, core, axis=2,
                                                    keepdims=False)
                    for k, v in back.items()}
        else:
            back = {k: v.reshape((n_intervals, n_dev, cap) + v.shape[2:])
                    for k, v in res_routed.items()}
        back = {k: jax.lax.all_to_all(v, eng.route_axes, split_axis=1,
                                      concat_axis=1)
                for k, v in back.items()}
        back = {k: v.reshape((n_intervals, n_route * cap) + v.shape[3:])
                for k, v in back.items()}
        res_loc = {
            k: jax.vmap(lambda p, v: unroute_gather(p, v, n_route, cap))(
                plans, v)
            for k, v in back.items()}

    # per-device exchange stats; reduced outside the shard_map ([1, n_i]
    # rows concatenate to [n_dev, n_i] under the fully-specified spec)
    dropped = plans.dropped[None]
    shipped = jnp.sum(plans.ok.astype(jnp.int32), axis=(1, 2))[None]
    fills = plans.fill[None]
    # per-local-slot access histogram over the whole chunk — the skew
    # signal the controller's reshard knob feeds on ([1, lpad+1] rows
    # concatenate to [n_dev, lpad+1]); each valid routed op is counted on
    # exactly one device (per_socket: the core-residue filter above)
    loads = jax.ops.segment_sum(
        rvalid.astype(jnp.int32).reshape(-1),
        jnp.minimum(ruid, lpad).reshape(-1),
        num_segments=lpad + 1)[None]

    # Every out_spec must mention every mesh axis: an under-specified
    # output (value replicated across an unmentioned axis) is treated as
    # an unreduced partial by the surrounding SPMD program and gets
    # *summed* when resharded (observed: per-socket state scaled by
    # n_core).  State replicated across axes is therefore chunk-sharded
    # (ownership.chunk_shard_output) and reassembled by the caller.
    if layout == "shared_per_socket":
        vals_fin = chunk_shard_output(vals_fin, core, eng.n_core)
    elif layout == "shared_everything":
        vals_fin = chunk_shard_output(vals_fin, dev, n_dev)
    # res/ebs leave the shard_map event-sharded; post-processing runs in
    # the enclosing jit so its reductions compile in the same (fusion)
    # context as the single-device driver and stay bit-identical to it
    return res_loc, ebs_all, vals_fin, dropped, shipped, fills, loads


# ---------------------------------------------------------------------------
# sharded lockstep (non-associative / gated apps; shared_nothing, 1-D mesh)
# ---------------------------------------------------------------------------
def _lockstep_interval(vals, sops, ch, gflat_r, *, eng: ShardedStream, R,
                       N_glob, pad_uid, Wq, axis, per, s_pad, max_ops, dev):
    """One interval of the sharded lockstep schedule (+ abort repass)."""
    app, cfg = eng.app, eng.cfg
    gflat_s = jnp.take(gflat_r, ch.order)
    ev = partial(_lockstep_eval, eng=eng, R=R, N_glob=N_glob,
                 pad_uid=pad_uid, Wq=Wq, axis=axis, per=per, s_pad=s_pad,
                 gflat_r=gflat_r, gflat_s=gflat_s, dev=dev)
    vals1, res1, succ1 = ev(vals, sops, ch)
    if not (cfg.abort_repass and app.may_abort):
        return vals1, {k: v[:R] for k, v in res1.items()}

    # abort repass: mask whole transactions whose ops failed, re-evaluate
    # from the pre-interval values.  Txn verdicts need the *global* valid
    # mask and success frontier.
    valid_r = ch.untake(sops.valid)
    gvalid = _bool_pmax(
        jnp.zeros((N_glob + 1,), bool).at[gflat_r].set(valid_r), axis)
    succ2d = succ1[:N_glob].reshape(-1, max_ops)
    valid2d = gvalid[:N_glob].reshape(-1, max_ops)
    txn_ok = jnp.all(succ2d | ~valid2d, axis=1)           # [interval]
    keep_s = jnp.take(txn_ok, jnp.minimum(gflat_s // max_ops,
                                          txn_ok.shape[0] - 1))
    keep_s = keep_s & (gflat_s < N_glob)
    sops2 = dataclasses.replace(sops, valid=sops.valid & keep_s)
    vals2, res2, _ = ev(vals, sops2, ch)
    return vals2, {k: v[:R] for k, v in res2.items()}


def _lockstep_eval(vals, sops, ch, *, eng: ShardedStream, R, N_glob,
                   pad_uid, Wq, axis, per, s_pad, gflat_r, gflat_s, dev):
    """Level-wise lockstep chain walk with a cross-device success frontier.

    Mirrors ``engines.eval_tstream_lockstep`` exactly, except success
    lookups for cross-chain gates resolve through a global [N+1] success
    array (merged with a bool pmax after each level — a gated op's mate
    chain always sits at a strictly lower level), and dependency-cycle
    residue runs as a *replicated* sequential sweep over the all-gathered
    residue ops.
    """
    app, cfg = eng.app, eng.cfg
    funs = app.funs
    res = dict(pre=jnp.zeros((R + 1, Wq)), post=jnp.zeros((R + 1, Wq)),
               success=jnp.zeros((R + 1,), bool))
    succ_glob = jnp.zeros((N_glob + 1,), bool)
    g2l = jnp.full((N_glob + 1,), R, jnp.int32).at[gflat_r].set(
        jnp.arange(R, dtype=jnp.int32))

    if not app.has_gates:
        vals, res = _sweep_sharded(vals, sops, ch, funs,
                                   jnp.ones((R,), bool), res, R, pad_uid,
                                   ch.max_len, succ_glob, g2l)
        # res is recorded at routed-flat sinks (ch.order), so it scatters
        # to global op indices directly — gflat_r is routed-flat too
        succ_glob = _bool_pmax(
            jnp.zeros((N_glob + 1,), bool).at[gflat_r].set(
                res["success"][:R]), axis)
        return vals, res, succ_glob

    lvl, unresolved = _chain_levels_sharded(
        sops, ch, gflat_s, R, N_glob, cfg.max_dep_levels, axis)
    for L in range(cfg.max_dep_levels + 1):
        mask = lvl == L
        in_level = jnp.take(mask, ch.seg_id) & sops.valid
        lvl_rounds = jnp.max(jnp.where(in_level, ch.pos, -1)) + 1
        vals, res = _sweep_sharded(vals, sops, ch, funs, mask, res, R,
                                   pad_uid, lvl_rounds, succ_glob, g2l)
        # res sinks are routed-flat (ch.order): aligned with gflat_r as-is
        succ_glob = _bool_pmax(
            jnp.zeros((N_glob + 1,), bool).at[gflat_r].set(
                res["success"][:R]), axis)
    vals, res, succ_glob = _residue_sharded(
        vals, sops, ch, unresolved, res, succ_glob, eng=eng, R=R,
        N_glob=N_glob, per=per, s_pad=s_pad, axis=axis,
        gflat_r=gflat_r, gflat_s=gflat_s, Wq=Wq, dev=dev)
    return vals, res, succ_glob


def _sweep_sharded(values, sops, ch, funs, chain_mask, res, n, pad_uid,
                   rounds, succ_glob, g2l):
    """A lockstep sweep that scatters into the table and the results each
    round (``engines._lockstep_sweep`` walks in sorted layout instead),
    with gate successes resolved locally when the mate op lives on this
    device (same-chain gates) and through the merged global frontier
    otherwise."""
    def round_body(r, carry):
        values, res = carry
        active = (ch.pos == r) & jnp.take(chain_mask, ch.seg_id) & sops.valid
        cur = jnp.take(values, sops.uid, axis=0)
        mate = jnp.maximum(sops.gate, 0)
        mate_loc = jnp.take(g2l, mate)
        # mate_loc == n marks a remote mate; row n of the success array is
        # the inactive-op dump slot and must never be read as a success
        ok_loc = (mate_loc < n) & jnp.take(res["success"], mate_loc)
        ok_glob = jnp.take(succ_glob, mate)
        gate_ok_s = jnp.where(sops.gate >= 0, ok_loc | ok_glob, True)
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

    return jax.lax.fori_loop(0, rounds, round_body, (values, res))


def _chain_levels_sharded(sops, ch, gflat_s, R, N_glob, max_levels, axis):
    """Distributed `engines._chain_levels`: the per-chain level fixpoint
    iterates against a replicated per-op level array keyed by global op
    index (merged with pmin; levels only decrease)."""
    gated = (sops.gate >= 0) & sops.valid
    chain_has_gate = jax.ops.segment_max(
        gated.astype(jnp.int32), ch.seg_id, num_segments=R) > 0
    lvl = jnp.where(chain_has_gate, _INF, 0)

    def op_lvl_of(lvl):
        per_op = jnp.take(lvl, ch.seg_id)
        arr = jnp.full((N_glob + 1,), _INF, jnp.int32).at[gflat_s].set(
            per_op)
        return jax.lax.pmin(arr, axis)

    opl = op_lvl_of(lvl)
    for _ in range(max_levels):
        pred = jnp.take(opl, jnp.maximum(sops.gate, 0))
        need = jax.ops.segment_max(
            jnp.where(gated, jnp.minimum(pred + 1, _INF), 0),
            ch.seg_id, num_segments=R)
        lvl = jnp.where(chain_has_gate, jnp.minimum(need, _INF), 0)
        opl = op_lvl_of(lvl)
    return lvl, lvl >= _INF


def _residue_sharded(vals, sops, ch, unresolved, res, succ_glob, *,
                     eng: ShardedStream, R, N_glob, per, s_pad, axis,
                     gflat_r, gflat_s, Wq, dev):
    """Dependency-cycle residue: the affected ops run *sequentially in
    global timestamp order*, replicated on every device (each device
    gathers the residue ops and the full value array, computes the same
    sweep bit-for-bit, then takes its own shard back)."""
    funs = eng.app.funs
    un_ops = jnp.take(unresolved, ch.seg_id) & sops.valid       # sorted [R]

    allv = jax.lax.all_gather(vals[:per], axis, axis=0)         # [n_dev,per,W]
    vals_full = jnp.concatenate(
        [allv.reshape(s_pad, Wq), jnp.zeros((1, Wq), vals.dtype)])

    uid_g = jnp.where(un_ops, sops.uid + dev * per, s_pad)
    gather = lambda x: jax.lax.all_gather(x, axis, axis=0).reshape(
        (-1,) + x.shape[1:])
    g = dict(uid=gather(uid_g), ts=gather(sops.ts), slot=gather(sops.slot),
             fun=gather(sops.fun), gate=gather(sops.gate),
             operand=gather(sops.operand), run=gather(un_ops),
             gflat=gather(jnp.where(un_ops, gflat_s, N_glob)))
    order = jnp.lexsort((g["slot"], g["ts"]))
    gres = dict(pre=jnp.zeros((N_glob + 1, Wq)),
                post=jnp.zeros((N_glob + 1, Wq)),
                success=succ_glob)

    def step(j, carry):
        values, gres = carry
        uid = g["uid"][j]
        cur = values[uid]
        gate = g["gate"][j]
        gate_ok = jnp.where(gate >= 0,
                            gres["success"][jnp.maximum(gate, 0)], True)
        post, ok = funs_apply_single(funs, g["fun"][j], cur, g["operand"][j])
        post = jnp.where(gate_ok, post, cur)
        ok = ok & gate_ok
        values = values.at[uid].set(post)
        values = values.at[s_pad].set(0.0)
        sink = g["gflat"][j]
        gres = dict(
            pre=gres["pre"].at[sink].set(cur),
            post=gres["post"].at[sink].set(post),
            success=gres["success"].at[sink].set(ok),
        )
        return values, gres

    (vals_full, gres), _ = sweep_selected(step, (vals_full, gres), order,
                                          g["run"])

    vals_new = jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(vals_full, dev * per, per),
         jnp.zeros((1, Wq), vals.dtype)])
    # merge residue results into the local routed-layout results
    un_flat = ch.untake(un_ops)                                  # [R]
    sel = lambda loc, glob: jnp.where(
        (un_flat[:, None] if loc.ndim == 2 else un_flat),
        jnp.take(glob, gflat_r, axis=0), loc[:R])
    res = dict(
        pre=jnp.concatenate([sel(res["pre"], gres["pre"]), res["pre"][R:]]),
        post=jnp.concatenate([sel(res["post"], gres["post"]),
                              res["post"][R:]]),
        success=jnp.concatenate([sel(res["success"], gres["success"]),
                                 res["success"][R:]]),
    )
    return vals_new, res, gres["success"]
