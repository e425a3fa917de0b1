"""Per-batch chain-shard layouts over a device mesh — the paper's NUMA-aware
processing configurations (§IV-E) mapped to SPMD (DESIGN.md §2.5):

  shared-nothing     state slots owned by one device (contiguous after the
                     ownership permutation); chains evaluate where their
                     state lives; **zero collectives**
  shared-per-socket  state owned per 'socket' mesh axis, work split across
                     the socket's 'core' axis -> intra-socket psum only
  shared-everything  state replicated; work split across all devices ->
                     global psum of state deltas (cross-socket traffic)

All three evaluate the same restructured batch with identical results;
compiled collective bytes per layout quantify the paper's Fig. 14 finding
(shared-nothing wins; cross-socket communication hurts).

This is the **replicate-everything baseline**: every device receives the
full OpBatch (``in_specs=P()``) and masks out non-local ops, paying
O(n_dev · N) replicated bytes, a fresh restructure sort and an ownership
re-permutation *per call*.  The owner-routed fused driver
(``core/sharded_stream``) replaces all three costs for streams; this path
remains the per-batch reference the benchmarks compare against.

Ownership permutation and local-store construction are shared with the
fused driver via ``core/ownership`` — local stores carry per-slot max
flags, so heterogeneous table families (e.g. TP's max sketches) work
under every layout.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .engines import eval_tstream_scan
from .ownership import (LAYOUTS, build_ownership, chunk_shard_output,
                        make_local_store, permute_values, unchunk_output,
                        unpermute_values)
from .restructure import restructure
from .types import FunSpec, OpBatch, StateStore

__all__ = ["LAYOUTS", "evaluate_sharded"]


def _remap_ops(ops: OpBatch, fwd: jnp.ndarray, pad_new: int) -> OpBatch:
    uid = jnp.where(ops.valid, jnp.take(fwd, ops.uid), pad_new)
    return dataclasses.replace(ops, uid=uid)


def _eval_local(vals, lops, slot_is_max, funs):
    """Restructure the remapped local batch exactly once and evaluate on a
    local store built by the shared constructor."""
    lstore = make_local_store(vals, slot_is_max)
    _, new_vals, _ = eval_tstream_scan(
        lstore, lops, funs,
        prestructured=restructure(lops, lstore.pad_uid, rowmajor_ts=True))
    return new_vals


def evaluate_sharded(store: StateStore, ops: OpBatch,
                     funs: Tuple[FunSpec, ...], mesh, layout: str):
    """TStream fast-path under a chain-shard layout (per-batch baseline).

    Returns values in the *original* slot order (un-permuted) for
    comparison; the layout governs where evaluation runs and which
    collectives reconcile state.  ``ops`` must come from ``build_opbatch``
    — row order is (ts, slot).
    """
    assert layout in LAYOUTS, layout
    n_dev = mesh.size
    axes = mesh.axis_names
    n_sockets = mesh.shape.get("socket", 1)
    n_owners = {"shared_nothing": n_dev,
                "shared_per_socket": n_sockets,
                "shared_everything": 1}[layout]

    own = build_ownership(store, n_owners)
    per, s_pad = own.per, own.s_pad
    has_max = own.slot_is_max is not None
    values = permute_values(own, store.values)              # [s_pad+1, W]
    sim = (own.slot_is_max if has_max
           else jnp.zeros((s_pad + 1,), bool))
    rops = _remap_ops(ops, own.fwd, s_pad)
    width = values.shape[1]

    def my_dev():
        idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def blocked(x, n_blocks, fill):
        """[s_pad(+1), ...] -> [n_blocks*(per+1), ...] with per-block pad."""
        core = x[:s_pad].reshape((n_blocks, per) + x.shape[1:])
        pad = jnp.full((n_blocks, 1) + x.shape[1:], fill, x.dtype)
        return jnp.concatenate([core, pad], axis=1).reshape(
            (n_blocks * (per + 1),) + x.shape[1:])

    def unblocked(x, n_blocks):
        return x.reshape((n_blocks, per + 1) + x.shape[1:])[:, :per].reshape(
            (n_blocks * per,) + x.shape[1:])

    if layout == "shared_nothing":
        # local state block [per+1, W]; ops with non-local uid -> local pad
        def body(vals_local, sim_local, ops_rep):
            base = my_dev() * per
            local_uid = ops_rep.uid - base
            is_local = (local_uid >= 0) & (local_uid < per) & ops_rep.valid
            lops = dataclasses.replace(
                ops_rep, uid=jnp.where(is_local, local_uid, per),
                valid=is_local)
            return _eval_local(vals_local, lops,
                               sim_local if has_max else None, funs)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(axes), P(axes), P()),
                           out_specs=P(axes), check_vma=False)
        out_blocks = fn(blocked(values, n_dev, 0.0),
                        blocked(sim, n_dev, False), rops)
        out = unblocked(out_blocks, n_dev)
        # original slot order, pad row dropped (the historical contract)
        return unpermute_values(
            own, jnp.concatenate([out, jnp.zeros((1, width))]))[:-1]

    if layout == "shared_per_socket":
        core_axis = axes[-1]

        def body(vals, sim_local, ops_rep):
            sock = jax.lax.axis_index(axes[0])
            core = jax.lax.axis_index(core_axis)
            n_core = mesh.shape[core_axis]
            base = sock * per
            local_uid = ops_rep.uid - base
            mine = (local_uid >= 0) & (local_uid < per) & ops_rep.valid \
                & ((ops_rep.uid % n_core) == core)   # split chains in socket
            lops = dataclasses.replace(
                ops_rep, uid=jnp.where(mine, local_uid, per), valid=mine)
            new_vals = _eval_local(vals, lops,
                                   sim_local if has_max else None, funs)
            delta = new_vals - vals
            merged = vals + jax.lax.psum(delta, core_axis)  # intra-socket
            # output must mention EVERY mesh axis (chunk the replicated
            # socket block across cores) — see ownership.chunk_shard_output
            return chunk_shard_output(merged, core, n_core)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(axes[0]), P(axes[0]), P()),
                           out_specs=P(axes), check_vma=False)
        out_chunks = fn(blocked(values, n_sockets, 0.0),
                        blocked(sim, n_sockets, False), rops)
        out = unchunk_output(out_chunks, n_sockets, per).reshape(s_pad, width)
        return unpermute_values(
            own, jnp.concatenate([out, jnp.zeros((1, width))]))[:-1]

    # shared_everything: replicated state, global psum merge
    def body(vals, ops_rep):
        dev = my_dev()
        mine = ((ops_rep.uid % n_dev) == dev) & ops_rep.valid
        lops = dataclasses.replace(
            ops_rep, uid=jnp.where(mine, ops_rep.uid, s_pad), valid=mine)
        new_vals = _eval_local(vals, lops, sim if has_max else None, funs)
        delta = new_vals - vals
        merged = vals + jax.lax.psum(delta, axes)       # global merge
        return chunk_shard_output(merged, dev, n_dev)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=P(axes), check_vma=False)
    out = fn(values, rops)
    out = unchunk_output(out, 1, s_pad + 1).reshape(s_pad + 1, width)
    return unpermute_values(own, out)[:-1]
