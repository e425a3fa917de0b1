"""Dynamic restructuring (paper §IV-C1): transactions -> operation chains.

The paper decomposes each postponed transaction into per-state operations
and inserts them into timestamp-sorted per-state lists (operation chains)
via a concurrent skip list.  The accelerator-native equivalent is a stable
grouping by (state uid, ts, slot): after grouping, each chain is a
contiguous, timestamp-ordered segment.

Because the major key is a **bounded integer** (uid < n_slots), the
grouping does not need a comparison sort: the default backbone is a
one-pass **radix/counting partition** (``kernels/radix_partition``) —
histogram + exclusive prefix + stable within-bucket rank, O(N + K) — that
yields the chain order, its inverse (by direct offset arithmetic instead
of binary search), the segment flags and the per-state commit gather map
from the *same* per-bucket histograms.  The fallback ladder when the
partition's bucket bounds don't hold is the packed single-operand sort
(uint32, then uint64 under x64), then the generic 3-key lexsort
(DESIGN.md §2.1).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from .types import OpBatch

log = logging.getLogger(__name__)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Chains:
    """Operation chains over a sorted view of an OpBatch.

    ``order``     : sorted index -> original flat op index (gather map)
    ``inv``       : original flat op index -> sorted index (inverse of
                    ``order``; lets results return to (txn, slot) layout by
                    *gather* instead of the much slower CPU/TPU scatter)
    ``seg_start`` : bool[N], True at the first op of each chain
    ``seg_id``    : chain id of each sorted op (== cumsum(seg_start)-1)
    ``pos``       : position of the op inside its chain (ts order)
    ``seg_end``   : True at the last op of each chain
    ``n_chains``  : traced scalar, number of distinct chains
    ``max_len``   : traced scalar, longest chain (lockstep round count)
    ``counts``    : i32[n_buckets] per-uid histogram — populated by the
                    partition path (None on the sort paths); feeds the
                    commit gather map and exchange capacities for free
    ``starts``    : i32[n_buckets] exclusive prefix of ``counts``
    """

    order: jnp.ndarray
    inv: jnp.ndarray
    seg_start: jnp.ndarray
    seg_id: jnp.ndarray
    pos: jnp.ndarray
    seg_end: jnp.ndarray
    n_chains: jnp.ndarray
    max_len: jnp.ndarray
    counts: Optional[jnp.ndarray] = None
    starts: Optional[jnp.ndarray] = None

    def take(self, x: jnp.ndarray) -> jnp.ndarray:
        """Gather a flat (pre-sort) per-op array into sorted chain order."""
        return jnp.take(x, self.order, axis=0)

    def untake(self, x_sorted: jnp.ndarray) -> jnp.ndarray:
        """Gather a sorted per-op array back into flat (pre-sort) layout."""
        return jnp.take(x_sorted, self.inv, axis=0)


# ---------------------------------------------------------------------------
# Path selection: partition -> packed sort (u32/u64) -> lexsort
# ---------------------------------------------------------------------------
RESTRUCTURE_METHODS = ("auto", "partition", "packed", "lexsort",
                       "megakernel")

# Counting-partition auto bounds — the measured crossover for the CURRENT
# device kind, resolved from ``kernels/autotune.LADDER_BOUNDS``.  On this
# repo's CPU hosts the row is the measured BENCH_restructure.json
# crossover (1.3-1.8x for the owner-routing shape at >=655k rows;
# wall-clock parity within host noise (0.9-1.1x) for a 9-bucket store at
# 512k, trending with N — engaged there because the commit map comes free
# and the structural cost is O(N + K) vs O(N log N); loses for large
# sparse stores), so "auto" only engages the partition inside that
# regime.  On accelerators the jnp.sort baseline is a bitonic network,
# which moves the crossover far right — the autotune table carries
# per-device rows instead of this one CPU measurement.  Forcing
# ``method="partition"`` bypasses the bound (parity tests, deployments).
PARTITION_MAX_BUCKETS, PARTITION_MIN_ROWS = autotune.ladder_bounds("cpu")


def partition_fits(n_rows: int, n_buckets: int) -> bool:
    """Whether "auto" picks the one-pass counting partition backbone
    (device-derived bounds; see ``kernels/autotune.LADDER_BOUNDS``)."""
    max_buckets, min_rows = autotune.ladder_bounds()
    return n_buckets <= max_buckets and int(n_rows) >= min_rows


def megakernel_engaged(n_rows: int, n_slots_incl_pad: int, *,
                       method: str, has_max: bool,
                       funs_simple: bool, use_pallas: bool = False) -> bool:
    """Whether the fused drivers evaluate chains through the fused
    partition→segscan→commit megakernel (``kernels/megakernel``).

    Structural eligibility first — the fused pipeline only expresses
    simple-affine tables (``FunSpec.affine_simple``; its one-hot
    gather/scatter is exact only for finite values, which ±inf max
    neutrals break) — then either an explicit ``method="megakernel"``
    force or, under "auto", the measured per-device win band
    (``kernels/autotune.MEGA_BOUNDS``).  Ineligible forces fall back to
    the staged path (bit-identical by construction), logged once.  With
    ``use_pallas``, "auto" also needs the kernel to hold the interval
    (``mega_kernel_fits``): the band never engages a rung whose kernel
    would give way to its XLA reference.
    """
    eligible = (not has_max) and funs_simple
    if method == "megakernel":
        if not eligible:
            _warn_mega_fallback(has_max, funs_simple)
        return eligible
    if method != "auto" or not eligible:
        return False
    band = autotune.mega_bounds()
    min_rows = band.get("min_rows")
    if use_pallas:
        from repro.kernels.megakernel import mega_kernel_fits
        if not mega_kernel_fits(n_rows, n_slots_incl_pad):
            return False
    return (min_rows is not None and int(n_rows) >= int(min_rows)
            and n_slots_incl_pad <= int(band.get("max_buckets", 0)))


_MEGA_FALLBACK_WARNED = set()


def _warn_mega_fallback(has_max: bool, funs_simple: bool) -> None:
    key = (has_max, funs_simple)
    if key in _MEGA_FALLBACK_WARNED:
        return
    _MEGA_FALLBACK_WARNED.add(key)
    why = []
    if has_max:
        why.append("store has max-type tables (-inf neutrals break the "
                   "kernel's one-hot gather exactness)")
    if not funs_simple:
        why.append("app registers non-simple affine functions")
    log.warning("restructure: method='megakernel' forced but %s — using the "
                "staged partition path (bit-identical)", "; ".join(why))


def packed_sort_fits(n_rows: int, max_major: int, bits: int = 32) -> bool:
    """Whether (major, row-index) packs into one ``bits``-wide sort key."""
    idx_bits = max(n_rows - 1, 1).bit_length()
    major_bits = max(int(max_major), 1).bit_length()
    return idx_bits + major_bits <= bits


def _x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


def restructure_path(n: int, pad_uid: int, *, rowmajor_ts: bool,
                     method: str = "auto") -> str:
    """Resolve the restructure backbone for an (n, pad_uid) batch.

    The ladder (DESIGN.md §2.1): counting partition when its bucket
    bounds hold; else the packed single-operand sort (uint32, or uint64
    when x64 is enabled); else the generic 3-key lexsort.  Every
    resolution is logged; the silent-slow case (packed key needs > 32
    bits but x64 is off) warns with the fix.
    """
    if method not in RESTRUCTURE_METHODS:
        raise ValueError(f"method={method!r}; choose from "
                         f"{RESTRUCTURE_METHODS}")
    if method in ("partition", "packed", "megakernel") and not rowmajor_ts:
        raise ValueError(
            f"method={method!r} needs rowmajor_ts=True: all replace the "
            "(ts, slot) tie-break with the flat row index, which is only "
            "equivalent when rows are already in (ts, slot) order")
    if method != "auto":
        # "megakernel" shares the partition's geometry (same histogram
        # backbone); whether chain EVALUATION goes through the fused
        # kernel is the drivers' megakernel_engaged() decision
        path = "partition" if method == "megakernel" else method
    elif not rowmajor_ts:
        path = "lexsort"
    elif partition_fits(n, pad_uid + 1):
        path = "partition"
    elif packed_sort_fits(n, pad_uid, bits=32):
        path = "packed"
    elif packed_sort_fits(n, pad_uid, bits=64) and _x64_enabled():
        path = "packed"
    else:
        if packed_sort_fits(n, pad_uid, bits=64):
            log.warning(
                "restructure: packed key for n=%d, max_major=%d needs more "
                "than 32 bits and jax_enable_x64 is off — falling back to "
                "the slow 3-key lexsort.  Enable x64 (JAX_ENABLE_X64=1 or "
                "jax.config.update('jax_enable_x64', True)) for the "
                "packed-uint64 sort path.", n, pad_uid)
        else:
            log.warning(
                "restructure: packed key for n=%d, max_major=%d exceeds 64 "
                "bits — falling back to the 3-key lexsort.", n, pad_uid)
        path = "lexsort"
    log.debug("restructure: path=%s (n=%d, n_buckets=%d, rowmajor_ts=%s)",
              path, n, pad_uid + 1, rowmajor_ts)
    return path


# ---------------------------------------------------------------------------
# Backbones
# ---------------------------------------------------------------------------
def packed_stable_sort(major: jnp.ndarray, max_major: int):
    """Stable sort of rows by an integer major key via ONE single-operand
    sort of ``major << idx_bits | index`` packed keys (~6x faster than a
    multi-key lexsort on CPU XLA; DESIGN.md §2.1).  Keys pack into uint32
    when they fit, else uint64 (requires ``jax_enable_x64``).

    ``major`` must lie in [0, max_major].  Returns
    ``(order, major_sorted, pos)`` with ``order`` the sorted->original
    gather map and ``pos`` the inverse permutation (original row ->
    sorted position, via vectorized binary search instead of a scatter).

    Shared by chain restructuring (major = state uid) and the owner-routed
    exchange (major = destination shard).
    """
    n = major.shape[0]
    idx_bits = max(n - 1, 1).bit_length()
    if packed_sort_fits(n, max_major, bits=32):
        dt = jnp.uint32
    elif packed_sort_fits(n, max_major, bits=64):
        if not _x64_enabled():
            raise ValueError(
                f"packed_stable_sort: key for n={n}, max_major={max_major} "
                "needs a uint64 pack but jax_enable_x64 is off — enable x64 "
                "(JAX_ENABLE_X64=1) or use the lexsort path")
        dt = jnp.uint64
    else:
        raise ValueError(
            f"packed_stable_sort: (major, index) for n={n}, "
            f"max_major={max_major} exceeds 64 bits — use the lexsort path")
    idx = jnp.arange(n, dtype=jnp.int32)
    shift = dt(1 << idx_bits)
    packed = major.astype(dt) * shift + idx.astype(dt)
    keys = jnp.sort(packed)
    order = (keys & dt((1 << idx_bits) - 1)).astype(jnp.int32)
    major_s = (keys // shift).astype(jnp.int32)
    # keys are unique, so each row's sorted position == binary search
    pos = jnp.searchsorted(keys, packed,
                           method="scan_unrolled").astype(jnp.int32)
    return order, major_s, pos


def partition_permutation(major: jnp.ndarray, rank: jnp.ndarray,
                          counts: jnp.ndarray):
    """(starts, pos, order) of the stable partition from its one-pass
    (rank, counts): exclusive bucket offsets, each row's sorted position
    by direct arithmetic, and the inverted permutation.  The ONE place
    this assembly lives — shared by the chain geometry below and the
    exchange bucketing (``ownership.bucket_by_owner``)."""
    n = major.shape[0]
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)     # exclusive
    pos = jnp.take(starts, major) + rank                         # direct
    order = jnp.zeros((n,), jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32))
    return starts, pos, order


def _partition_chains(major: jnp.ndarray, n_buckets: int, *,
                      use_pallas: bool = False,
                      rank_counts=None, geometry: bool = True,
                      block_rows: Optional[int] = None):
    """Stable counting partition of one batch: the full chain geometry
    from ONE pass over the keys (rank + histogram), no sort, no binary
    search, no flag-compare pass.

    Returns ``(order, major_sorted, Chains)``; ``rank_counts`` lets the
    stream driver inject a batched kernel result.  ``geometry=False``
    skips the per-row seg_id/pos/seg_end scatters that only the staged
    segscan path reads — the fused megakernel rebuilds the flags it needs
    in VMEM, so its plan carries just order/inv/seg_start + histograms.
    """
    from repro.kernels.radix_partition.ops import radix_partition_rank

    n = major.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if rank_counts is None:
        rank, counts = radix_partition_rank(major, n_buckets,
                                            use_pallas=use_pallas,
                                            block_rows=block_rows)
    else:
        rank, counts = rank_counts
    starts, inv, order = partition_permutation(major, rank, counts)
    major_s = jnp.take(major, order)
    nz = counts > 0
    # segment geometry straight from the histogram (empty buckets -> drop)
    seg_start = jnp.zeros((n,), bool).at[
        jnp.where(nz, starts, n)].set(True, mode="drop")
    if geometry:
        seg_end = jnp.zeros((n,), bool).at[
            jnp.where(nz, starts + counts - 1, n)].set(True, mode="drop")
        seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
        pos = idx - jnp.take(starts, major_s)
    else:
        seg_end = seg_id = pos = None
    chains = Chains(
        order=order, inv=inv, seg_start=seg_start, seg_id=seg_id, pos=pos,
        seg_end=seg_end, n_chains=jnp.sum(nz.astype(jnp.int32)),
        max_len=jnp.max(counts), counts=counts, starts=starts)
    return order, major_s, chains


def _sorted_chains(uid_s: jnp.ndarray, order: jnp.ndarray,
                   inv: jnp.ndarray) -> Chains:
    """Chain geometry from a sorted uid column (the sort backbones)."""
    n = uid_s.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), uid_s[1:] != uid_s[:-1]])
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    start_idx = jax.lax.cummax(jnp.where(seg_start, idx, 0))
    pos = idx - start_idx
    seg_end = jnp.concatenate(
        [uid_s[1:] != uid_s[:-1], jnp.ones((1,), bool)])
    return Chains(order=order, inv=inv, seg_start=seg_start, seg_id=seg_id,
                  pos=pos, seg_end=seg_end, n_chains=seg_id[-1] + 1,
                  max_len=jnp.max(pos) + 1)


def _sorted_view(ops: OpBatch, uid_s: jnp.ndarray, order: jnp.ndarray,
                 light: bool) -> OpBatch:
    return OpBatch(
        uid=uid_s,
        ts=None if light else jnp.take(ops.ts, order),
        txn=None if light else jnp.take(ops.txn, order),
        slot=None if light else jnp.take(ops.slot, order),
        kind=None if light else jnp.take(ops.kind, order),
        fun=jnp.take(ops.fun, order),
        gate=None if light else jnp.take(ops.gate, order),
        operand=jnp.take(ops.operand, order, axis=0),
        valid=jnp.take(ops.valid, order),
    )


def restructure(ops: OpBatch, pad_uid: int, *,
                rowmajor_ts: bool = False,
                light: bool = False,
                method: str = "auto",
                use_pallas: bool = False,
                geometry: bool = True) -> Tuple[OpBatch, Chains]:
    """Group the op batch into operation chains.

    Invalid (padding) ops are routed to the padding chain (uid = pad_uid)
    and group to the end; chain order within a state follows (ts, slot) so
    that a transaction's intra-state ops keep their registration order.

    ``rowmajor_ts``: caller's promise that flat row order already equals
    (ts, slot) lexicographic order — true for every batch built by
    ``build_opbatch`` (ts = ts_base + txn, rows laid out (txn, slot)).
    Then the stable tie-break is the flat row index, and the backbone is
    chosen by ``restructure_path``: the one-pass counting partition
    (O(N + K), with the commit histograms as a by-product), else the
    packed single-operand sort, else the generic lexsort.  All backbones
    produce bit-identical output.

    ``light``: gather only the columns the segmented-scan path reads
    (uid, fun, operand, valid); ts/txn/slot/kind/gate are ``None`` in the
    returned sorted batch.  Lockstep/mvlk callers need the full view.

    ``method``: force a backbone ("partition" / "packed" / "lexsort");
    "auto" resolves the ladder.  ``use_pallas`` lets the partition path
    use the Pallas kernel when its bucket bound holds.  ``geometry=False``
    (partition path only) builds the megakernel's light plan — see
    ``_partition_chains``.
    """
    uid = jnp.where(ops.valid, ops.uid, pad_uid)
    n = uid.shape[0]
    path = restructure_path(n, pad_uid, rowmajor_ts=rowmajor_ts,
                            method=method)

    if path == "partition":
        order, uid_s, chains = _partition_chains(uid, pad_uid + 1,
                                                 use_pallas=use_pallas,
                                                 geometry=geometry)
    elif path == "packed":
        order, uid_s, inv = packed_stable_sort(uid, pad_uid)
        chains = _sorted_chains(uid_s, order, inv)
    else:
        idx = jnp.arange(n, dtype=jnp.int32)
        order = jnp.lexsort((ops.slot, ops.ts, uid))  # uid major, ts, slot
        uid_s = jnp.take(uid, order)
        inv = jnp.zeros((n,), jnp.int32).at[order].set(idx)
        chains = _sorted_chains(uid_s, order, inv)

    return _sorted_view(ops, uid_s, order, light), chains


def restructure_stream(ops_all: OpBatch, pad_uid: int, *,
                       rowmajor_ts: bool = False,
                       light: bool = False,
                       method: str = "auto",
                       use_pallas: bool = False,
                       geometry: bool = True,
                       block_rows: Optional[int] = None):
    """Batched restructure over stacked ``[n_intervals, N]`` op batches.

    On the partition path the within-bucket ranks and histograms for ALL
    intervals come from ONE (kernel) dispatch — the fused drivers' hoisted
    one-pass plan; only the cheap geometry assembly is vmapped.  Other
    paths vmap the per-batch restructure unchanged.  ``geometry=False``
    (partition path only) builds the megakernel's light plan — see
    ``_partition_chains``.
    """
    n = ops_all.uid.shape[-1]
    path = restructure_path(n, pad_uid, rowmajor_ts=rowmajor_ts,
                            method=method)
    if path != "partition":
        return jax.vmap(lambda o: restructure(
            o, pad_uid, rowmajor_ts=rowmajor_ts, light=light,
            method=path))(ops_all)

    from repro.kernels.radix_partition.ops import radix_partition_rank
    uid = jnp.where(ops_all.valid, ops_all.uid, pad_uid)   # [n_i, N]
    rank, counts = radix_partition_rank(uid, pad_uid + 1,
                                        use_pallas=use_pallas,
                                        block_rows=block_rows)

    def assemble(o, u, r, c):
        order, uid_s, chains = _partition_chains(u, pad_uid + 1,
                                                 rank_counts=(r, c),
                                                 geometry=geometry)
        return _sorted_view(o, uid_s, order, light), chains

    return jax.vmap(assemble)(ops_all, uid, rank, counts)


def commit_index(uid_sorted: jnp.ndarray, n_slots_incl_pad: int):
    """Per-state commit gather map from the sorted uid column.

    Returns ``(pos, ok)`` with ``pos[u]`` = sorted index of the *last* op
    of chain ``u`` and ``ok[u]`` = chain ``u`` has ops in this batch.  The
    state update then becomes a [S+1] gather + select instead of an [N]
    scatter (CPU/TPU scatters serialize; binary search vectorizes).

    The partition path does not need this: its histogram gives the same
    map directly (``commit_from_histogram``).
    """
    slots = jnp.arange(n_slots_incl_pad, dtype=uid_sorted.dtype)
    pos = jnp.searchsorted(uid_sorted, slots, side="right",
                           method="scan_unrolled") - 1
    ok = (pos >= 0) & (jnp.take(uid_sorted, jnp.maximum(pos, 0)) == slots)
    return jnp.maximum(pos, 0), ok


def commit_from_histogram(counts: jnp.ndarray, starts: jnp.ndarray):
    """Commit gather map from the partition histogram: the last op of
    chain ``u`` sits at ``starts[u] + counts[u] - 1`` — bit-identical to
    ``commit_index`` (searchsorted-right of u == starts[u] + counts[u])
    with the two binary-search passes gone."""
    pos = jnp.maximum(starts + counts - 1, 0).astype(jnp.int32)
    return pos, counts > 0


def segmented_scan_affine(a: jnp.ndarray, b: jnp.ndarray,
                          seg_start: jnp.ndarray,
                          exclusive: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Segmented scan of affine maps f(v) = a*v + b under composition.

    Composition (applied left-to-right): (a2,b2)∘(a1,b1) = (a2*a1, a2*b1+b2).
    Returns per-op (A, B) such that the state seen by op i within its chain is
    A_i * v0 + B_i (exclusive) — the paper's multiversion value at ts_i.

    Implemented as an explicit log-step Hillis–Steele sweep with
    segment-flag blocking (the same scheme the Pallas kernel uses inside a
    block).  Unlike ``lax.associative_scan`` — whose combine tree depends
    on an element's *global* array offset — the association here is fixed
    by each op's position **within its segment**, so a chain produces
    bit-identical results wherever it sits in the array.  The sharded
    fused driver relies on this: the same chain lands at different offsets
    on different devices and must still match the single-device schedule
    bit for bit (DESIGN.md §2.5).
    """
    n = a.shape[0]
    f = seg_start
    a_inc, b_inc = a, b
    d = 1
    while d < n:
        ap = jnp.concatenate([jnp.ones_like(a_inc[:d]), a_inc[:-d]], axis=0)
        bp = jnp.concatenate([jnp.zeros_like(b_inc[:d]), b_inc[:-d]], axis=0)
        fp = jnp.concatenate([jnp.ones((d,), bool), f[:-d]])
        blocked = f[:, None]
        a_inc, b_inc = (jnp.where(blocked, a_inc, a_inc * ap),
                        jnp.where(blocked, b_inc, a_inc * bp + b_inc))
        f = f | fp
        d *= 2
    if not exclusive:
        return a_inc, b_inc
    # shift right within segments: identity at segment starts.
    ident_a = jnp.ones_like(a[:1])
    ident_b = jnp.zeros_like(b[:1])
    a_exc = jnp.concatenate([ident_a, a_inc[:-1]], axis=0)
    b_exc = jnp.concatenate([ident_b, b_inc[:-1]], axis=0)
    a_exc = jnp.where(seg_start[:, None], jnp.ones_like(a_exc), a_exc)
    b_exc = jnp.where(seg_start[:, None], jnp.zeros_like(b_exc), b_exc)
    return a_exc, b_exc


def segmented_scan_max(m: jnp.ndarray, seg_start: jnp.ndarray,
                       exclusive: bool = True) -> jnp.ndarray:
    """Segmented running max (for max-type tables, e.g. LPC sketches).

    Same segment-relative Hillis–Steele sweep as the affine scan (max is
    order-insensitive, but the uniform structure keeps the two paths'
    round counts identical).
    """
    neg = jnp.full_like(m, -jnp.inf)
    n = m.shape[0]
    f = seg_start
    m_inc = m
    d = 1
    while d < n:
        mp = jnp.concatenate([neg[:d], m_inc[:-d]], axis=0)
        fp = jnp.concatenate([jnp.ones((d,), bool), f[:-d]])
        m_inc = jnp.where(f[:, None], m_inc, jnp.maximum(m_inc, mp))
        f = f | fp
        d *= 2
    if not exclusive:
        return m_inc
    m_exc = jnp.concatenate([neg[:1], m_inc[:-1]], axis=0)
    return jnp.where(seg_start[:, None], neg, m_exc)
