"""Pallas TPU kernel: one-pass stable radix/counting partition ranks.

Dynamic restructuring (paper §IV-C1) groups the op stream into per-state
chains.  The major keys are *bounded integers* (state uid < n_slots,
destination shard < n_dev), so the comparison-sort backbone
(``jnp.sort`` — O(N log² N) bitonic on accelerators) is overkill: a
histogram + exclusive-prefix + stable rank is O(N + K) and yields the
same stable grouping, plus the per-bucket histograms that the commit
gather map and the exchange capacities need — for free.

This kernel computes, in ONE sequential-grid pass over the key stream:

  ``rank[i]``  — number of earlier rows with the same key (the stable
                 within-bucket rank; ``pos[i] = starts[key[i]] + rank[i]``
                 then places every row without any sort), and
  ``counts[k]`` — the full key histogram (the last grid step's running
                 histogram).

TPU mapping
-----------
Keys are tiled into blocks of BLOCK_ROWS rows; the bucket axis is padded
to a lane multiple.  Each grid step builds a one-hot ``[BLOCK_ROWS, K]``
matrix (broadcasted-iota compare — the same MXU/VPU-friendly trick as
``hash_probe``), takes its within-block exclusive column prefix sum (a
strictly-lower-triangular matmul on the MXU), adds
the running histogram carried in VMEM scratch across grid steps (the
standard Pallas sequential-carry pattern, as in ``segscan``), and reads
each row's rank back out of its own one-hot column by a masked row-sum.
Counts stay exact in f32 (N < 2^24).

The grid is ``(batch, n_blocks)``: the batch axis lets a whole stream of
stacked intervals partition in one dispatch (the carry re-initializes at
block 0 of every batch), without relying on vmap-of-pallas_call.

VMEM per grid step: one-hot + prefix ≈ 2 · BLOCK_ROWS · K · 4 B
(BLOCK_ROWS=256, K=2048: 4 MiB ≪ 16 MiB); larger bucket counts fall back
to the XLA counting path (``ref.py``), the next rung of the ladder.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
LANES = 128
SUBLANES = 8  # a block's second-minor dim is a multiple of this
MAX_KERNEL_BUCKETS = 2048  # one-hot VMEM bound; beyond -> XLA counting ref
MAX_KERNEL_ROWS = 1 << 24  # f32 carry exactness: ranks/counts < 2^24


def _radix_rank_kernel(k_ref, rank_ref, cnt_ref, hist_ref, *,
                       block_rows: int, n_buckets_padded: int):
    """Running within-bucket rank; histogram carry across a batch's blocks."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    k = k_ref[...]                                     # [B, 1] i32 keys
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_rows, n_buckets_padded),
                                    1)
    oh = (iota == k).astype(jnp.float32)               # [B, K] one-hot
    # within-block exclusive column prefix sum as a strictly-lower-
    # triangular matmul (Mosaic has no cumsum lowering); 0/1 operands and
    # counts < 2^24 keep it exact in f32 at HIGHEST precision
    row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 1)
    lower = (col < row).astype(jnp.float32)
    ex = jnp.dot(lower, oh, preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
    carry = hist_ref[0:1, :]                           # [1, K] running hist
    r = jnp.sum((ex + carry) * oh, axis=1, keepdims=True)  # [B, 1] exact
    rank_ref[...] = r.astype(jnp.int32)

    new_hist = carry + jnp.sum(oh, axis=0, keepdims=True)
    hist_ref[...] = jnp.broadcast_to(new_hist, hist_ref.shape)
    # constant index map: the block stays resident and the last grid step
    # of this batch leaves the total histogram (in all 8 sublane rows: a
    # block must span 8 rows, row 0 is read back)
    cnt_ref[...] = jnp.broadcast_to(new_hist.astype(jnp.int32),
                                    cnt_ref.shape)


def radix_partition_pallas(keys: jnp.ndarray, n_buckets_padded: int, *,
                           interpret: bool = True,
                           block_rows: int = BLOCK_ROWS):
    """keys: i32[BN, R] with R % block_rows == 0 and values in
    [0, n_buckets_padded); returns (rank i32[BN, R], counts i32[BN, K])."""
    bn, rows = keys.shape
    assert rows % block_rows == 0, (keys.shape, block_rows)
    assert n_buckets_padded % LANES == 0, (n_buckets_padded,)
    n_blocks = rows // block_rows
    kernel = functools.partial(_radix_rank_kernel, block_rows=block_rows,
                               n_buckets_padded=n_buckets_padded)
    kspec = pl.BlockSpec((block_rows, 1),
                         lambda b, t, nb=n_blocks: (b * nb + t, 0))
    rank, counts = pl.pallas_call(
        kernel,
        grid=(bn, n_blocks),
        in_specs=[kspec],
        out_specs=[kspec,
                   pl.BlockSpec((SUBLANES, n_buckets_padded),
                                lambda b, t: (b, 0))],
        out_shape=[jax.ShapeDtypeStruct((bn * rows, 1), jnp.int32),
                   jax.ShapeDtypeStruct((bn * SUBLANES, n_buckets_padded),
                                        jnp.int32)],
        scratch_shapes=[pltpu.VMEM((SUBLANES, n_buckets_padded),
                                   jnp.float32)],
        interpret=interpret,
        name="radix_partition",
    )(keys.reshape(bn * rows, 1))
    counts = counts.reshape(bn, SUBLANES, n_buckets_padded)[:, 0]
    return rank[:, 0].reshape(bn, rows), counts
