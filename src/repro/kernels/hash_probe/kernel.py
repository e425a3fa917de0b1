"""Pallas TPU kernel: bucketed cuckoo-style hash probe (key -> slot).

The paper's time-breakdown (§VI-D) finds index lookup to be the residual
bottleneck once locking is removed (the *No-Lock* "Others" share).  TStream's
state tables use direct addressing for dense keys; for *sparse* keys (the
framework's data-pipeline dedup / per-domain statistics), this kernel
resolves key -> table slot.

TPU adaptation: TPUs have no efficient random gather inside a kernel, so the
probe is reformulated as a **one-hot matmul gather** (MXU-friendly): a query
block builds a one-hot [BLK, n_buckets] matrix and multiplies it against the
bucketed key table [n_buckets, assoc].  Key equality is checked exactly by
splitting 32-bit keys into two 16-bit halves (each exact in f32).  Linear
probing over MAX_PROBES consecutive buckets handles overflow.

VMEM: table 8192×8 ×2 halves ×4B = 512 KiB + one-hot BLK×8192×4B (BLK=128:
4 MiB) — fits; larger tables tile the bucket axis via the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 128
ASSOC = 8
MAX_PROBES = 4
_MULT = 2654435761  # Knuth multiplicative hash


def bucket_of(key: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    h = (key.astype(jnp.uint32) * jnp.uint32(_MULT)) >> jnp.uint32(16)
    return (h % jnp.uint32(n_buckets)).astype(jnp.int32)


def _probe_kernel(q_ref, base_ref, tlo_ref, thi_ref, out_ref, *,
                  n_buckets: int):
    q = q_ref[...]                       # [BLK, 1] i32 query keys
    blk = q.shape[0]
    qlo = (q & 0xFFFF).astype(jnp.float32)                  # [BLK, 1]
    qhi = ((q >> 16) & 0xFFFF).astype(jnp.float32)
    tlo = tlo_ref[...]                   # [n_buckets, ASSOC] f32 halves
    thi = thi_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (blk, ASSOC), 1).astype(
        jnp.float32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (blk, n_buckets), 1)

    base = base_ref[...]                 # [BLK, 1] i32 home bucket
    found_slot = jnp.full((blk, 1), -1, jnp.int32)
    for p in range(MAX_PROBES):
        bkt = base + p
        bkt = jnp.where(bkt >= n_buckets, bkt - n_buckets, bkt)
        onehot = (iota == bkt).astype(jnp.float32)
        # HIGHEST: the MXU's default bf16 pass would round 16-bit halves
        cand_lo = jnp.dot(onehot, tlo, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        cand_hi = jnp.dot(onehot, thi, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        match = (cand_lo == qlo) & (cand_hi == qhi)        # [BLK, ASSOC]
        # first matching lane as an f32 min (Mosaic reduces f32 only)
        first = jnp.min(jnp.where(match, lane, float(ASSOC)), axis=1,
                        keepdims=True)
        hit = first < float(ASSOC)
        slot = bkt * ASSOC + first.astype(jnp.int32)
        found_slot = jnp.where((found_slot < 0) & hit, slot, found_slot)
    out_ref[...] = found_slot


def hash_probe_pallas(keys: jnp.ndarray, table_lo: jnp.ndarray,
                      table_hi: jnp.ndarray, *, interpret: bool = True,
                      block_q: int = BLOCK_Q):
    """keys: i32[N] (N % block_q == 0); table halves f32[n_buckets, ASSOC].

    Returns i32[N] slot index, -1 if absent.
    """
    n = keys.shape[0]
    n_buckets = table_lo.shape[0]
    assert n % block_q == 0 and table_lo.shape == (n_buckets, ASSOC)
    assert n_buckets >= MAX_PROBES, n_buckets
    kernel = functools.partial(_probe_kernel, n_buckets=n_buckets)
    qspec = pl.BlockSpec((block_q, 1), lambda g: (g, 0))
    tspec = pl.BlockSpec((n_buckets, ASSOC), lambda g: (0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(n // block_q,),
        in_specs=[qspec, qspec, tspec, tspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
        name="hash_probe",
    )(keys[:, None], bucket_of(keys, n_buckets)[:, None], table_lo, table_hi)
    return out[:, 0]
