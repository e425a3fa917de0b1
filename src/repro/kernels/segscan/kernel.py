"""Pallas TPU kernel: segmented scans over sorted operation chains.

This is the compute hot spot of TStream's state-access mode: after dynamic
restructuring, every operation chain is a contiguous, timestamp-sorted
segment of the op stream.  Evaluating all chains = one segmented scan:

  * affine family — compose f(v) = a*v + b (READ/WRITE/ADD/PUT/affine RMW)
  * max family    — running elementwise max (LPC sketches)

TPU mapping
-----------
The op stream [N, W] is tiled into VMEM blocks of BLOCK_ROWS rows on the
sublane axis (W padded to the 128-lane register width by ``ops.py``).  The
grid iterates blocks *sequentially* (TPU grid order); the running segment
carry lives in VMEM scratch — the standard Pallas sequential-carry pattern.
Within a block the scan is a log2(BLOCK_ROWS)-step Hillis–Steele sweep with
segment-flag blocking, so per-chain evaluation is log-depth — strictly more
parallel than the paper's one-thread-per-chain sequential walk.

VMEM budget per grid step (BLOCK_ROWS=256, LANES=128, f32):
3 inputs + 2 outputs + 2 carries ≈ 6 × 128 KiB ≈ 0.75 MiB ≪ 16 MiB VMEM.
All matmul-free; bandwidth-bound on the VPU, which is the right regime for
a data-movement-dominated scheduling workload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
LANES = 128


def _shift_down(x: jnp.ndarray, d: int, fill) -> jnp.ndarray:
    """x[i-d] with ``fill`` for i < d (rows axis)."""
    pad = jnp.full((d,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([pad, x[:-d]], axis=0)


def _before_first_start(flags: jnp.ndarray, block_rows: int) -> jnp.ndarray:
    """Rows with no segment start at or above them in the block: those
    continue the carry's segment.  An inclusive prefix-OR of the f32 flags
    as a log-step shifted max (Mosaic has no cumsum lowering)."""
    seen = flags
    d = 1
    while d < block_rows:
        seen = jnp.maximum(seen, _shift_down(seen, d, 0.0))
        d *= 2
    return seen == 0.0


def _segscan_affine_kernel(f_ref, a_ref, b_ref, oa_ref, ob_ref,
                           ca_ref, cb_ref, *, block_rows: int):
    """Exclusive segmented scan of affine maps, carry across blocks."""
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        ca_ref[...] = jnp.ones_like(ca_ref)
        cb_ref[...] = jnp.zeros_like(cb_ref)

    fr = f_ref[...]               # [R, LANES] f32 seg-start flags (0/1)
    f = fr > 0.0
    a = a_ref[...]
    b = b_ref[...]

    # --- inclusive segmented scan within the block (Hillis–Steele). ------
    # combine(L, R) = R if R's range already crossed a segment start,
    #                 else R∘L:  A = A_R·A_L,  B = A_R·B_L + B_R.
    # The shift fill uses flag=1: the block boundary blocks combining;
    # the carry is folded in afterwards.  Flags stay f32 (OR = max): Mosaic
    # cannot shift or concatenate i1 vectors.
    fi, ai, bi = fr, a, b
    d = 1
    while d < block_rows:
        fL = _shift_down(fi, d, 1.0)
        aL = _shift_down(ai, d, 1.0)
        bL = _shift_down(bi, d, 0.0)
        crossed = fi > 0.0
        na = jnp.where(crossed, ai, ai * aL)
        nb = jnp.where(crossed, bi, ai * bL + bi)
        fi, ai, bi = jnp.maximum(fi, fL), na, nb
        d *= 2

    # --- exclusive view: identity at row 0 and at segment starts. --------
    ae = _shift_down(ai, 1, 1.0)
    be = _shift_down(bi, 1, 0.0)
    ae = jnp.where(f, jnp.ones_like(ae), ae)
    be = jnp.where(f, jnp.zeros_like(be), be)

    # --- fold the running carry into rows before the first segment start.
    open_head = _before_first_start(fr, block_rows)
    ca, cb = ca_ref[...], cb_ref[...]
    oa_ref[...] = jnp.where(open_head, ae * ca, ae)
    ob_ref[...] = jnp.where(open_head, ae * cb + be, be)

    # --- update carry with the block's last inclusive row. ---------------
    any_flag = jnp.max(fr, axis=0, keepdims=True) > 0.0
    la, lb = ai[-1:], bi[-1:]
    ca_ref[...] = jnp.where(any_flag, la, la * ca)
    cb_ref[...] = jnp.where(any_flag, lb, la * cb + lb)


def _segscan_max_kernel(f_ref, m_ref, om_ref, cm_ref, *, block_rows: int):
    """Exclusive segmented running-max, carry across blocks."""
    g = pl.program_id(0)
    neg = jnp.float32(-jnp.inf)

    @pl.when(g == 0)
    def _init():
        cm_ref[...] = jnp.full_like(cm_ref, neg)

    fr = f_ref[...]
    f = fr > 0.0
    m = m_ref[...]

    fi, mi = fr, m
    d = 1
    while d < block_rows:
        fL = _shift_down(fi, d, 1.0)
        mL = _shift_down(mi, d, neg)
        mi = jnp.where(fi > 0.0, mi, jnp.maximum(mi, mL))
        fi = jnp.maximum(fi, fL)
        d *= 2

    me = _shift_down(mi, 1, neg)
    me = jnp.where(f, jnp.full_like(me, neg), me)

    open_head = _before_first_start(fr, block_rows)
    cm = cm_ref[...]
    om_ref[...] = jnp.where(open_head, jnp.maximum(me, cm), me)

    any_flag = jnp.max(fr, axis=0, keepdims=True) > 0.0
    lm = mi[-1:]
    cm_ref[...] = jnp.where(any_flag, lm, jnp.maximum(cm, lm))


def segscan_affine_pallas(flags: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                          *, interpret: bool = True,
                          block_rows: int = BLOCK_ROWS):
    """Exclusive segmented affine scan.  flags/a/b: f32[N, LANES], N % block_rows == 0."""
    n = a.shape[0]
    assert n % block_rows == 0 and a.shape[1] == LANES, (a.shape, block_rows)
    spec = pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))
    kernel = functools.partial(_segscan_affine_kernel, block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype)],
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32),
                        pltpu.VMEM((1, LANES), jnp.float32)],
        interpret=interpret,
        name="segscan_affine",
    )(flags, a, b)


def segscan_max_pallas(flags: jnp.ndarray, m: jnp.ndarray,
                       *, interpret: bool = True,
                       block_rows: int = BLOCK_ROWS):
    """Exclusive segmented max scan.  flags/m: f32[N, LANES], N % block_rows == 0."""
    n = m.shape[0]
    assert n % block_rows == 0 and m.shape[1] == LANES, (m.shape, block_rows)
    spec = pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))
    kernel = functools.partial(_segscan_max_kernel, block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(m.shape, m.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.float32)],
        interpret=interpret,
        name="segscan_max",
    )(flags, m)
