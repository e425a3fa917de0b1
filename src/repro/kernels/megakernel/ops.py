"""Jit-friendly wrapper: pad to the megakernel layout, dispatch, commit.

``fused_chain_eval`` is the megakernel rung's drop-in replacement for the
staged ``tstream_scan_plan → tstream_scan_coefs → tstream_scan_execute``
pipeline of ``core/engines.py`` — same inputs (a sorted light OpBatch +
its partition Chains), same outputs (sorted-layout results, new state
values, EngineStats), bit-identical values on every shape.  The Pallas
kernel carries the interval when it fits VMEM; otherwise the XLA ref
(``ref.py`` — the staged pipeline recomposed op-for-op) handles it, the
same structural-fallback pattern as ``radix_partition.kernel_fits``.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax.numpy as jnp

from ..runtime import default_interpret
from . import kernel as K
from .ref import fused_chain_eval_ref

# VMEM fit bounds for the single-block kernel (interpret-validated; a
# real-device tuning run will tighten them per device kind):
#   MEGA_MAX_ROWS  — the whole interval is ONE block, so ~8 [rows, 128]
#                    f32 residents bound the row count.
#   MEGA_MAX_CELLS — the one-hot gather/scatter matrix is
#                    [rows, n_slots_padded] f32 (4 MiB at 2^20 cells).
MEGA_MAX_ROWS = 4096
MEGA_MAX_CELLS = 1 << 22
# EngineStats.path of a megakernel dispatch that the XLA reference served
FALLBACK_PATH = "megakernel-xla-fallback"

log = logging.getLogger(__name__)


def _pad_rows(n: int) -> int:
    return -(-n // 8) * 8  # sublane multiple


def mega_kernel_fits(n_rows: int, n_slots: int) -> bool:
    """Whether the Pallas megakernel carries this interval (else the XLA
    ref — bit-identical — does)."""
    rows = _pad_rows(int(n_rows))
    slots = -(-int(n_slots) // K.LANES) * K.LANES
    return rows <= MEGA_MAX_ROWS and rows * slots <= MEGA_MAX_CELLS


def fused_chain_eval(values: jnp.ndarray, sops, ch, pad_uid: int, *,
                     a_lut: jnp.ndarray, b_lut: jnp.ndarray,
                     use_pallas: bool = False,
                     interpret: Optional[bool] = None):
    """Evaluate all chains of one restructured interval in one dispatch.

    values: f32[S, W] state (S includes the pad slot); sops: sorted light
    OpBatch; ch: partition Chains (counts/starts REQUIRED — the commit
    map comes from the histogram).  a_lut/b_lut: the app's simple-affine
    LUTs (``engines.simple_affine_luts``).  Returns
    ``(res_sorted, new_values, stats)`` exactly like
    ``tstream_scan_execute(..., raw=True)``.
    """
    from repro.core.engines import EngineStats
    from repro.core.restructure import commit_from_histogram

    assert ch.counts is not None, "megakernel needs the partition histogram"
    n, w = sops.operand.shape
    s = values.shape[0]
    interp = default_interpret() if interpret is None else interpret

    if use_pallas and mega_kernel_fits(n, s):
        rows = _pad_rows(n)
        s_pad = -(-s // K.LANES) * K.LANES
        a_sel = jnp.take(a_lut, sops.fun).astype(jnp.float32)
        b_is = jnp.take(b_lut, sops.fun).astype(jnp.float32)
        flags = jnp.broadcast_to(
            ch.seg_start.astype(jnp.float32)[:, None], (n, K.LANES))
        # padding rows: own dead segment (flag=1), identity coefficients,
        # invalid, routed to the pad slot (post = v0[pad] = 0 — their
        # commit contributions are exact zeros)
        flags = jnp.pad(flags, ((0, rows - n), (0, 0)), constant_values=1.0)
        a_sel = jnp.pad(a_sel, (0, rows - n), constant_values=1.0)[:, None]
        b_is = jnp.pad(b_is, (0, rows - n))[:, None]
        valid = jnp.pad(sops.valid.astype(jnp.float32),
                        (0, rows - n))[:, None]
        uid = jnp.pad(sops.uid.astype(jnp.int32), (0, rows - n),
                      constant_values=pad_uid)[:, None]
        operand = jnp.pad(sops.operand.astype(jnp.float32),
                          ((0, rows - n), (0, K.LANES - w)))
        vals = jnp.pad(values.astype(jnp.float32),
                       ((0, s_pad - s), (0, K.LANES - values.shape[1])))
        pre, post, acc = K.fused_chain_pallas(
            flags, a_sel, b_is, valid, uid, operand, vals, interpret=interp)
        pre, post = pre[:n, :w], post[:n, :w]
        committed = acc[:s, :values.shape[1]]
        _, commit_ok = commit_from_histogram(ch.counts, ch.starts)
        new_values = jnp.where(commit_ok[:, None], committed, values)
        new_values = new_values.at[pad_uid].set(0.0)
        res = dict(pre=pre, post=post, success=sops.valid)
        stats = EngineStats(
            rounds=jnp.ceil(jnp.log2(ch.max_len.astype(jnp.float32) + 1)),
            n_chains=ch.n_chains, max_chain=ch.max_len,
            n_ops=n, scheme="tstream", path="megakernel")
        return res, new_values, stats

    res, new_values, stats = fused_chain_eval_ref(values, sops, ch, pad_uid,
                                                  a_lut=a_lut, b_lut=b_lut)
    if use_pallas:
        _count_fallback(n, s)
        stats = dataclasses.replace(stats, path=FALLBACK_PATH)
    return res, new_values, stats


def _count_fallback(n_rows: int, n_slots: int) -> None:
    """A megakernel dispatch asked for the kernel but the interval does
    not fit it: counted (once per traced program) in the default telemetry
    registry and logged, and the rung's stats report ``FALLBACK_PATH``."""
    from repro.runtime.telemetry import get_default
    get_default().event(
        "kernels.mega_fallback",
        "megakernel: %d rows x %d slots exceed the kernel's bounds "
        "(MEGA_MAX_ROWS=%d, MEGA_MAX_CELLS=%d) — the XLA reference "
        "evaluates this interval", n_rows, n_slots, MEGA_MAX_ROWS,
        MEGA_MAX_CELLS, logger=log)
