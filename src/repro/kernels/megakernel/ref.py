"""XLA reference for the fused megakernel: the staged pipeline, recomposed.

This is DELIBERATELY the staged ``plan → coefs → execute`` operation
sequence inlined op-for-op (same LUT coefficient expansion, the same
``segmented_scan_affine``, the same compose/apply/commit arithmetic), so
it is bitwise identical to the staged path by construction — XLA does not
reassociate elementwise chains, only reductions.  It doubles as the
structural fallback when an interval exceeds the kernel's VMEM fit
(``ops.mega_kernel_fits``) and as the thing benchmarked on hosts, where
fusing the pipeline still pays by skipping the staged path's materialized
[N, W] coefficient arrays and per-row chain geometry.
"""
from __future__ import annotations

import jax.numpy as jnp


def fused_chain_eval_ref(values: jnp.ndarray, sops, ch, pad_uid: int, *,
                         a_lut: jnp.ndarray, b_lut: jnp.ndarray):
    from repro.core.engines import EngineStats
    from repro.core.restructure import (commit_from_histogram,
                                        segmented_scan_affine)

    n = sops.uid.shape[0]
    # coefficient expansion (== engines.affine_coeffs simple-LUT path,
    # then the no-max-table neutralization of tstream_scan_plan)
    a = jnp.broadcast_to(jnp.take(a_lut.astype(sops.operand.dtype),
                                  sops.fun)[:, None], sops.operand.shape)
    b = jnp.where(jnp.take(b_lut, sops.fun)[:, None], sops.operand,
                  jnp.zeros_like(sops.operand))
    neutralize = (~sops.valid)[:, None]
    a = jnp.where(neutralize, jnp.ones_like(a), a)
    b = jnp.where(neutralize, jnp.zeros_like(b), b)

    # exclusive segmented scan + inclusive composition (== tstream_scan_coefs)
    A, B = segmented_scan_affine(a, b, ch.seg_start, exclusive=True)
    Ai = a * A
    Bi = a * B + b

    # values-dependent stage (== tstream_scan_execute(raw=True))
    v0 = jnp.take(values, sops.uid, axis=0)
    pre = A * v0 + B
    post = Ai * v0 + Bi
    success = sops.valid

    commit_pos, commit_ok = commit_from_histogram(ch.counts, ch.starts)
    committed = jnp.take(post, commit_pos, axis=0)
    new_values = jnp.where(commit_ok[:, None], committed, values)
    new_values = new_values.at[pad_uid].set(0.0)

    vmask = sops.valid
    pre = jnp.where(vmask[:, None], pre, 0.0)
    post = jnp.where(vmask[:, None], post, 0.0)
    res = dict(pre=pre, post=post, success=success & vmask)
    stats = EngineStats(
        rounds=jnp.ceil(jnp.log2(ch.max_len.astype(jnp.float32) + 1)),
        n_chains=ch.n_chains, max_chain=ch.max_len,
        fallback_ops=jnp.int32(0), n_ops=n, scheme="tstream",
        path="megakernel")
    return res, new_values, stats
