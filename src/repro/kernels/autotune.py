"""Hardware-derived kernel dispatch: autotuned grid/block parameters.

Every Pallas kernel in this repo (``segscan``, ``radix_partition``,
``hash_probe``, and the fused ``megakernel``) used to hard-code its block
shape, validated on exactly one CPU host.  BriskStream's lesson
(PAPERS.md) is that *execution-plan selection* — not just kernel quality
— is what scales stream transaction throughput across machines, so this
module makes the block parameters a function of the device:

1. **Candidate derivation** — ``candidates(kernel)`` derives a short list
   of legal block shapes from ``jax.devices()[0]`` properties (core
   count, lane width, VMEM budget).  The first candidate is the
   *default*: on every device kind it reproduces the hand-validated
   shape this repo shipped with, so behavior without a tuning run is
   exactly the pre-autotune behavior.
2. **Microbenchmark on first use** — ``decide()`` times the candidate
   list (min-of-k, interleaved) the first time a ``(kernel,
   shape-bucket, dtype, device_kind)`` key is seen on a *compiled*
   backend.  Under interpret mode (``kernels/runtime.default_interpret``
   — every CPU host, and CI's ``JAX_PALLAS_INTERPRET=1`` runs) timing a
   Python emulation is meaningless, so the decision is the deterministic
   default candidate, recorded with ``source="interpret-default"``.
   Nothing is timed under a trace either: the ops wrappers resolve their
   block while ``jax.jit`` traces them, when no kernel can run, so such a
   decision is the default candidate with ``source="default"``.
3. **Caching** — winners live in an in-process dict keyed by
   ``(kernel, shape_bucket, dtype, device_kind)``; set
   ``REPRO_AUTOTUNE_CACHE=/path.json`` to also round-trip decisions
   through an on-disk JSON cache (loaded lazily, written after every new
   measured decision; defaults are never written).  Decisions are
   deterministic given a cache: the same key never re-benchmarks in one
   process or across processes sharing the disk cache.
4. **Logging** — every decision is logged exactly once per process per
   key (and appended to ``REPRO_AUTOTUNE_LOG`` as JSON lines when set —
   CI uploads that file as a build artifact).
5. **Forcing** — callers pass ``force=<int>`` (threaded from
   ``EngineConfig.kernel_block_params``) to bypass derivation, bench and
   cache entirely; forced values are logged with ``source="forced"``.

The module also owns the **device tables** that turn measured win bands
into dispatch bounds:

* ``LADDER_BOUNDS`` — the restructure ladder's counting-partition auto
  bounds (``core/restructure.partition_fits``).  The CPU row is the
  measured BENCH_restructure.json crossover; accelerator rows are
  provisional estimates (bitonic sort moves the crossover far right)
  pending a real-device tuning run.
* ``MEGA_BOUNDS`` — the fused partition→segscan→commit megakernel's
  auto win band (``core/restructure.megakernel_auto``), from the
  ``kind="fused"`` rows of BENCH_restructure.json.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from .runtime import default_interpret

log = logging.getLogger(__name__)

LANES = 128  # TPU register lane width — all kernels pad lanes to this

# ---------------------------------------------------------------------------
# Device tables: measured win bands -> dispatch bounds
# ---------------------------------------------------------------------------
# Restructure-ladder counting-partition bounds (max_buckets, min_rows):
# "auto" engages the one-pass partition backbone when the key space is at
# most max_buckets and the batch at least min_rows.  The "cpu" row is THE
# measured host crossover (BENCH_restructure.json, PR 3: 1.3-1.8x for
# owner routing at >=655k rows; parity-to-1.1x for a 9-bucket store at
# 512k; loses for large sparse stores).  Accelerator rows are provisional
# — the jnp.sort baseline is an O(N log^2 N) bitonic network there, which
# moves the crossover toward the partition — and are refined by a
# real-device bench run, not trusted blindly (decide() logs which row was
# used).
LADDER_BOUNDS: Dict[str, Tuple[int, int]] = {
    "cpu": (16, 1 << 18),
    "tpu v4": (64, 1 << 16),
    "tpu v5 lite": (64, 1 << 16),
    "tpu v5": (64, 1 << 16),
    "tpu v6 lite": (64, 1 << 16),
}

# Fused megakernel auto band, per device kind:
#   min_rows  — smallest per-interval op count where the fused
#               partition→segscan→commit pipeline beat the staged path
#               (kind="fused" rows of BENCH_restructure.json; interleaved
#               A/B, min-wall).  None = never auto-engage (forced only).
#   max_buckets — the fused path reuses the counting partition, so its
#               bucket bound applies; beyond it the staged path wins by
#               construction.
# The "cpu" row is measured on this host (BENCH_restructure.json,
# kind="fused"): the fused XLA path — no seg_id/pos/seg_end geometry
# passes, no materialized [N, W] A/B/Ai/Bi coefficient arrays — runs at
# parity-within-noise with the staged pipeline (0.99–1.03x end-to-end
# across N ∈ [32k, 512k], slots ∈ [8, 10k]; the segmented scan dominates
# both).  The headline fusion win (one VMEM-resident dispatch instead of
# three HBM round-trips between restructure, coefs and execute) is a
# device property a host A/B cannot exhibit, so the CPU band engages the
# rung from 32k rows for cost-free continuous coverage of the fused
# path — an honest "no measured win, no measured loss", not a speedup
# claim.  Real-device rows are provisional pending a tuning run.
MEGA_BOUNDS: Dict[str, Dict] = {
    "cpu": dict(min_rows=1 << 15, max_buckets=1 << 14),
    "tpu v4": dict(min_rows=1 << 12, max_buckets=1 << 14),
    "tpu v5 lite": dict(min_rows=1 << 12, max_buckets=1 << 14),
    "tpu v5": dict(min_rows=1 << 12, max_buckets=1 << 14),
    "tpu v6 lite": dict(min_rows=1 << 12, max_buckets=1 << 14),
}


def _canon_kind(device_kind: Optional[str]) -> str:
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    return str(device_kind).strip().lower()


def _table_row(table: Dict[str, object], kind: str):
    """The row for ``kind`` (tables are keyed by the lower-cased
    ``device_kind`` JAX reports; a v5e reports "TPU v5 lite").  A kind
    missing from the table raises: another device's row is not a bound
    for this one."""
    if kind not in table:
        raise KeyError(f"no autotune row for device_kind {kind!r}; known "
                       f"kinds: {sorted(table)}")
    return table[kind]


def ladder_bounds(device_kind: Optional[str] = None) -> Tuple[int, int]:
    """(max_buckets, min_rows) for the counting-partition auto rung."""
    return _table_row(LADDER_BOUNDS, _canon_kind(device_kind))


def mega_bounds(device_kind: Optional[str] = None) -> Dict:
    """Auto win band of the fused megakernel rung."""
    return _table_row(MEGA_BOUNDS, _canon_kind(device_kind))


# ---------------------------------------------------------------------------
# Device profile + candidate derivation
# ---------------------------------------------------------------------------
def device_profile(device=None) -> Dict:
    """Coarse hardware profile of one device, with conservative fallbacks
    for backends that don't expose a property (CPU hosts expose almost
    nothing — the fallbacks reproduce the hand-validated CPU shapes)."""
    if device is None:
        device = jax.devices()[0]
    kind = _canon_kind(device.device_kind)
    cores = getattr(device, "num_cores", None) or getattr(
        device, "core_count", None) or os.cpu_count() or 1
    # per-core VMEM budget: 16 MiB on every shipped TPU core; on CPU the
    # "VMEM" is L2-ish — the same 16 MiB keeps interpret-mode shapes
    # identical to the TPU shapes (interpret mode is a TPU emulator, not
    # a CPU backend in its own right)
    vmem = getattr(device, "vmem_size_bytes", None) or 16 * 2 ** 20
    return dict(kind=kind, cores=int(cores), lanes=LANES,
                vmem_bytes=int(vmem),
                platform=getattr(device, "platform", "cpu"))


def candidates(kernel: str, profile: Optional[Dict] = None) -> Tuple[int, ...]:
    """Short candidate list of the kernel's tunable block parameter.

    The FIRST entry is the default (== the shape this repo shipped with
    and validated on CPU); the rest bracket it within the device's VMEM
    budget.  Kernels interpret the parameter as:

      segscan          block_rows  (sublane rows per grid step)
      radix_partition  block_rows  (key rows per grid step)
      hash_probe       block_q     (query rows per grid step)
      megakernel       block_rows  (single-block row capacity)
    """
    p = profile or device_profile()
    # rows such that the kernel's dominant VMEM tenant fits the budget:
    # segscan holds ~7 [rows, LANES] f32 arrays; radix's one-hot is
    # [rows, K<=2048]; hash_probe's one-hot is [rows, n_buckets<=8192]
    budget_rows = max(p["vmem_bytes"] // (8 * LANES * 4), 128)
    if kernel == "segscan":
        cand = [256, 128, 512, 1024]
    elif kernel == "radix_partition":
        cand = [256, 128, 512]
    elif kernel == "hash_probe":
        cand = [128, 256, 512]
    elif kernel == "megakernel":
        cand = [4096, 2048, 8192]
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    out = [c for c in cand if c <= budget_rows]
    return tuple(out or cand[:1])


def shape_bucket(n: int) -> str:
    """Power-of-two shape bucket: one tuning decision covers a 2x range
    of row counts (block choice is insensitive within a bucket; keying
    raw N would re-bench every distinct shape)."""
    b = max(int(n) - 1, 1).bit_length()
    return f"2^{b}"


# ---------------------------------------------------------------------------
# The decision cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Decision:
    kernel: str
    shape_bucket: str
    dtype: str
    device_kind: str
    param: int
    source: str  # interpret-default | default | microbench | forced | disk
    candidates: Tuple[int, ...] = ()
    timings_us: Optional[Dict[str, float]] = None

    @property
    def key(self) -> Tuple[str, str, str, str]:
        return (self.kernel, self.shape_bucket, self.dtype, self.device_kind)


_CACHE: Dict[Tuple[str, str, str, str], Decision] = {}
_LOGGED: set = set()
_DISK_LOADED: set = set()  # cache paths already read this process

_MEASURED = ("microbench", "disk")  # the sources the disk cache keeps
_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_LOG_ENV = "REPRO_AUTOTUNE_LOG"


def clear_cache() -> None:
    """Test hook: forget all in-process decisions (disk cache untouched)."""
    _CACHE.clear()
    _LOGGED.clear()
    _DISK_LOADED.clear()


def _record(d: Decision) -> None:
    _CACHE[d.key] = d
    if d.key not in _LOGGED:
        _LOGGED.add(d.key)
        log.info("autotune: %s[%s,%s,%s] -> %d (%s)", d.kernel,
                 d.shape_bucket, d.dtype, d.device_kind, d.param, d.source)
        logp = os.environ.get(_LOG_ENV, "")
        if logp:
            try:
                with open(logp, "a") as f:
                    f.write(json.dumps(dataclasses.asdict(d)) + "\n")
            except OSError as e:  # artifact logging must never break dispatch
                log.warning("autotune: cannot append to %s: %s", logp, e)


def _disk_path(cache_path: Optional[str]) -> Optional[str]:
    return cache_path or os.environ.get(_CACHE_ENV) or None


def _load_disk(path: str) -> None:
    if path in _DISK_LOADED:
        return
    _DISK_LOADED.add(path)
    if not os.path.exists(path):
        return
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        log.warning("autotune: ignoring unreadable cache %s: %s", path, e)
        return
    for rec in raw.get("decisions", []):
        try:
            d = Decision(kernel=rec["kernel"],
                         shape_bucket=rec["shape_bucket"],
                         dtype=rec["dtype"],
                         device_kind=rec["device_kind"],
                         param=int(rec["param"]), source="disk",
                         candidates=tuple(rec.get("candidates", ())))
        except (KeyError, TypeError, ValueError):
            continue  # skip malformed rows, keep the rest
        if d.key not in _CACHE:  # in-process decisions win over disk
            _CACHE[d.key] = d
    log.debug("autotune: loaded %d decisions from %s", len(raw.get(
        "decisions", [])), path)


def _save_disk(path: str) -> None:
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(dict(decisions=[
                dataclasses.asdict(d) for d in _CACHE.values()
                if d.source in _MEASURED]), f, indent=2)
        os.replace(tmp, path)
    except OSError as e:
        log.warning("autotune: cannot write cache %s: %s", path, e)


def _tracing() -> bool:
    """Whether a ``jax.jit``/``vmap`` trace is being built: a new array is
    then a tracer, and ``block_until_ready`` on it returns at once — a
    timing taken now would measure tracing, not the device."""
    import jax.numpy as jnp
    return isinstance(jnp.zeros(()), jax.core.Tracer)


def decisions_log() -> list:
    """All decisions made (or loaded) this process, as plain dicts."""
    return [dataclasses.asdict(d) for d in _CACHE.values()]


# ---------------------------------------------------------------------------
# decide / kernel-facing lookups
# ---------------------------------------------------------------------------
def _microbench(cands: Tuple[int, ...],
                bench_fn: Callable[[int], float],
                iters: int = 3) -> Tuple[int, Dict[str, float]]:
    """Min-of-k interleaved timing of the candidate list.  ``bench_fn``
    runs one blocked dispatch with the given parameter and returns wall
    seconds (it must block until ready)."""
    for c in cands:          # warm every compile before timing any
        bench_fn(c)
    best: Dict[int, float] = {c: float("inf") for c in cands}
    for _ in range(iters):
        for c in cands:
            best[c] = min(best[c], bench_fn(c))
    winner = min(cands, key=lambda c: best[c])
    return winner, {str(c): best[c] * 1e6 for c in cands}


def decide(kernel: str, n: int, *, dtype: str = "float32",
           device_kind: Optional[str] = None,
           force: Optional[int] = None,
           bench_fn: Optional[Callable[[int], float]] = None,
           interpret: Optional[bool] = None,
           cache_path: Optional[str] = None) -> Decision:
    """Resolve the kernel's block parameter for an ``n``-row dispatch.

    Resolution order: ``force`` (no cache interaction, logged once) ->
    in-process cache -> on-disk cache -> microbenchmark (compiled
    backends with a ``bench_fn``, outside any trace) or the deterministic
    default candidate (interpret mode / no bench_fn / under a trace).
    Only microbenchmarked decisions are written to the disk cache.
    """
    kind = _canon_kind(device_kind)
    if force is not None:
        d = Decision(kernel=kernel, shape_bucket=shape_bucket(n),
                     dtype=dtype, device_kind=kind, param=int(force),
                     source="forced")
        if d.key + ("forced",) not in _LOGGED:
            _LOGGED.add(d.key + ("forced",))
            log.info("autotune: %s[%s,%s,%s] -> %d (forced)", kernel,
                     d.shape_bucket, dtype, kind, int(force))
        return d

    key = (kernel, shape_bucket(n), dtype, kind)
    path = _disk_path(cache_path)
    if key not in _CACHE and path:
        _load_disk(path)
    if key in _CACHE:
        return _CACHE[key]

    cands = candidates(kernel)
    interp = default_interpret() if interpret is None else interpret
    if interp or bench_fn is None or _tracing():
        d = Decision(kernel=kernel, shape_bucket=key[1], dtype=dtype,
                     device_kind=kind, param=cands[0],
                     source="interpret-default" if interp else "default",
                     candidates=cands)
        _record(d)
        return d
    winner, timings = _microbench(cands, bench_fn)
    d = Decision(kernel=kernel, shape_bucket=key[1], dtype=dtype,
                 device_kind=kind, param=winner, source="microbench",
                 candidates=cands, timings_us=timings)
    _record(d)
    if path:
        _save_disk(path)
    return d


def _default_bench(kernel: str, n: int) -> Optional[Callable[[int], float]]:
    """Self-contained microbenchmark thunk for a compiled backend: one
    synthetic blocked dispatch per candidate.  Returns None in interpret
    mode (decide() then takes the deterministic default)."""
    if default_interpret():
        return None
    import jax.numpy as jnp

    rows = max(-(-int(n) // 128) * 128, 128)
    if kernel == "segscan":
        from .segscan import kernel as K
        a = jnp.ones((rows, LANES), jnp.float32)
        f = jnp.zeros((rows, LANES), jnp.float32).at[0].set(1.0)

        def bench(c: int) -> float:
            rp = -(-rows // c) * c
            ap = jnp.pad(a, ((0, rp - rows), (0, 0)), constant_values=1.0)
            fp = jnp.pad(f, ((0, rp - rows), (0, 0)), constant_values=1.0)
            t0 = time.perf_counter()
            jax.block_until_ready(K.segscan_affine_pallas(
                fp, ap, ap, interpret=False, block_rows=c))
            return time.perf_counter() - t0
        return bench
    if kernel == "radix_partition":
        from .radix_partition import kernel as K
        keys = jnp.zeros((rows,), jnp.int32)

        def bench(c: int) -> float:
            rp = -(-rows // c) * c
            kp = jnp.pad(keys, (0, rp - rows))[None]
            t0 = time.perf_counter()
            jax.block_until_ready(K.radix_partition_pallas(
                kp, LANES, interpret=False, block_rows=c))
            return time.perf_counter() - t0
        return bench
    if kernel == "hash_probe":
        from .hash_probe import kernel as K
        lo = jnp.zeros((256, K.ASSOC), jnp.float32)
        q = jnp.zeros((rows,), jnp.int32)

        def bench(c: int) -> float:
            rp = -(-rows // c) * c
            qp = jnp.pad(q, (0, rp - rows))
            t0 = time.perf_counter()
            jax.block_until_ready(K.hash_probe_pallas(
                qp, lo, lo, interpret=False, block_q=c))
            return time.perf_counter() - t0
        return bench
    return None


def block_rows(kernel: str, n: int, *, force: Optional[int] = None,
               dtype: str = "float32") -> int:
    """The kernel-facing lookup: tuned block parameter for an ``n``-row
    dispatch (called by the ops wrappers at trace time — the result is a
    static argument of the inner ``pallas_call``)."""
    bench = None if _tracing() else _default_bench(kernel, n)
    return decide(kernel, n, dtype=dtype, force=force, bench_fn=bench).param


def main() -> None:  # pragma: no cover - CLI artifact helper
    import argparse
    ap = argparse.ArgumentParser(
        description="dump autotune decisions / device tables")
    ap.add_argument("--dump", default="", help="write decisions JSON here")
    args = ap.parse_args()
    out = dict(profile=device_profile(), decisions=decisions_log(),
               ladder_bounds=ladder_bounds(), mega_bounds=mega_bounds())
    text = json.dumps(out, indent=2)
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":  # pragma: no cover
    main()
