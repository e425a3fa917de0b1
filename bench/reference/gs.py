"""Plain reference of Grep-and-Sum: the serial schedule in event order.

Each event, in timestamp order, either READs its ``txn_len`` distinct keys
and emits the sum of what it read, or PUTs its values into them.  Every
access succeeds.  The reference computes this a block of events at a
time: with the accesses of a block sorted by key (stable, so event order
is kept within a key), a read sees the newest PUT before it on its key,
or the table as it stood before the block.  The table before block ``b``
is the table before block ``b - 1`` with that block's newest PUT per key
applied.  Blocks are resolved in threads (numpy releases the interpreter
lock in the sorts and gathers), then chained in order.

It imports nothing of the program.  ``dtype`` is the precision the table
and the values are held in: float32 as the configuration states, or a
lower one for the control.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 19     # events per block


def _key_dtype(n_slots: int):
    return np.uint16 if n_slots <= np.iinfo(np.uint16).max else np.int64


def _resolve(events, b0, b1, dtype, kd):
    """Within-block resolution: for each access in key order, the value of
    the newest PUT before it in the block, if any."""
    keys = np.asarray(events["keys"][b0:b1])
    m = keys.shape[1]
    is_read = np.asarray(events["is_read"][b0:b1])
    flat_key = keys.reshape(-1).astype(kd)
    flat_val = np.asarray(events["values"][b0:b1]).reshape(-1).astype(dtype)
    order = np.argsort(flat_key, kind="stable")
    sk = flat_key[order]
    pos = np.arange(sk.size)
    first = np.ones(sk.size, bool)
    first[1:] = sk[1:] != sk[:-1]
    start = np.maximum.accumulate(np.where(first, pos, 0))
    is_write = np.repeat(~is_read, m)
    last_write = np.maximum.accumulate(np.where(is_write[order], pos, -1))
    seen = last_write >= start
    written = flat_val[order[np.maximum(last_write, 0)]]
    end = np.ones(sk.size, bool)
    end[:-1] = first[1:]
    put = end & seen
    return dict(order=order, sk=sk, seen=seen, written=written, m=m,
                is_read=is_read, put_keys=sk[put], put_vals=written[put])


def _finish(part, table, dtype):
    """Sums of the block's read events, given the table before it."""
    sk, order, m = part["sk"], part["order"], part["m"]
    read_sorted = np.where(part["seen"], part["written"], table[sk])
    read = np.empty(sk.size, dtype)
    read[order] = read_sorted
    read = read.reshape(-1, m)
    if dtype is np.float32:
        sums = read.astype(np.float64).sum(axis=1)
    else:
        sums = read.sum(axis=1, dtype=dtype).astype(np.float64)
    return np.where(part["is_read"], sums, 0.0)


def run(init, events, n_events: int, cfg, dtype=np.float32, at=()):
    """Returns ``(outputs, table, tables_at)``: ``outputs`` holds ``sum``
    (float64, the sum of the values a read event read, 0 for writes) and
    ``ok`` (bool) per event; ``table`` is the final table, and
    ``tables_at[c]`` the table after the first ``c`` events, for each
    ``c`` in ``at``."""
    table = np.asarray(init).astype(dtype)[:, 0].copy()
    kd = _key_dtype(table.size)
    cuts = sorted({0, n_events, *range(0, n_events, BLOCK),
                   *(int(c) for c in at if 0 < c < n_events)})
    bounds = list(zip(cuts[:-1], cuts[1:]))
    workers = max(1, min(8, os.cpu_count() or 1, len(bounds)))
    tables_at = {int(c): table.copy() for c in at if c <= 0}
    with ThreadPoolExecutor(workers) as ex:
        parts = list(ex.map(lambda b: _resolve(events, *b, dtype, kd),
                            bounds))
        before = []
        for (_, b1), p in zip(bounds, parts):
            before.append(table.copy())
            table[p["put_keys"]] = p["put_vals"]
            if b1 in at:
                tables_at[b1] = table.copy()
        sums = list(ex.map(lambda pt: _finish(*pt, dtype),
                           zip(parts, before)))
    out_sum = np.concatenate(sums) if sums else np.zeros(0)
    return dict(sum=out_sum, ok=np.ones(n_events, bool)), table, tables_at
