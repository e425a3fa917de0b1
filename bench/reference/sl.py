"""Plain reference of Streaming Ledger with abort repass, interval by
interval.

A deposit ADDs ``amount`` to its source account and source asset.  A
transfer TAKEs ``amount`` from the source account and from the source
asset, each only if the balance covers it, and ADDs it to the destination
account (destination asset) only if the debit of the source account
(source asset) succeeded.  The transaction commits when both debits
succeed.

Abort repass, the semantics the program states for ``abort_repass``:
each punctuation interval runs serially in event order once; every
transaction that failed an access is then masked, and the interval runs
serially again from the table as it stood before the interval, without
the masked transactions.  The outputs are those of the second pass: a
masked transaction reports ``ok`` false and a source balance of 0.

It imports nothing of the program.  Balances are held in float32 by
storing them in an ``array('f')``: a sum or difference of two float32
values computed in float64 and rounded once to float32 is the float32
result.  The control passes ``dtype`` bfloat16 instead.
"""
from array import array

import numpy as np


class _Rounded(list):
    """A list of balances that rounds every stored value to ``dtype``."""

    def __init__(self, values, dtype):
        self.dtype = dtype
        super().__init__(float(dtype(v)) for v in values)

    def __setitem__(self, i, v):
        super().__setitem__(i, float(self.dtype(v)))

    def copy(self):
        out = _Rounded((), self.dtype)
        out.extend(self)
        return out


def _table(values, dtype):
    if dtype is np.float32:
        return array("f", values)
    return _Rounded(values, dtype)


def _pass(tab, ev, active):
    """One serial pass over an interval.  Returns ``(ok, src_balance)``."""
    n = len(ev[0])
    ok = [False] * n
    bal = [0.0] * n
    for e, (sa, sb, da, db, amt, tr) in enumerate(zip(*ev)):
        if not active[e]:
            continue
        a = tab[sa]
        if tr:
            ok_a = a >= amt
            if ok_a:
                tab[sa] = a - amt
            b = tab[sb]
            ok_b = b >= amt
            if ok_b:
                tab[sb] = b - amt
            if ok_a:
                tab[da] = tab[da] + amt
            if ok_b:
                tab[db] = tab[db] + amt
            ok[e] = ok_a and ok_b
        else:
            tab[sa] = a + amt
            tab[sb] = tab[sb] + amt
            ok[e] = True
        bal[e] = tab[sa]
    return ok, bal


def run(init, events, n_events: int, cfg, dtype=np.float32, at=()):
    """Returns ``(outputs, table, tables_at)``: ``ok``, ``src_balance``
    and ``rejected`` per event, the final table, and ``tables_at[c]`` the
    table after the first ``c`` events, for each ``c`` in ``at`` (a
    multiple of the interval)."""
    interval, n_acct = cfg["punct_interval"], cfg["tables"][0]
    init = np.asarray(init)[:, 0]
    tab = _table(init.astype(np.float32).tolist(), dtype)
    amount = np.asarray(events["amount"][:n_events])
    if dtype is not np.float32:
        amount = amount.astype(dtype)
    cols = [np.asarray(events["src_acct"][:n_events]),
            np.asarray(events["src_asset"][:n_events]) + n_acct,
            np.asarray(events["dst_acct"][:n_events]),
            np.asarray(events["dst_asset"][:n_events]) + n_acct]
    cols = [c.astype(np.int64).tolist() for c in cols]
    cols.append(amount.astype(np.float64).tolist())
    cols.append(np.asarray(events["is_transfer"][:n_events]).tolist())
    ok_all, bal_all = [], []
    tables_at = {}

    def table_now():
        return np.asarray(tab, np.float64).astype(
            np.float32 if dtype is np.float32 else dtype)
    for i0 in range(0, n_events, interval):
        if i0 in at:
            tables_at[i0] = table_now()
        ev = [c[i0:i0 + interval] for c in cols]
        before = tab[:] if isinstance(tab, array) else tab.copy()
        ok1, _ = _pass(tab, ev, [True] * len(ev[0]))
        tab = before
        ok, bal = _pass(tab, ev, ok1)
        ok_all.extend(ok)
        bal_all.extend(bal)
    ok = np.asarray(ok_all, bool)
    is_tr = np.asarray(events["is_transfer"][:n_events], bool)
    table = table_now()
    if n_events in at:
        tables_at[n_events] = table
    return dict(ok=ok, src_balance=np.asarray(bal_all, np.float64),
                rejected=is_tr & ~ok), table, tables_at
