"""Streaming Ledger traffic (arXiv:1904.03800 §VI-A/B), one block at a time.

An event is a deposit (top up a source account and a source asset) or,
with probability ``transfer_ratio``, a transfer of ``amount`` from the
source account and asset to a destination account and asset.  Source and
destination are distinct Zipf keys of each table.  Columns are those the
program's SL app consumes.
"""
import numpy as np

from zipf import KeySampler


def initial_table(rng, cfg) -> np.ndarray:
    """float32[n_accounts + n_assets + 1, 1]: balances before the first
    event; the last row is the padding slot the program keeps at 0."""
    n = sum(cfg["tables"])
    init = np.zeros((n + 1, 1), np.float32)
    init[:n, 0] = rng.uniform(50.0, 500.0, n)
    return init


class Sampler:
    def __init__(self, cfg):
        self.cfg = cfg
        n_acct, n_asset = cfg["tables"]
        align = cfg.get("align_mod", 0)
        self.acct = KeySampler(n_acct, cfg["theta"], align_mod=align)
        self.asset = KeySampler(n_asset, cfg["theta"], align_mod=align)

    def events(self, rng, n: int) -> dict:
        acct = self.acct.draw(rng, n, 2)
        asset = self.asset.draw(rng, n, 2)
        return dict(
            src_acct=acct[:, 0], dst_acct=acct[:, 1],
            src_asset=asset[:, 0], dst_asset=asset[:, 1],
            amount=rng.uniform(1.0, 50.0, n).astype(np.float32),
            is_transfer=rng.random(n) < self.cfg["transfer_ratio"],
        )
