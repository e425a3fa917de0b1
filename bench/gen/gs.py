"""Grep-and-Sum traffic (arXiv:1904.03800 §VI-A/B), one block at a time.

An event is one transaction of ``txn_len`` accesses on distinct keys of
one table: a read event READs them and sums what it read, a write event
PUTs its ``values`` into them.  Columns are those the program's GS app
consumes: ``keys`` int32[n, txn_len], ``is_read`` bool[n], ``values``
float32[n, txn_len].
"""
import numpy as np

from zipf import KeySampler


def initial_table(rng, cfg) -> np.ndarray:
    """float32[n_keys + 1, 1]: the table before the first event; the last
    row is the padding slot the program keeps at 0."""
    n = cfg["tables"][0]
    init = np.zeros((n + 1, 1), np.float32)
    init[:n, 0] = rng.uniform(1.0, 100.0, n)
    return init


class Sampler:
    def __init__(self, cfg):
        self.cfg = cfg
        self.keys = KeySampler(cfg["tables"][0], cfg["theta"],
                               cfg.get("n_partitions", 0),
                               cfg.get("mp_ratio", 0.0),
                               cfg.get("mp_len", 1),
                               cfg.get("align_mod", 0))

    def events(self, rng, n: int) -> dict:
        m = self.cfg["txn_len"]
        return dict(
            keys=self.keys.draw(rng, n, m),
            is_read=rng.random(n) < self.cfg["read_ratio"],
            values=rng.uniform(1.0, 100.0, (n, m)).astype(np.float32),
        )
