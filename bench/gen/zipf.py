"""Vectorised Zipf key draws shared by the traffic generators.

The same distributions as the program's ``apps/common.py`` generators,
drawn for a whole block of events at once:

* ``P(k) ∝ 1/(k+1)^theta`` over ``n_keys`` keys, through Walker's alias
  table (exact, and a constant number of array passes per draw);
* keys distinct within a transaction by successive sampling: slot ``j``
  is redrawn while it equals a key of an earlier slot of its row, which
  is the distribution of ``rng.choice(..., replace=False, p=p)`` and of
  the per-key rejection loop of ``sample_multipartition_keys``;
* the multi-partition mix: ``mp_ratio`` of the transactions span exactly
  ``mp_len`` distinct partitions (``key % n_partitions``), chosen
  uniformly; slot ``j`` draws from partition ``parts[j % span]`` with the
  Zipf probabilities restricted to that partition;
* aligned skew (``align_mod`` > 1, without partitions): every key passes
  through the bijection ``k -> align_mod * (k % K) + k // K`` with
  ``K = n_keys / align_mod``, so the Zipf head lands on one residue class
  mod ``align_mod``, as ``apps/common.align_keys`` maps it.
"""
import numpy as np


def zipf_weights(n_keys: int, theta: float) -> np.ndarray:
    return 1.0 / np.power(np.arange(1, n_keys + 1, dtype=np.float64), theta)


class Alias:
    """Walker's alias table of a discrete distribution (Vose's build)."""

    def __init__(self, weights):
        w = np.asarray(weights, np.float64)
        n = w.size
        scaled = w * (n / w.sum())
        prob = np.ones(n)
        alias = np.arange(n)
        small = [i for i in range(n) if scaled[i] < 1.0]
        large = [i for i in range(n) if scaled[i] >= 1.0]
        while small and large:
            s, g = small.pop(), large.pop()
            prob[s], alias[s] = scaled[s], g
            scaled[g] -= 1.0 - scaled[s]
            (small if scaled[g] < 1.0 else large).append(g)
        self.prob, self.alias, self.n = prob, alias, n

    def draw(self, rng, size: int) -> np.ndarray:
        u = rng.random(size) * self.n
        i = u.astype(np.int64)
        np.minimum(i, self.n - 1, out=i)
        return np.where(u - i < self.prob[i], i, self.alias[i])


class KeySampler:
    """Rows of ``m`` distinct Zipf keys; built once, drawn per block."""

    def __init__(self, n_keys: int, theta: float, n_partitions: int = 0,
                 mp_ratio: float = 0.0, mp_len: int = 1,
                 align_mod: int = 0):
        p = zipf_weights(n_keys, theta)
        self.n_partitions = int(n_partitions)
        self.n_keys, self.align_mod = int(n_keys), int(align_mod)
        if self.align_mod > 1 and (self.n_partitions
                                   or n_keys % self.align_mod):
            raise ValueError("align_mod needs no partitions and n_keys a "
                             "multiple of it")
        self.mp_ratio, self.mp_len = float(mp_ratio), int(mp_len)
        if not self.n_partitions:
            self.table = Alias(p)
            return
        if n_keys % self.n_partitions:
            raise ValueError("n_keys must be a multiple of n_partitions")
        parts = [Alias(p[q::self.n_partitions])
                 for q in range(self.n_partitions)]
        # one [n_partitions, n_keys / n_partitions] table: a slot's draw
        # indexes the row of its partition
        self.prob = np.stack([t.prob for t in parts])
        self.alias = np.stack([t.alias for t in parts])
        self.per_part = n_keys // self.n_partitions

    def _partitions(self, rng, n: int, m: int) -> np.ndarray:
        """int[n, m]: the partition each slot draws from."""
        np_ = self.n_partitions
        span = np.where(rng.random(n) < self.mp_ratio,
                        min(self.mp_len, np_, m), 1)
        # a uniform random ordering of the partitions per row; the first
        # ``span`` of them are the row's partitions
        perm = np.argsort(rng.random((n, np_)), axis=1)
        return perm[np.arange(n)[:, None],
                    np.arange(m)[None, :] % span[:, None]]

    def draw(self, rng, n: int, m: int) -> np.ndarray:
        """int32[n, m] keys, distinct within each row."""
        if not self.n_partitions:
            table = self.table
            draw = lambda rows, _j: table.draw(rng, rows.size)
        else:
            part = self._partitions(rng, n, m)

            def draw(rows, j):
                q = part[rows, j]
                u = rng.random(rows.size) * self.per_part
                i = u.astype(np.int64)
                np.minimum(i, self.per_part - 1, out=i)
                k = np.where(u - i < self.prob[q, i], i, self.alias[q, i])
                return q + self.n_partitions * k

        keys = np.empty((n, m), np.int32)
        every = np.arange(n)
        for j in range(m):
            keys[:, j] = draw(every, j)
            if not j:
                continue
            rows = np.flatnonzero(
                (keys[:, :j] == keys[:, j:j + 1]).any(axis=1))
            while rows.size:
                keys[rows, j] = draw(rows, j)
                hit = (keys[rows, :j] == keys[rows, j:j + 1]).any(axis=1)
                rows = rows[hit]
        if self.align_mod > 1:
            per = self.n_keys // self.align_mod
            keys = self.align_mod * (keys % per) + keys // per
        return keys
