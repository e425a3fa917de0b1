"""The load generator and the source object the service pulls from.

The generator runs in a thread of its own, apart from the service, and
hands the service arrival batches of ``arrival_batch`` events through
:class:`Traffic`, which ``StreamService.run`` iterates.  The event at
arrival position ``i`` carries event time ``i``, or with ``jitter`` J > 0
a time displaced from ``i`` by at most J within its block (each event's
sort key ``i + U[0, J)``, ranked), so it arrives out of order.  Payloads
are drawn by the cell's ``bench/gen/<app>.py`` sampler, in blocks whose
random streams are pure functions of ``(seed, block index)``, so the same
seed gives the same events however fast they are consumed.

Two modes, set by the traffic file:

* ``backlog``: every event is due at the window's start.  The thread
  keeps a bounded queue of blocks ahead of the service, filled before the
  window opens, so the source does not run dry.
* ``open``: Poisson arrivals at ``rate_per_s``, or through ``phases``, a
  list of ``{"rate_per_s", "seconds"}`` repeated from the window's start
  (on/off bursts).  The whole stream for the window is drawn in set-up,
  and the thread releases each batch once its last event is due, stamping
  how late it released it.  Latency is taken from each event's due time,
  so a wait behind backpressure counts.

The stream ends at a multiple of ``stop_multiple`` (one chunk of
punctuation intervals), so every interval the service cuts is full and
every chunk has the shape that set-up compiled: in backlog mode at the
first such multiple after the window closes, in open mode at the first
one that covers every event due inside the window.
"""
import math
import queue
import threading
import time

import numpy as np

STREAM_EVENTS, STREAM_ARRIVALS, STREAM_TIMES = 1, 2, 5


def block_rng(seed: int, stream: int, index: int):
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), stream, int(index)]))


def phases_of(traffic: dict):
    """``[(rate_per_s, seconds), ...]`` of an open-loop traffic file; one
    phase of unbounded length for a constant rate."""
    if "phases" in traffic:
        ph = [(float(p["rate_per_s"]), float(p["seconds"]))
              for p in traffic["phases"]]
    else:
        ph = [(float(traffic["rate_per_s"]), float("inf"))]
    if not ph or any(r <= 0 or s <= 0 for r, s in ph):
        raise ValueError(f"phases need positive rates and lengths: {ph}")
    return ph


def due_times(rng, phases, n: int) -> np.ndarray:
    """Due offsets (s) of ``n`` Poisson arrivals whose rate follows
    ``phases``, repeated: unit-rate arrival times mapped through the
    inverse of the cumulative rate."""
    u = np.cumsum(rng.exponential(1.0, n))
    if len(phases) == 1:
        return u / phases[0][0]
    rates = np.array([r for r, _ in phases])
    lens = np.array([s for _, s in phases])
    t_at = np.concatenate([[0.0], np.cumsum(lens)])
    lam_at = np.concatenate([[0.0], np.cumsum(rates * lens)])
    cycle, rem = np.divmod(u, lam_at[-1])
    return cycle * t_at[-1] + np.interp(rem, lam_at, t_at)


def block_times(seed: int, block: int, size: int, jitter: int):
    """Event times of the ``size`` arrivals of block ``block``."""
    lo = block * size
    if not jitter:
        return np.arange(lo, lo + size, dtype=np.int64)
    key = np.arange(size) + block_rng(seed, STREAM_TIMES, block).uniform(
        0.0, jitter, size)
    return lo + np.argsort(np.argsort(key, kind="stable"))


class Traffic:
    def __init__(self, sampler, traffic: dict, seed: int, *,
                 stop_multiple: int, seconds: float):
        self.sampler = sampler
        self.mode = traffic["mode"]
        if self.mode not in ("backlog", "open"):
            raise ValueError(f"traffic mode {self.mode!r}")
        self.batch = int(traffic.get("arrival_batch", 64))
        self.block = int(traffic.get("block_events", 1 << 15))
        self.jitter = int(traffic.get("jitter", 0))
        self.seed = int(seed)
        self.stop_multiple = int(stop_multiple)
        self.seconds = float(seconds)
        self.blocks = []            # generated blocks, in stream order
        self.generated = 0
        self._q = queue.Queue(maxsize=int(traffic.get("queue_blocks", 32)))
        self._stop = threading.Event()
        self._thread = None
        self._cur = None            # (lo, hi) range being handed out
        self.handed = 0
        self.batch_ends = []        # stream position after each batch
        self.stop_at = None
        self.t0 = self.t1 = None
        self.released = 0
        self.released_at_close = self.handed_at_close = None
        self.ran_dry = 0            # pulls that found nothing (backlog)
        self.lag_s = []             # due, release - due, per batch (open)
        self.due_s = None           # due offsets of every event (open)
        if self.mode == "open":
            phases = phases_of(traffic)
            n = int(math.ceil(max(r for r, _ in phases)
                              * (self.seconds + 1.0)))
            n += -n % self.block
            self.due_s = due_times(block_rng(self.seed, STREAM_ARRIVALS, 0),
                                   phases, n)
            while self.generated < n:
                self._make_block()

    # -- generation ------------------------------------------------------
    def _make_block(self):
        b = len(self.blocks)
        ev = self.sampler.events(block_rng(self.seed, STREAM_EVENTS, b),
                                 self.block)
        ev["_time"] = block_times(self.seed, b, self.block, self.jitter)
        self.blocks.append(ev)
        lo = self.generated
        self.generated += self.block
        return lo, self.generated

    def prefill(self):
        """Set-up: start the thread; in backlog mode wait until its queue
        is full, so the window opens on a full backlog."""
        if self.mode == "backlog":
            self._thread = threading.Thread(target=self._backlog_loop,
                                             name="bench-generator",
                                             daemon=True)
            self._thread.start()
            while not self._q.full() and self._thread.is_alive():
                time.sleep(0.01)

    def _backlog_loop(self):
        while not self._stop.is_set():
            rng_range = self._make_block()
            while not self._stop.is_set():
                try:
                    self._q.put(rng_range, timeout=0.05)
                    self.released = rng_range[1]
                    break
                except queue.Full:
                    continue

    def _open_loop(self):
        due, batch = self.due_s, self.batch
        last = due[batch - 1::batch]          # due offset of each batch
        nb = last.size
        i = 0
        while i < nb and not self._stop.is_set():
            now = time.perf_counter() - self.t0
            if last[i] > now:
                time.sleep(min(last[i] - now, 0.002))
                continue
            j = int(np.searchsorted(last, now, side="right"))
            for k in range(i, j):
                self.lag_s += (float(last[k]), now - float(last[k]))
            self._q.put((i * batch, j * batch))
            self.released = j * batch
            i = j

    # -- the window ------------------------------------------------------
    def open_window(self, t0: float):
        """Start the clock: events are due from ``t0`` on."""
        self.t0, self.t1 = t0, t0 + self.seconds
        if self.mode == "open":
            # every event due inside the window is served, however late
            n_due = int(np.searchsorted(self.due_s, self.seconds, "right"))
            m = self.stop_multiple
            self.stop_at = -(-n_due // m) * m
            self._q = queue.Queue()
            self._thread = threading.Thread(target=self._open_loop,
                                            name="bench-generator",
                                            daemon=True)
            self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self.handed_at_close is None and time.perf_counter() >= self.t1:
            self.released_at_close = self.released
            self.handed_at_close = self.handed
            if self.stop_at is None:
                m = self.stop_multiple
                self.stop_at = -(-self.handed // m) * m
        if self.stop_at is not None and self.handed >= self.stop_at:
            raise StopIteration
        while self._cur is None or self._cur[0] >= self._cur[1]:
            try:
                self._cur = self._q.get_nowait()
            except queue.Empty:
                if self.mode == "backlog" and self.stop_at is None:
                    self.ran_dry += 1
                self._cur = self._q.get()
        lo, hi = self._cur
        n = min(self.batch, hi - lo)
        if self.stop_at is not None:
            n = min(n, self.stop_at - self.handed)
        assert lo == self.handed, (lo, self.handed)
        self._cur = (lo + n, hi)
        self.handed += n
        self.batch_ends.append(self.handed)
        ev = self._slice(lo, lo + n)
        return {k: v for k, v in ev.items() if k != "_time"}, ev["_time"]

    def _slice(self, lo: int, hi: int) -> dict:
        b, off = divmod(lo, self.block)
        blk = self.blocks[b]
        if off + (hi - lo) <= self.block:
            return {k: v[off:off + hi - lo] for k, v in blk.items()}
        return self.events(lo, hi)

    def events(self, lo: int, hi: int) -> dict:
        """Events ``[lo, hi)`` of the stream in arrival order, as
        generated, with their event times under ``_time``."""
        b0, b1 = lo // self.block, -(-hi // self.block)
        cat = {k: np.concatenate([self.blocks[b][k] for b in range(b0, b1)])
               for k in self.blocks[b0]}
        off = b0 * self.block
        return {k: v[lo - off:hi - off] for k, v in cat.items()}

    def close(self):
        """Stop the thread and wait for it."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            if self._thread.is_alive():
                raise RuntimeError("the generator thread did not stop")
