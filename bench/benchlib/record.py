"""What one run leaves for the metric readers under ``bench/metrics/``.

Every time is in seconds on the host's monotonic clock
(``time.perf_counter``); the window is ``[t0, t1)``.
"""
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class RunRecord:
    cell: str
    cfg: Dict
    traffic: Dict
    chips: int
    seconds: float
    t0: float
    t1: float
    setup_s: float
    committed: int                  # events committed inside the window
    interval: int
    latency_s: Optional[np.ndarray] = None   # due -> commit (open loop)
    gen_lag_s: Optional[np.ndarray] = None   # release - due per batch
    spans: Optional[List[Tuple[str, str, float, float]]] = None
    device: Any = None              # devtrace.DeviceTrace (traced run)
    peaks: Optional[Dict] = None    # the device's row of bench/peaks.json
    work: Any = None                # bench/work/<app>.py

    def span_seconds(self, names, thread: str) -> float:
        """Summed durations of the spans called ``names`` on ``thread``
        that start inside the window."""
        names = set(names)
        return sum(e - s for n, th, s, e in self.spans
                   if n in names and th == thread and self.t0 <= s < self.t1)

    def span_durations(self, name: str) -> np.ndarray:
        return np.asarray([e - s for n, _, s, e in self.spans
                           if n == name and self.t0 <= s < self.t1])

    def per_event_us(self, seconds: float) -> Optional[float]:
        if not self.committed:
            return None
        return seconds / self.committed * 1e6
