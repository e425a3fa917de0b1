"""The snapshots a run left in its checkpoint directory, read back.

The program publishes a snapshot as ``<dir>/step_<N>/`` holding a
``manifest.json`` and one ``.npy`` file per leaf, the manifest giving each
leaf's file, byte size and CRC-32 (``N`` is the number of punctuation
intervals committed before it).  This reader opens them with ``json``,
``zlib`` and ``numpy`` alone: a snapshot whose manifest is missing or
unreadable, or whose leaf file is missing or differs from its recorded
size or CRC-32, reads as ``None``.
"""
import json
import os
import re
import zlib

import numpy as np

STEP = re.compile(r"^step_(\d+)$")


def expected_steps(n_intervals: int, every: int):
    """The steps a run that committed ``n_intervals`` intervals publishes:
    every multiple of ``every`` up to and including the last boundary."""
    if not every:
        return []
    return list(range(every, n_intervals + 1, every))


def kept_steps(ckpt_dir: str):
    """Published steps on disk, ascending (``.tmp`` writer debris is not
    published)."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(int(m.group(1)) for m in map(STEP.match,
                                               os.listdir(ckpt_dir)) if m)


def _crc32(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            crc = zlib.crc32(block, crc)
    return crc


def read_values(ckpt_dir: str, step: int, leaf: str = "values"):
    """The ``leaf`` array of snapshot ``step``, or ``None`` where it is
    missing or damaged."""
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    try:
        with open(os.path.join(d, "manifest.json")) as f:
            entry = json.load(f)["leaves"][leaf]
        path = os.path.join(d, entry["file"])
        if (os.path.getsize(path) != entry["bytes"]
                or _crc32(path) != entry["crc32"]):
            return None
        return np.load(path)
    except (OSError, ValueError, KeyError, TypeError):
        return None
