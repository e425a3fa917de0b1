"""The benchmark's traffic generators draw the program's distributions.

Each statistic of ``bench/gen`` is compared with the same statistic of
the program's own per-event generators (``repro.apps``) at a small size,
within a tolerance of several standard errors of the sample.
"""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(BENCH, "gen"))

import gs as gen_gs  # noqa: E402
import sl as gen_sl  # noqa: E402
from zipf import Alias, KeySampler, zipf_weights  # noqa: E402

from repro.apps import gs as app_gs  # noqa: E402
from repro.apps import sl as app_sl  # noqa: E402
from repro.apps.common import zipf_probs  # noqa: E402

N = 4000
GS_CFG = dict(tables=[10000], txn_len=10, theta=0.6, read_ratio=0.5)
MP_CFG = dict(GS_CFG, n_partitions=4, mp_ratio=0.5, mp_len=4)
SL_CFG = dict(tables=[10000, 10000], theta=0.6, transfer_ratio=0.5)


def head_share(keys, n=100):
    return float(np.mean(np.asarray(keys) < n))


def spans(keys, n_part=4):
    return np.bincount([len(set(r % n_part)) for r in np.asarray(keys)],
                       minlength=n_part + 1) / len(keys)


@pytest.mark.parametrize("theta", [0.0, 0.6, 1.2])
def test_alias_matches_zipf(theta):
    rng = np.random.default_rng(0)
    x = Alias(zipf_weights(1000, theta)).draw(rng, 400_000)
    freq = np.bincount(x, minlength=1000) / x.size
    p = zipf_probs(1000, theta)
    se = np.sqrt(p * (1 - p) / x.size)
    assert np.all(np.abs(freq - p) < 6 * se + 1e-9)


@pytest.mark.parametrize("cfg", [GS_CFG, MP_CFG], ids=["gs", "gs_mp"])
def test_gs_statistics_match_the_app(cfg):
    ours = gen_gs.Sampler(cfg).events(np.random.default_rng(1), N)
    kw = {k: cfg[k] for k in ("n_partitions", "mp_ratio", "mp_len")
          if k in cfg}
    theirs = app_gs.gen_events(np.random.default_rng(2), N,
                               theta=cfg["theta"],
                               read_ratio=cfg["read_ratio"], **kw)
    k, t = ours["keys"], theirs["keys"]
    assert k.shape == t.shape and k.dtype == np.int32
    assert all(len(set(r)) == cfg["txn_len"] for r in k)
    assert k.min() >= 0 and k.max() < cfg["tables"][0]
    se = np.sqrt(0.25 / k.size)
    assert abs(head_share(k) - head_share(t)) < 8 * se
    assert abs(ours["is_read"].mean() - theirs["is_read"].mean()) < 0.05
    assert ours["values"].dtype == np.float32
    assert 1.0 <= ours["values"].min() and ours["values"].max() < 100.0
    if "n_partitions" in cfg:
        s_ours, s_theirs = spans(k), spans(t)
        assert np.all(np.abs(s_ours - s_theirs) < 0.05)
        assert s_ours[1] + s_ours[4] == pytest.approx(1.0)


def test_sl_statistics_match_the_app():
    ours = gen_sl.Sampler(SL_CFG).events(np.random.default_rng(3), N)
    theirs = app_sl.gen_events(np.random.default_rng(4), N, theta=0.6,
                               transfer_ratio=0.5)
    assert set(ours) == set(theirs)
    assert np.all(ours["src_acct"] != ours["dst_acct"])
    assert np.all(ours["src_asset"] != ours["dst_asset"])
    for col in ("src_acct", "dst_acct", "src_asset", "dst_asset"):
        assert abs(head_share(ours[col]) - head_share(theirs[col])) < 0.05
    assert abs(ours["is_transfer"].mean()
               - theirs["is_transfer"].mean()) < 0.05
    assert ours["amount"].dtype == np.float32


def test_blocks_are_a_function_of_the_seed():
    from benchlib.traffic import STREAM_EVENTS, block_rng
    s = gen_gs.Sampler(GS_CFG)
    big = 2 ** 31 + 12345
    a = s.events(block_rng(big, STREAM_EVENTS, 7), 256)
    b = s.events(block_rng(big, STREAM_EVENTS, 7), 256)
    c = s.events(block_rng(big, STREAM_EVENTS, 8), 256)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["keys"], c["keys"])


def test_key_sampler_rejects_uneven_partitions():
    with pytest.raises(ValueError):
        KeySampler(10, 0.6, n_partitions=4)


def test_aligned_skew_matches_the_app():
    """``align_mod``: the Zipf head on one residue class, as the app's
    ``align_keys`` puts it."""
    from repro.apps.common import sample_keys
    rng = np.random.default_rng(5)
    ours = KeySampler(10000, 1.2, align_mod=4).draw(rng, N, 10)
    theirs = sample_keys(np.random.default_rng(6), N, 10, 10000, 1.2,
                         align_mod=4)
    assert all(len(set(r)) == 10 for r in ours)
    share = lambda k: np.bincount(np.asarray(k).reshape(-1) % 4,
                                  minlength=4) / np.asarray(k).size
    assert np.all(np.abs(share(ours) - share(theirs)) < 0.03)
    assert share(ours)[0] > 0.4
    with pytest.raises(ValueError):
        KeySampler(10000, 0.6, n_partitions=4, align_mod=4)


def test_jittered_times_are_a_bounded_permutation():
    from benchlib.traffic import block_times
    t = block_times(2 ** 31 + 3, 5, 4096, 64)
    assert np.array_equal(np.sort(t), np.arange(5 * 4096, 6 * 4096))
    disp = np.abs(t - np.arange(5 * 4096, 6 * 4096))
    assert 0 < disp.max() < 64
    assert np.array_equal(block_times(1, 2, 100, 0), np.arange(200, 300))


def test_phases_set_the_arrival_rate():
    from benchlib.traffic import due_times, phases_of
    ph = phases_of({"phases": [{"rate_per_s": 4000, "seconds": 0.5},
                               {"rate_per_s": 500, "seconds": 1.5}]})
    due = due_times(np.random.default_rng(9), ph, 40000)
    assert np.all(np.diff(due) >= 0)
    cyc = np.mod(due[due < 16.0], 2.0)          # 8 whole cycles
    on, off = np.sum(cyc < 0.5) / 8, np.sum(cyc >= 0.5) / 8
    assert on == pytest.approx(2000, rel=0.05)
    assert off == pytest.approx(750, rel=0.1)
    flat = due_times(np.random.default_rng(9), phases_of(
        {"rate_per_s": 1000}), 20000)
    assert flat[-1] == pytest.approx(20.0, rel=0.03)
    with pytest.raises(ValueError):
        phases_of({"phases": [{"rate_per_s": 0, "seconds": 1}]})
