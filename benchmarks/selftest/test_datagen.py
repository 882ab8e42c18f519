"""The key chooser against YCSB's constant, the hash against a plain loop,
the op-weighted percentile, the roofline's bytes on a known job."""

import numpy as np
import pytest

from benchmarks import datagen, roofline
from benchmarks.drivers.ycsb import weighted_percentile


def plain_fnv(val: int) -> int:
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    if h >= 1 << 63:
        h -= 1 << 64
    return abs(h)


def test_fnv_is_ycsbs():
    vals = [0, 1, 255, 256, 99_999, 2**31 + 5, 2**40 + 12345]
    assert datagen.fnv1a64(np.asarray(vals)).tolist() == \
        [plain_fnv(v) for v in vals]


def test_zipfian_shares_match_the_constant():
    n, theta = 100_000, 0.99
    z = datagen.ScrambledZipfian(n, theta)
    zeta = sum(1.0 / i ** theta for i in range(1, n + 1))
    assert z.zeta == pytest.approx(zeta)
    draws = 2_000_000
    ranks = z.ranks(datagen.rng_for(7, 0), draws)
    hottest = np.mean(ranks == 0)
    assert hottest == pytest.approx(1.0 / zeta, rel=0.02)        # ~7.8%
    top1pct = np.mean(ranks < n // 100)
    want = sum(1.0 / i ** theta for i in range(1, n // 100 + 1)) / zeta
    assert top1pct == pytest.approx(want, rel=0.01)              # ~62%
    # scattered: the hottest record is fnv(0) % n, not record 0
    recs = z.draw(datagen.rng_for(7, 0), draws)
    assert np.bincount(recs, minlength=n).argmax() == plain_fnv(0) % n


def test_same_seed_same_draws_and_large_seeds():
    z = datagen.ScrambledZipfian(1000, 0.99)
    big = 2**31 + 12345
    a = z.draw(datagen.rng_for(big, 3), 100)
    assert (a == z.draw(datagen.rng_for(big, 3), 100)).all()
    assert (a != z.draw(datagen.rng_for(big + 1, 3), 100)).any()


def test_weighted_percentile_is_over_operations():
    # 90 ops saw 10 ms, 10 ops saw 100 ms: p50 = 10, p95 = 100, p90 = 10
    samples = [(100.0, 10), (10.0, 90)]
    assert weighted_percentile(samples, 50) == 10.0
    assert weighted_percentile(samples, 90) == 10.0
    assert weighted_percentile(samples, 95) == 100.0
    # a batch counts as many times as it has operations
    assert weighted_percentile([(1.0, 1), (2.0, 1), (50.0, 98)], 5) == 50.0
    assert weighted_percentile([], 95) is None


def test_compaction_bytes_of_a_known_job():
    # 1,000 rows in, 600 out, 26-byte keys (7 words = 28 B + 36 B of
    # columns = 64 B a row), 40,000 value bytes in and 24,000 out
    assert roofline.slab_row_bytes(26) == 64
    assert roofline.compaction_job_bytes(1000, 600, 26, 40_000, 24_000) == \
        1000 * 64 + 40_000 + 600 * 64 + 24_000 + 1000 * 4
    assert roofline.bytes_bound_s(819_000_000, "TPU v5 lite") == \
        pytest.approx(1e-3)
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
