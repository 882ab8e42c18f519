"""ValueArray.gather: the native row copy (native/compaction_engine.cc
ce_gather_rows) against the numpy fallback it replaces on the fast path
(ValueArray._gather_numpy, called directly as the oracle)."""

import numpy as np
import pytest

from yugabyte_tpu.ops.slabs import ValueArray, gather_metrics
from yugabyte_tpu.storage import native_engine

needs_native = pytest.mark.requires_native("compaction_engine")

TOMB = b"\x58\x01\x02"   # any multi-byte replacement


def _array(rng, lens, read_only=False):
    lens = np.asarray(lens, dtype=np.int64)
    offs = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    data = rng.integers(0, 256, size=int(offs[-1]), dtype=np.uint8)
    if read_only:
        data = np.frombuffer(data.tobytes(), dtype=np.uint8)
        assert not data.flags.writeable
    return ValueArray(data, offs)


def _cell_lens(rng, n):
    """The kv64 cell's mix: 5% tombstones, 25% INT64 columns, 70% ~44-byte
    strings."""
    kind = rng.random(n)
    return np.where(kind < 0.05, 1,
                    np.where(kind < 0.30, 9, rng.integers(40, 49, size=n)))


def _case(name, rng):
    """-> (ValueArray, idx, replace_mask)"""
    n = 5000
    if name == "cell_mix":
        va = _array(rng, _cell_lens(rng, n))
        return va, np.sort(rng.choice(n, 2300, replace=False)), None
    if name == "uniform_stride":
        return _array(rng, np.full(n, 16)), rng.permutation(n)[:1700], None
    if name == "zero_length_rows":
        va = _array(rng, rng.choice([0, 0, 3, 44], size=n))
        return va, rng.integers(0, n, size=3000), None
    if name == "all_rows_zero_length":
        return _array(rng, np.zeros(n)), rng.integers(0, n, size=100), None
    if name == "empty_idx":
        return _array(rng, _cell_lens(rng, n)), np.zeros(0, np.int64), None
    if name == "empty_array_empty_idx":
        return ValueArray.empty_rows(0), np.zeros(0, np.int32), None
    if name == "repeated_indices":
        return (_array(rng, _cell_lens(rng, n)),
                np.repeat(rng.integers(0, n, size=200), 7), None)
    if name == "descending_indices":
        return (_array(rng, _cell_lens(rng, n)),
                np.arange(n - 1, -1, -1, dtype=np.int32), None)
    if name == "non_contiguous_idx_slice":
        idx = rng.integers(0, n, size=4000).astype(np.int64)[::3]
        assert not idx.flags.c_contiguous
        return _array(rng, _cell_lens(rng, n)), idx, None
    if name == "read_only_frombuffer_source":
        va = _array(rng, _cell_lens(rng, n), read_only=True)
        return va, rng.integers(0, n, size=2000), None
    if name == "sliced_rows_source":
        # slice_rows: offsets rebased, data a view into a larger blob
        va = _array(rng, _cell_lens(rng, n)).slice_rows(1000, 3000)
        return va, rng.integers(0, 2000, size=1500), None
    if name == "replace_none":
        idx = rng.integers(0, n, size=2000)
        return (_array(rng, _cell_lens(rng, n)), idx,
                np.zeros(len(idx), dtype=bool))
    if name == "replace_some":
        idx = rng.integers(0, n, size=2000)
        return (_array(rng, _cell_lens(rng, n)), idx,
                rng.random(len(idx)) < 0.2)
    if name == "replace_all":
        idx = rng.integers(0, n, size=2000)
        return (_array(rng, _cell_lens(rng, n)), idx,
                np.ones(len(idx), dtype=bool))
    if name == "replace_some_read_only":
        idx = rng.integers(0, n, size=2000)
        return (_array(rng, _cell_lens(rng, n), read_only=True), idx,
                rng.random(len(idx)) < 0.5)
    raise AssertionError(name)


@needs_native
@pytest.mark.parametrize("name", [
    "cell_mix", "uniform_stride", "zero_length_rows", "all_rows_zero_length",
    "empty_idx", "empty_array_empty_idx", "repeated_indices",
    "descending_indices", "non_contiguous_idx_slice",
    "read_only_frombuffer_source", "sliced_rows_source", "replace_none",
    "replace_some", "replace_all", "replace_some_read_only"])
def test_native_gather_matches_numpy_oracle(name):
    rng = np.random.default_rng(sum(name.encode()))
    va, idx, mask = _case(name, rng)
    gm = gather_metrics()
    n0, f0 = gm["native_rows"].value(), gm["fallback_rows"].value()
    got = va.gather(idx, replace_mask=mask, replacement=TOMB)
    want = va._gather_numpy(idx, replace_mask=mask, replacement=TOMB)
    assert gm["native_rows"].value() - n0 == len(idx)
    assert gm["fallback_rows"].value() == f0
    assert got.offsets.dtype == np.int64 and got.data.dtype == np.uint8
    assert np.array_equal(got.offsets, want.offsets)
    assert got.blob() == want.blob()
    assert len(got) == len(idx)
    if mask is not None and mask.any():
        assert got[int(np.flatnonzero(mask)[0])] == TOMB


@pytest.mark.parametrize("force_fallback", [
    False, pytest.param(True, id="fallback")])
@pytest.mark.parametrize("bad", [[5000], [0, 1, 7000, 2], [-1], [3, -4, 2]])
def test_out_of_range_idx_raises_index_error(bad, force_fallback,
                                             monkeypatch):
    if force_fallback:
        monkeypatch.setattr(native_engine, "available", lambda: False)
    va = _array(np.random.default_rng(7), np.full(5000, 8))
    gm = gather_metrics()
    before = gm["native_rows"].value(), gm["fallback_rows"].value()
    with pytest.raises(IndexError):
        va.gather(np.asarray(bad), replace_mask=None)
    assert (gm["native_rows"].value(), gm["fallback_rows"].value()) == before


@needs_native
def test_offsets_past_the_blob_raise_index_error():
    """Corrupt offsets reach the native copy as spans: they are held to
    the blob in Python, as numpy's fancy index held them."""
    rng = np.random.default_rng(8)
    va = _array(rng, np.full(100, 8))
    short = ValueArray(va.data[:400], va.offsets)
    assert short.gather(np.arange(50)).blob() == va.data[:400].tobytes()
    with pytest.raises(IndexError):
        short.gather(np.array([3, 50]))


@pytest.mark.parametrize("name", ["cell_mix", "uniform_stride",
                                  "replace_some"])
def test_fallback_runs_where_the_library_did_not_load(name, monkeypatch):
    rng = np.random.default_rng(sum(name.encode()))
    va, idx, mask = _case(name, rng)
    want = va._gather_numpy(idx, replace_mask=mask, replacement=TOMB)
    monkeypatch.setattr(native_engine, "available", lambda: False)

    def _no_native(*a, **k):
        raise AssertionError("native copy ran with available() false")
    monkeypatch.setattr(native_engine, "gather_rows", _no_native)
    gm = gather_metrics()
    n0, f0 = gm["native_rows"].value(), gm["fallback_rows"].value()
    got = va.gather(idx, replace_mask=mask, replacement=TOMB)
    assert gm["fallback_rows"].value() - f0 == len(idx)
    assert gm["native_rows"].value() == n0
    assert got == want
