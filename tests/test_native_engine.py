"""Native compaction shell (native/compaction_engine.cc) equivalence tests.

The C++ byte path must produce BYTE-IDENTICAL output SSTs to the Python
shell + JAX kernel route — same data files, same base files (index, bloom,
props) — across compression, TTL-rewrite and multi-output splits.
"""

import os
import tempfile

import numpy as np
import pytest

from yugabyte_tpu.docdb.value import Value
from yugabyte_tpu.ops.slabs import FLAG_HAS_TTL, KVSlab, ValueArray
from yugabyte_tpu.storage import compaction as compaction_mod
from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter
from yugabyte_tpu.utils import flags

pytestmark = pytest.mark.requires_native("compaction_engine")


def _write_runs(workdir, runs):
    paths = []
    for i, slab in enumerate(runs):
        p = os.path.join(workdir, f"in{i:03d}.sst")
        SSTWriter(p).write(slab, Frontier())
        paths.append(p)
    return [SSTReader(p) for p in paths]


def _mk_run(rng, n, key_space, value_bytes=32, ttl_frac=0.0):
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from test_run_merge import _make_run
    slab = _make_run(rng, n, key_space, ttl_frac=ttl_frac)
    data = rng.integers(0, 256, size=n * value_bytes, dtype=np.uint8)
    offs = np.arange(n + 1, dtype=np.int64) * value_bytes
    slab.values = ValueArray(data, offs)
    return slab


def _run_both(readers, cutoff, is_major, tmp, block_entries=512):
    ids_n = iter(range(1, 500))
    ids_p = iter(range(1, 500))
    nat_dir = os.path.join(tmp, "nat")
    py_dir = os.path.join(tmp, "py")
    os.makedirs(nat_dir)
    os.makedirs(py_dir)
    rn = compaction_mod._run_native_job(
        readers, nat_dir, lambda: next(ids_n), cutoff, is_major, False,
        block_entries)
    rp = compaction_mod.run_compaction_job(
        readers, py_dir, lambda: next(ids_p), cutoff, is_major,
        block_entries=block_entries, device=None)
    assert rn.rows_in == rp.rows_in
    assert rn.rows_out == rp.rows_out
    assert len(rn.outputs) == len(rp.outputs)
    for (_, b1, p1), (_, b2, p2) in zip(rn.outputs, rp.outputs):
        with open(b1 + ".sblock.0", "rb") as f1, \
                open(b2 + ".sblock.0", "rb") as f2:
            assert f1.read() == f2.read(), "data file mismatch"
        with open(b1, "rb") as f1, open(b2, "rb") as f2:
            assert f1.read() == f2.read(), "base file mismatch"
    return rn


def test_byte_identical_basic(tmp_path):
    rng = np.random.default_rng(5)
    runs = [_mk_run(rng, int(rng.integers(200, 800)), 120)
            for _ in range(4)]
    readers = _write_runs(str(tmp_path), runs)
    _run_both(readers, (1 << 21) << 12, True, str(tmp_path))
    for r in readers:
        r.close()


def test_byte_identical_ttl_rewrite(tmp_path):
    """Minor compaction TTL expiry rewrites values as tombstones in both."""
    rng = np.random.default_rng(6)
    runs = [_mk_run(rng, 400, 60, ttl_frac=0.5) for _ in range(3)]
    readers = _write_runs(str(tmp_path), runs)
    rn = _run_both(readers, (1 << 22) << 12, False, str(tmp_path))
    assert rn.rows_out > 0
    for r in readers:
        r.close()


def test_multi_output_split(tmp_path):
    rng = np.random.default_rng(7)
    runs = [_mk_run(rng, 600, 4000) for _ in range(3)]
    readers = _write_runs(str(tmp_path), runs)
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 500)
    try:
        rn = _run_both(readers, (1 << 21) << 12, True, str(tmp_path))
        assert len(rn.outputs) >= 2
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)
    for r in readers:
        r.close()


def test_multi_output_split_with_ttl_rewrite(tmp_path):
    """Regression: surv_mk is survivor-absolute — output files after the
    first must read tombstone-rewrite flags from absolute positions, not
    file-relative ones (caught in round-3 review; silent corruption)."""
    rng = np.random.default_rng(9)
    runs = [_mk_run(rng, 500, 3000, ttl_frac=0.5) for _ in range(3)]
    readers = _write_runs(str(tmp_path), runs)
    old = flags.get_flag("compaction_max_output_entries_per_sst")
    flags.set_flag("compaction_max_output_entries_per_sst", 400)
    try:
        rn = _run_both(readers, (1 << 22) << 12, False, str(tmp_path))
        assert len(rn.outputs) >= 2
    finally:
        flags.set_flag("compaction_max_output_entries_per_sst", old)
    for r in readers:
        r.close()


def test_outputs_reopen_and_read(tmp_path):
    """Native outputs must be readable by the Python SSTReader path."""
    rng = np.random.default_rng(8)
    runs = [_mk_run(rng, 300, 50) for _ in range(3)]
    readers = _write_runs(str(tmp_path), runs)
    ids = iter(range(1, 50))
    out_dir = os.path.join(str(tmp_path), "out")
    os.makedirs(out_dir)
    rn = compaction_mod._run_native_job(
        readers, out_dir, lambda: next(ids), (1 << 21) << 12, True, False,
        256)
    total = 0
    for _, base, props in rn.outputs:
        rd = SSTReader(base)
        slab = rd.read_all()
        assert slab.n == props.n_entries
        # bloom must answer positively for every doc key it holds
        for i in range(0, slab.n, 37):
            dk = slab.key_bytes(i)[: int(slab.doc_key_len[i])]
            assert rd.may_contain_doc(dk)
        total += slab.n
        rd.close()
    assert total == rn.rows_out
    for r in readers:
        r.close()
