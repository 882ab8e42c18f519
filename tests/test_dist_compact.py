"""Distributed compaction over an 8-device virtual mesh vs single-device.

The multi-chip path (sample -> all_gather splitters -> all_to_all -> local
merge/GC) must keep exactly the same entries as the single-chip kernel.
"""

import random

import numpy as np
import pytest

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
from yugabyte_tpu.docdb.compaction_model import ModelEntry
from yugabyte_tpu.ops.merge_gc import GCParams, _ROW_WORDS, merge_and_gc_device
from yugabyte_tpu.parallel.mesh import make_mesh
from yugabyte_tpu.parallel.dist_compact import distributed_compact
from tests.test_merge_gc_kernel import slab_from_model, mk_key, ht, CUTOFF


def _kept_set_single(entries, is_major):
    slab = slab_from_model(entries)
    perm, keep, mk = merge_and_gc_device(slab, GCParams(CUTOFF, is_major))
    out = set()
    for pos in np.nonzero(keep)[0]:
        i = int(perm[pos])
        out.add((slab.key_bytes(i), int(slab.ht_hi[i]), int(slab.ht_lo[i]),
                 int(slab.write_id[i]), bool(mk[pos])))
    return out


def _kept_set_dist(entries, is_major, n_shards=8):
    slab = slab_from_model(entries)
    mesh = make_mesh(n_shards)
    cols, keep, mk, _idx = distributed_compact(slab, GCParams(CUTOFF, is_major), mesh)
    out = set()
    w = cols.shape[0] - _ROW_WORDS
    for pos in np.nonzero(keep)[0]:
        klen = int(cols[0, pos])
        key = cols[_ROW_WORDS:, pos].astype(">u4").tobytes()[:klen]
        out.add((key, int(cols[2, pos]), int(cols[3, pos]),
                 int(cols[4, pos]), bool(mk[pos])))
    return out


@pytest.mark.parametrize("is_major", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_dist_matches_single(seed, is_major):
    rng = random.Random(seed)
    entries = []
    seen = set()
    for _ in range(400):
        row = rng.randint(0, 40)
        col = rng.choice([None, 0, 1])
        key, dkl = mk_key(row, col)
        e = ModelEntry(key, dkl, ht(rng.randint(1, 2000), rng.randint(0, 3)),
                       is_tombstone=rng.random() < 0.15,
                       ttl_ms=rng.choice([None, None, 0, 10**9]))
        if (e.key, e.dht) in seen:
            continue
        seen.add((e.key, e.dht))
        entries.append(e)
    single = _kept_set_single(entries, is_major)
    dist = _kept_set_dist(entries, is_major)
    assert dist == single


def test_dist_actually_distributes_common_prefix_keys():
    """Real DocDB keyspaces share leading bytes (value-type tags etc.);
    routing must still spread documents across shards, and a document's
    root + column entries must land on ONE shard (GC straddle hazard)."""
    n_shards = 8
    entries = []
    for r in range(256):
        # two column entries per document
        for col in (0, 1):
            key, dkl = mk_key(r, col)
            entries.append(ModelEntry(key, dkl, ht(100 + r)))
    slab = slab_from_model(entries)
    mesh = make_mesh(n_shards)
    cols, keep, mk, _idx = distributed_compact(slab, GCParams(CUTOFF, False), mesh)
    per_shard = keep.reshape(n_shards, -1).sum(axis=1)
    # all entries survive, and no shard holds more than half of them
    assert per_shard.sum() == len(entries)
    assert (per_shard > 0).sum() >= 4, per_shard
    assert per_shard.max() <= len(entries) // 2, per_shard
    # each document's entries are contiguous within one shard slice
    shard_width = cols.shape[1] // n_shards
    doc_to_shard = {}
    for pos in np.nonzero(keep)[0]:
        dkl_v = int(cols[1, pos])
        doc = cols[_ROW_WORDS:, pos].astype(">u4").tobytes()[:dkl_v]
        shard = int(pos) // shard_width
        assert doc_to_shard.setdefault(doc, shard) == shard, doc
    assert len(doc_to_shard) == 256


def test_dist_short_doc_keys_stay_with_document():
    """Doc keys shorter than one route word (4 bytes) must not split a
    document across shards: a root tombstone has to keep covering its
    subkey entries during major compaction."""
    entries = []
    for r in range(64):
        # 2-byte doc keys: kInt-ish tag + 1 byte; subkey extends past it
        doc = bytes([0x48, r])
        entries.append(ModelEntry(doc, 2, ht(500), is_tombstone=True))
        entries.append(ModelEntry(doc + bytes([0x4B, 0, 1]), 2, ht(400)))
    single = _kept_set_single(entries, True)
    dist = _kept_set_dist(entries, True)
    assert dist == single
    # the tombstone (visible, major) and the covered subkey both vanish
    assert len(dist) == 0


def test_dist_output_globally_ordered():
    entries = []
    for r in range(100):
        key, dkl = mk_key(r)
        entries.append(ModelEntry(key, dkl, ht(100 + r)))
    slab = slab_from_model(entries)
    mesh = make_mesh(8)
    cols, keep, mk, _idx = distributed_compact(slab, GCParams(CUTOFF, False), mesh)
    kept_keys = []
    for pos in range(cols.shape[1]):
        if keep[pos]:
            klen = int(cols[0, pos])
            kept_keys.append(cols[_ROW_WORDS:, pos].astype(">u4").tobytes()[:klen])
    # globally range-partitioned: concatenation across shards is sorted
    assert kept_keys == sorted(kept_keys)
    assert len(kept_keys) == 100


def test_run_compaction_job_mesh_byte_identical(tmp_path):
    """VERDICT r3 #3: a production compaction job with a mesh visible must
    fan subcompactions across it and produce BYTE-identical output SSTs to
    the single-device job over the same inputs."""
    import jax

    from yugabyte_tpu.integration.synth import (attach_values, split_runs,
                                                synth_ycsb_runs)
    from yugabyte_tpu.storage.compaction import run_compaction_job
    from yugabyte_tpu.storage.sst import Frontier, SSTReader, SSTWriter
    from yugabyte_tpu.utils import flags

    n = 60_000
    slab, offsets = synth_ycsb_runs(n, 4, n // 2, seed=5)
    attach_values(slab, 24)
    runs = split_runs(slab, offsets)
    in_dir = tmp_path / "in"
    in_dir.mkdir()
    paths = []
    for i, sub in enumerate(runs):
        p = str(in_dir / f"{i:06d}.sst")
        SSTWriter(p).write(sub, Frontier())
        paths.append(p)
    cutoff = (10_000_000 << 12)
    old = flags.get_flag("distributed_compaction_min_rows")
    flags.set_flag("distributed_compaction_min_rows", 1000)
    try:
        outs = {}
        for tag, mesh in (("mesh", make_mesh(8)), ("single", None)):
            readers = [SSTReader(p) for p in paths]
            out_dir = tmp_path / tag
            out_dir.mkdir()
            ids = iter(range(1, 1000))
            res = run_compaction_job(
                readers, str(out_dir), lambda: next(ids), cutoff, True,
                device=jax.devices()[0], mesh=mesh)
            for r in readers:
                r.close()
            outs[tag] = res
        assert outs["mesh"].rows_out == outs["single"].rows_out
        assert len(outs["mesh"].outputs) == len(outs["single"].outputs)
        for (f1, p1, _), (f2, p2, _) in zip(outs["mesh"].outputs,
                                            outs["single"].outputs):
            from yugabyte_tpu.storage.sst import data_file_name
            for path_fn in (lambda p: p, data_file_name):
                b1 = open(path_fn(p1), "rb").read()
                b2 = open(path_fn(p2), "rb").read()
                assert b1 == b2, f"{path_fn(p1)} differs from single-device"
    finally:
        flags.set_flag("distributed_compaction_min_rows", old)


def test_dist_overflow_retry_counts_and_reuses_device_cols():
    """A too-small capacity factor overflows the exchange buckets; the
    retry must re-launch at doubled capacity from the device-resident
    cols (no host re-pack), increment dist_compact_overflow_retry_total,
    and converge to the same decisions as a comfortable first try."""
    from yugabyte_tpu.parallel.dist_compact import _overflow_retry_counter
    entries = []
    for r in range(2048):
        key, dkl = mk_key(r)
        entries.append(ModelEntry(key, dkl, ht(100 + (r % 500))))
    slab = slab_from_model(entries)
    mesh = make_mesh(8)
    before = _overflow_retry_counter().value()
    cols, keep, mk, idx = distributed_compact(
        slab, GCParams(CUTOFF, True), mesh, capacity_factor=0.05)
    assert _overflow_retry_counter().value() > before, \
        "overflow retries must be counted"
    cols2, keep2, mk2, idx2 = distributed_compact(
        slab, GCParams(CUTOFF, True), mesh)
    assert int(keep.sum()) == int(keep2.sum())
    assert np.array_equal(np.sort(idx[keep]), np.sort(idx2[keep2]))


@pytest.mark.slow
def test_dist_compact_1m_rows_8_shards():
    """Scale test (VERDICT r3 #3): 1M rows across the 8-device CPU mesh;
    survivor count must match the single-core C++ baseline exactly."""
    from yugabyte_tpu.integration.synth import split_runs, synth_ycsb_runs
    from yugabyte_tpu.ops.slabs import concat_slabs
    from yugabyte_tpu.storage.cpu_baseline import compact_cpu_baseline

    n = 1 << 20
    slab, offsets = synth_ycsb_runs(n, 4, n // 2, seed=9)
    cutoff = (10_000_000 << 12)
    _, keep_c, _ = compact_cpu_baseline(slab, offsets, cutoff, True)
    mesh = make_mesh(8)
    cols, keep, mk, idx = distributed_compact(
        slab, GCParams(cutoff, True), mesh)
    assert int(keep.sum()) == int(keep_c.sum())
    # survivors map back to real input rows, in globally sorted order
    surv = idx[keep]
    assert len(np.unique(surv)) == len(surv)
    assert surv.max() < n
