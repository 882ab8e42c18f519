"""Synthetic compaction inputs: a YCSB-A-shaped tablet as overlapping
sorted runs (L0 SSTs) of uniform-random row updates plus row tombstones,
made in bulk with numpy from a seed. Tests and the graft entry's mesh
dry run feed compaction jobs from it.
"""

from __future__ import annotations

import os

import numpy as np

from yugabyte_tpu.ops.slabs import FLAG_TOMBSTONE, KVSlab, ValueArray
from yugabyte_tpu.storage.sst import Frontier, SSTWriter


def synth_ycsb_runs(n_total: int, n_runs: int, key_space: int, seed: int = 42,
                    tombstone_frac: float = 0.05):
    """Vectorized YCSB-A-like slab: n_runs sorted runs of row writes.

    Key layout (DocDB encoding, docdb/doc_key.py): root = 'S' 'user%08d'
    00 00 '!' (16B); column write = root + 'K' + 2B col id (19B).
    """
    rng = np.random.default_rng(seed)
    per_run = n_total // n_runs
    stride = 20  # 19B padded to 4B words -> w=5
    all_parts = []
    offsets = [0]
    for g in range(n_runs):
        ids = rng.integers(0, key_space, size=per_run)
        is_tomb = rng.random(per_run) < tombstone_frac
        keys = np.zeros((per_run, stride), dtype=np.uint8)
        keys[:, 0] = ord("S")
        keys[:, 1:5] = np.frombuffer(b"user", dtype=np.uint8)
        digits = ids[:, None] // (10 ** np.arange(7, -1, -1)[None, :]) % 10
        keys[:, 5:13] = (digits + ord("0")).astype(np.uint8)
        keys[:, 13] = 0
        keys[:, 14] = 0
        keys[:, 15] = ord("!")
        # column writes address col 0; tombstones hit the row root
        col_part = np.where(is_tomb[:, None],
                            np.zeros((per_run, 3), np.uint8),
                            np.array([[ord("K"), 0, 0]], np.uint8))
        keys[:, 16:19] = col_part
        key_len = np.where(is_tomb, 16, 19).astype(np.int32)
        dkl = np.full(per_run, 16, dtype=np.int32)
        ht = (1_000_000 * (g + 1) + rng.permutation(per_run)).astype(np.uint64) << 12
        flags = np.where(is_tomb, FLAG_TOMBSTONE, 0).astype(np.uint32)
        # sort run by (key, ht desc): lexsort minor->major
        sort_cols = [~ht] + [keys[:, j] for j in range(stride - 1, -1, -1)]
        order = np.lexsort(sort_cols)
        all_parts.append((keys[order], key_len[order], dkl[order], ht[order],
                          flags[order]))
        offsets.append(offsets[-1] + per_run)
    keys = np.concatenate([p[0] for p in all_parts])
    n = keys.shape[0]
    kw = keys.reshape(n, stride // 4, 4)
    key_words = ((kw[:, :, 0].astype(np.uint32) << 24)
                 | (kw[:, :, 1].astype(np.uint32) << 16)
                 | (kw[:, :, 2].astype(np.uint32) << 8)
                 | kw[:, :, 3].astype(np.uint32))
    ht = np.concatenate([p[3] for p in all_parts])
    slab = KVSlab(
        key_words=key_words,
        key_len=np.concatenate([p[1] for p in all_parts]),
        doc_key_len=np.concatenate([p[2] for p in all_parts]),
        ht_hi=(ht >> 32).astype(np.uint32),
        ht_lo=(ht & 0xFFFFFFFF).astype(np.uint32),
        write_id=np.zeros(n, dtype=np.uint32),
        flags=np.concatenate([p[4] for p in all_parts]),
        ttl_ms=np.zeros(n, dtype=np.int64),
        value_idx=np.arange(n, dtype=np.int32),
        values=ValueArray.empty_rows(n),
    )
    return slab, offsets


def attach_values(slab, value_bytes: int):
    """Give every row a value payload (uniform stride — one big buffer)."""
    n = slab.n
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=n * value_bytes, dtype=np.uint8)
    offsets = (np.arange(n + 1, dtype=np.int64) * value_bytes)
    slab.values = ValueArray(data, offsets)
    return slab


def slice_slab(slab, lo, hi):
    va = slab.values
    sel = slab.value_idx[lo:hi]
    return KVSlab(
        key_words=slab.key_words[lo:hi], key_len=slab.key_len[lo:hi],
        doc_key_len=slab.doc_key_len[lo:hi], ht_hi=slab.ht_hi[lo:hi],
        ht_lo=slab.ht_lo[lo:hi], write_id=slab.write_id[lo:hi],
        flags=slab.flags[lo:hi], ttl_ms=slab.ttl_ms[lo:hi],
        value_idx=np.arange(hi - lo, dtype=np.int32),
        values=va.gather(sel))


def split_runs(slab, offsets):
    return [slice_slab(slab, offsets[r], offsets[r + 1])
            for r in range(len(offsets) - 1)]


def write_input_ssts(slab, offsets, workdir: str):
    """Materialize the L0 input runs as real split-SST files on disk."""
    in_dir = os.path.join(workdir, "in")
    os.makedirs(in_dir, exist_ok=True)
    paths = []
    for r in range(len(offsets) - 1):
        sub = slice_slab(slab, offsets[r], offsets[r + 1])
        path = os.path.join(in_dir, f"{r:06d}.sst")
        SSTWriter(path).write(sub, Frontier())
        paths.append(path)
    return paths
