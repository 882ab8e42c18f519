#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the store still starts on the chip.

One process; it alone touches JAX. Drives the system's main path once
through the entry points a user would call, checks what comes out against
the repo's own references, and makes every fallback visible through the
counters the program already keeps.

  python chip_smoke.py            one chip: phases `storage` and `cluster`
  python chip_smoke.py --chips 4  four chips: phase `dist` and nothing else

Every phase prints one JSON line; the last line of stdout is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
The script exits non-zero, and says `"ok": false` with the reason, when
`jax.devices()[0].platform` is not `tpu`, when a phase raises, or when a
check fails. Nothing here catches a phase's failure, there is no CPU
branch and no interpret mode. Wall and compile seconds on the phase lines
are smoke facts for sizing the next run, not benchmark metrics.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# Rows per L0 run of the `storage` phase. The sizing rule (ISSUE 22): the
# largest of 2^20, 2^18, 2^16 whose whole executable set compiled for v5e
# in the sandbox in under ten minutes together. 2^20 and 2^18 both launch
# the (4, 2^18) merge bucket, whose Pallas program alone compiled in 205 s
# and whose snapshot-scan program (radix sort at n_pad 2^20) in 390 s.
ROWS_PER_RUN = 1 << 16
N_RUNS = 4
STORAGE_REDUCED = {
    "rows": "4 x 2^16, not 4 x 2^20",
    "forced_by_compile_s": {"pallas_merge (4, 2^18)": 205,
                            "scan sort program n_pad 2^20": 390},
}
FULL_ROWS_PER_RUN = 1 << 20
CLUSTER_ROWS = 100_000
# two tablets: each leader's compacted SST then holds ~140K entries, the
# n_pad 2^18 bucket the storage phase's scans already compiled
CLUSTER_TABLETS = 2
WRITE_BATCH = 1024                    # ops per YBSession flush
FILTER_RANGE = (100_000, 110_000)     # WHERE n >= lo AND n < hi
AGG_THRESHOLD = 750_000               # WHERE n >= thr
AGGS = (("count", None), ("sum", "n"), ("min", "n"), ("max", "n"))
# --chips 4: the mesh step's executable for 4 x 2^20 rows (a radix sort
# over 2^21 rows per shard) had not compiled for v5e:2x2 in the sandbox
# after 12 minutes; for 4 x 2^18 rows (the distributed_compaction_min_rows
# default, the smallest job the mesh path takes) it compiled in 451 s.
DIST_ROWS_PER_RUN = 1 << 18
DIST_REDUCED = {
    "rows": "4 x 2^18, not 4 x 2^20",
    "forced_by_compile_s": {"dist_compact 2^18 rows/shard": 451,
                            "dist_compact 2^20 rows/shard": ">720, abandoned"},
}
POOL_ROWS_PER_RUN = 1 << 14           # a flush-sized tablet job per slot


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------- observation

class CompileClock:
    """Sums what JAX reports of its own compiles (cache loads included)."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


def counters() -> dict:
    """The fallback / routing counters the program already keeps."""
    from yugabyte_tpu.ops.block_codec import codec_metrics
    from yugabyte_tpu.ops.point_read import point_read_snapshot
    from yugabyte_tpu.ops.scan import pushdown_snapshot
    from yugabyte_tpu.storage import offload_policy
    from yugabyte_tpu.storage.compaction import _storage_fallback_counter
    from yugabyte_tpu.storage.integrity import shadow_mismatch_counter
    from yugabyte_tpu.utils.metrics import kernel_metrics
    km = kernel_metrics()
    oc = offload_policy._offload_counters()
    pr = point_read_snapshot()
    pd = pushdown_snapshot()
    cm = codec_metrics()
    return {
        "offload_decisions_device_total": oc["device"].value(),
        "offload_decisions_native_total": oc["native"].value(),
        "offload_decisions_forced_total": oc["forced"].value(),
        "kernel_pallas_merge_total": km.counter(
            "kernel_pallas_merge_total", "").value(),
        "kernel_network_merge_total": km.counter(
            "kernel_network_merge_total", "").value(),
        "kernel_pallas_fallback_total": km.counter(
            "kernel_pallas_fallback_total", "").value(),
        "compaction_device_fallback_total":
            _storage_fallback_counter().value(),
        "point_read_device_fallback_total": pr["device_fallbacks"],
        "point_read_batched_keys_total": pr["batched_keys"],
        "device_shadow_mismatch_total": shadow_mismatch_counter().value(),
        "compaction_block_encode_fallback_total":
            cm["encode_fallbacks"].value(),
        "compaction_block_encode_device_total": cm["encode_blocks"].value(),
        "pushdown_hits": pd["filtered_scans"] + pd["agg_scans"],
        "pushdown_fallbacks": sum(pd["fallbacks"].values()),
    }


ZERO_COUNTERS = ("offload_decisions_forced_total",
                 "kernel_pallas_fallback_total",
                 "compaction_device_fallback_total",
                 "point_read_device_fallback_total",
                 "device_shadow_mismatch_total")


class Phase:
    """Brackets one phase: wall and compile seconds, counter deltas, the
    merge buckets dispatched, peak device bytes."""

    def __init__(self, name: str, clock: CompileClock, device):
        from yugabyte_tpu.ops import run_merge
        self.name = name
        self._clock = clock
        self._device = device
        self._t0 = time.monotonic()
        self._c0 = (clock.seconds, clock.count)
        self._k0 = counters()
        self._b0 = set(run_merge._bucket_keys_seen)

    def delta(self) -> dict:
        now = counters()
        return {k: now[k] - self._k0[k] for k in now}

    def line(self, facts: dict) -> dict:
        from yugabyte_tpu.ops import run_merge
        from yugabyte_tpu.storage import native_engine
        from yugabyte_tpu.storage.offload_policy import bucket_quarantine
        d = self.delta()
        quarantined = bucket_quarantine().snapshot()
        for name in ZERO_COUNTERS:
            require(d[name] == 0, f"{self.name}: {name} = {d[name]}")
        require(not quarantined,
                f"{self.name}: quarantined buckets {quarantined}")
        require(native_engine.available(),
                f"{self.name}: native engine unavailable (g++ failed?)")
        stats = self._device.memory_stats() or {}
        buckets = sorted(
            [str(x) for x in b[:6]]
            for b in set(run_merge._bucket_keys_seen) - self._b0)
        out = {"phase": self.name, "ok": True,
               "wall_s": round(time.monotonic() - self._t0, 1),
               "compile_s": round(self._clock.seconds - self._c0[0], 1),
               "compiles": self._clock.count - self._c0[1],
               "buckets": buckets,
               "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
               "counters": d, "quarantined": quarantined,
               "native_engine": True}
        out.update(facts)
        return out


def run_phase(name: str, clock: CompileClock, device, body, *args) -> dict:
    """Run one phase body (it gets its Phase first), check the common
    counters, print and return the phase's line."""
    ph = Phase(name, clock, device)
    line = ph.line(body(ph, *args))
    print(json.dumps(line), flush=True)
    return line


# ---------------------------------------------------------------------- data

def usertable_schema(hash_key: bool):
    """k (key), f (~41-byte string), n (INT64, what the predicates read).
    The storage phase keys by range so its vectorised keys need no hash."""
    from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
    return Schema(columns=[ColumnSchema("k", DataType.STRING),
                           ColumnSchema("f", DataType.STRING),
                           ColumnSchema("n", DataType.INT64)],
                  num_hash_key_columns=int(hash_key),
                  num_range_key_columns=int(not hash_key))


F_PAYLOAD = 41      # 'S' + 41 + 00 00 = 44 value bytes; 19-byte key: ~64 B KV
_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", dtype=np.uint8)


class YcsbRuns:
    """Vectorised YCSB-shaped L0 runs in the repo's own DocDB encoding:
    key 'S' 'user%08d' 00 00 '!' (+ 'K' col id), one column write or one
    row tombstone per entry, ~64-byte key-values (BASELINE.md)."""

    def __init__(self, seed: int):
        from yugabyte_tpu.docdb.doc_key import DocKey
        from yugabyte_tpu.docdb.doc_operations import column_key_suffix
        from yugabyte_tpu.docdb.value import Value
        self.schema = usertable_schema(hash_key=False)
        self.rng = np.random.default_rng(seed)
        root = DocKey(range_components=("user00000000",)).encode()
        self.root = np.frombuffer(root, dtype=np.uint8)
        self.digit0 = root.index(b"00000000")
        self.suffix = {c: np.frombuffer(column_key_suffix(
            self.schema.column_id(c)), dtype=np.uint8) for c in ("f", "n")}
        self.tomb = Value.tombstone().encode()
        f_enc = Value(primitive="x" * F_PAYLOAD).encode()
        self.f_head, self.f_tail = f_enc[:1], f_enc[1 + F_PAYLOAD:]
        self.n_tag = Value(primitive=0).encode()[:1]
        self._self_check()

    def root_keys(self, ids: np.ndarray) -> np.ndarray:
        keys = np.tile(self.root, (len(ids), 1))
        digits = ids[:, None] // (10 ** np.arange(7, -1, -1)[None, :]) % 10
        keys[:, self.digit0:self.digit0 + 8] = digits + ord("0")
        return keys

    def column_key(self, uid: int, col: str) -> bytes:
        return self.root_keys(np.asarray([uid]))[0].tobytes() \
            + self.suffix[col].tobytes()

    def encode_n(self, vals: np.ndarray) -> np.ndarray:
        biased = (vals.astype(np.int64).view(np.uint64)
                  ^ np.uint64(1 << 63)).astype(">u8")
        out = np.empty((len(vals), 9), dtype=np.uint8)
        out[:, 0] = self.n_tag[0]
        out[:, 1:] = biased.view(np.uint8).reshape(-1, 8)
        return out

    def run(self, n: int, key_space: int, ht_base_us: int):
        """One run of n entries: 5% row tombstones, 25% writes of the
        INT64 column, the rest writes of the ~44-byte string column."""
        rng = self.rng
        ids = rng.integers(0, key_space, size=n)
        u = rng.random(n)
        is_tomb, is_n = u < 0.05, (u >= 0.05) & (u < 0.30)
        sfx = len(self.suffix["f"])
        keys = np.zeros((n, len(self.root) + sfx), dtype=np.uint8)
        keys[:, :len(self.root)] = self.root_keys(ids)
        keys[:, len(self.root):] = np.where(
            is_n[:, None], self.suffix["n"][None, :],
            self.suffix["f"][None, :])
        key_len = np.where(is_tomb, len(self.root), keys.shape[1])
        f_len = 1 + F_PAYLOAD + len(self.f_tail)
        vals = np.zeros((n, f_len), dtype=np.uint8)
        vals[:, 0] = self.f_head[0]
        vals[:, 1:1 + F_PAYLOAD] = _LETTERS[
            rng.integers(0, 26, size=(n, F_PAYLOAD))]
        vals[:, 1 + F_PAYLOAD:] = np.frombuffer(self.f_tail, dtype=np.uint8)
        n_vals = rng.integers(0, 1_000_000, size=n)
        vals[is_n, :9] = self.encode_n(n_vals[is_n])
        vals[is_tomb, 0] = self.tomb[0]
        val_len = np.where(is_tomb, 1, np.where(is_n, 9, f_len))
        ht = ((np.uint64(ht_base_us) + rng.permutation(n).astype(np.uint64))
              << np.uint64(12))
        return {
            "ids": ids,
            "keys_blob": keys[np.arange(keys.shape[1])[None, :]
                              < key_len[:, None]].tobytes(),
            "key_offs": np.concatenate([[0], np.cumsum(key_len)]).astype(
                np.int64),
            "vals_blob": vals[np.arange(f_len)[None, :]
                              < val_len[:, None]].tobytes(),
            "val_offs": np.concatenate([[0], np.cumsum(val_len)]).astype(
                np.int64),
            "ht": ht, "wid": np.zeros(n, dtype=np.uint32),
        }

    def _self_check(self) -> None:
        """The vectorised bytes are what the repo's encoder writes."""
        from yugabyte_tpu.docdb.doc_key import DocKey
        from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
        for uid, nv in ((7, 0), (12345678, 999_999), (99999999, 31337)):
            op = QLWriteOp(WriteOpKind.UPDATE,
                           DocKey(range_components=("user%08d" % uid,)),
                           {"n": nv})
            (key, val), = op.to_kv_pairs(self.schema)
            require(key == self.column_key(uid, "n"), "key encoding drifted")
            require(val == self.encode_n(np.asarray([nv]))[0].tobytes(),
                    "INT64 value encoding drifted")


def decode_n(value: bytes) -> int:
    return int.from_bytes(value[1:9], "big") - (1 << 63)


# ------------------------------------------------------------- phase: storage

def _sst_files(outputs, base: bool = True):
    """Bytes of every output's data file, and of its base file too."""
    from yugabyte_tpu.storage.sst import data_file_name
    out = []
    for _fid, base_path, _props in outputs:
        for p in ([base_path] if base else []) + [data_file_name(base_path)]:
            with open(p, "rb") as f:
                out.append(f.read())
    return out


def _props_but_lindex(outputs):
    """Base-file contents as the reader sees them, less the learned
    index: the device path fits one at write-through (while the keys are
    on the device) and the native job never does, so it alone may differ
    between the two jobs' base files."""
    from yugabyte_tpu.storage import SSTReader
    out = []
    for _fid, base_path, _props in outputs:
        r = SSTReader(base_path)
        d = dict(vars(r.props))
        d.pop("lindex", None)
        out.append((d, r.block_handles))
        r.close()
    return out


def storage_phase(phase: Phase, device, seed: int, rows_per_run: int,
                  workdir: str) -> dict:
    """4 overlapping L0 runs -> major compaction decided by the health
    board on defaults -> byte-identity against the native C++ job over
    the same input files -> snapshot scan, filtered scan, aggregate scan
    and a 1,024-key multi_get against the native read engine."""
    from yugabyte_tpu.common.hybrid_time import HybridTime
    from yugabyte_tpu.docdb import scan_spec as SS
    from yugabyte_tpu.storage import DB, DBOptions, SSTReader
    from yugabyte_tpu.storage.bucket_health import health_board
    from yugabyte_tpu.storage.compaction import run_compaction_job
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    from yugabyte_tpu.storage.sst import BlockCache, data_file_name
    from yugabyte_tpu.utils import flags

    require(flags.get_flag("device_offload_mode") == "auto",
            "device_offload_mode is not at its default")
    data = YcsbRuns(seed)
    schema = data.schema
    n_total = N_RUNS * rows_per_run
    key_space = n_total // 2
    cutoff = HybridTime.from_micros(10_000_000_000).value
    db = DB(os.path.join(workdir, "db"), DBOptions(
        device=device, offload_policy=health_board(),
        device_cache=DeviceSlabCache(device),
        block_cache=BlockCache(256 << 20),  # a tserver's default
        retention_policy=lambda: cutoff, auto_compact=False))
    used_ids = []
    for g in range(N_RUNS):
        run = data.run(rows_per_run, key_space, 1_000_000 * (g + 1))
        used_ids.append(run["ids"][:256])
        db.ingest_packed(run["keys_blob"], run["key_offs"], run["ht"],
                         run["wid"], run["vals_blob"], run["val_offs"],
                         op_id=(1, g + 1))
    # the native job below reads the SAME input files: keep them alive
    # past the DB's post-compaction delete through hard links
    in_dir = os.path.join(workdir, "native_in")
    os.makedirs(in_dir)
    linked = []
    for fm in db.versions.live_files():
        dst = os.path.join(in_dir, os.path.basename(fm.path))
        for src, d in ((fm.path, dst),
                       (data_file_name(fm.path), data_file_name(dst))):
            os.link(src, d)
        linked.append(dst)
    require(len(linked) == N_RUNS, f"expected {N_RUNS} L0 files")

    read_ht = HybridTime.kMax.value

    def native_visible():
        return [(k, v, ht) for k, v, ht, _w, _f, _d in db.scan_native(
            visible=True, read_ht_value=read_ht).entries()]

    def pushdown_checks(when: str, ref):
        """The two query forms the cluster phase serves too: a filtered
        scan over lo <= n < hi, and count/sum/min/max WHERE n >= thr.
        Reference: the native engine's visible entries with the predicate
        applied to the decoded value (one entry per (row, column) at
        depth 2). Run over the 4 L0 files and again over the compacted
        SST: the source count is a static of both programs (one sorted
        source skips the merge sort), a tablet's read may meet either,
        and a cold compile of one outlasts its RPC deadline — so this
        phase leaves both variants compiled."""
        n_sfx = data.suffix["n"].tobytes()
        root_len = len(data.root)
        n_of = {k[:root_len]: decode_n(v) for k, v, _ht in ref
                if k[root_len:] == n_sfx}
        lo, hi = FILTER_RANGE
        preds = (SS.compile_predicate(schema, "n", ">=", lo),
                 SS.compile_predicate(schema, "n", "<", hi))
        want_rows = [(k, v, ht) for k, v, ht in ref
                     if lo <= n_of.get(k[:root_len], -1) < hi]
        got_rows = list(db.scan_filtered(read_ht, SS.ScanSpec(preds, ())))
        require(got_rows == want_rows,
                f"scan_filtered {when} differs from the reference "
                f"({len(got_rows)} vs {len(want_rows)} entries)")
        aggs = tuple(SS.compile_aggregate(schema, fn, col)
                     for fn, col in AGGS)
        agg = db.scan_aggregate(read_ht, SS.ScanSpec(
            (SS.compile_predicate(schema, "n", ">=", AGG_THRESHOLD),),
            aggs))
        n_vals = [v for v in n_of.values() if v >= AGG_THRESHOLD]
        require(agg["rows"] == len(n_vals),
                f"count(*) {when}: {agg['rows']} != {len(n_vals)}")
        col = agg["cols"][schema.column_id("n")]
        require((col["sum"], col["min"], col["max"]) ==
                (sum(n_vals), min(n_vals), max(n_vals)),
                f"aggregate over n {when} differs: {col}")
        return len(got_rows), agg["rows"]

    pushdown_checks("before compaction", native_visible())

    t0 = time.monotonic()
    db.compact_all()
    compact_s = time.monotonic() - t0
    require(db.background_error is None,
            f"compaction parked the DB: {db.background_error}")
    device_out = [(fm.file_id, fm.path, None)
                  for fm in db.versions.live_files()]
    require(device_out, "compaction left no output")

    readers = [SSTReader(p) for p in linked]
    native_dir = os.path.join(workdir, "native_out")
    os.makedirs(native_dir)
    ids = iter(range(1000, 1 << 20))
    native = run_compaction_job(readers, native_dir, lambda: next(ids),
                                cutoff, True, device="native")
    for r in readers:
        r.close()
    identical = _sst_files(device_out, base=False) == \
        _sst_files(native.outputs, base=False)
    require(identical, "device compaction's SST data files differ from the "
                       "native C++ job's over the same input files")
    require(str(_props_but_lindex(device_out)) ==
            str(_props_but_lindex(native.outputs)),
            "device compaction's SST base files differ from the native "
            "job's in more than the learned index")

    ref = native_visible()
    got = list(db.scan_visible(read_ht))
    require(got == ref, f"snapshot scan differs from the native engine "
                        f"({len(got)} vs {len(ref)} entries)")
    rows_out = len(ref)
    require(rows_out == native.rows_out, "scan row count != survivors")

    filtered_entries, agg_rows = pushdown_checks("after compaction", ref)

    present = np.unique(np.concatenate(used_ids))[:512]
    absent = key_space + 1 + np.arange(1024 - len(present))
    keys = [data.column_key(int(i), "f") for i in present] + \
           [data.column_key(int(i), "f") for i in absent]
    got_pts = db.multi_get(keys)
    want_pts = [db.get(k) for k in keys]
    require(got_pts == want_pts, "multi_get differs from per-key get")
    # and the small batch bucket, which a tablet's last partial chunk takes
    require(db.multi_get(keys[500:540]) == want_pts[500:540],
            "40-key multi_get differs from per-key get")
    hits = sum(1 for p in want_pts if p is not None)
    db.close()

    d = phase.delta()
    require(d["offload_decisions_device_total"] >= 1,
            "no compaction was decided on the device")
    require(d["kernel_pallas_merge_total"] >= 1,
            "the merge never launched the Pallas kernel")
    require(d["compaction_block_encode_fallback_total"] == 0,
            "outputs went through the shell encode, not the device codec")
    require(d["point_read_batched_keys_total"] > 0,
            "multi_get never took the batched device path")
    require(d["pushdown_hits"] >= 4, "pushdown scans did not hit the device")
    facts = {"rows": n_total, "rows_per_run": rows_per_run,
             "rows_out": rows_out, "compact_s": round(compact_s, 1),
             "byte_identical_to_native": identical,
             "output_files": len(device_out),
             "scan_entries": len(got), "filtered_entries": filtered_entries,
             "agg_rows": agg_rows, "multi_get_keys": len(keys),
             "multi_get_hits": hits}
    if rows_per_run != FULL_ROWS_PER_RUN:
        facts["reduced"] = STORAGE_REDUCED
    return facts


# ------------------------------------------------------------- phase: cluster

def cluster_phase(phase: Phase, platform: str, seed: int, n_rows: int,
                  workdir: str, n_tablets: int) -> dict:
    """In-process RF3 MiniCluster on default flags: write n_rows through
    YBSession in three rounds with yb_admin flush_table between and
    compact_table after, then batched reads, a pushed-down count(*), a
    filtered scan, and a read of every sampled key from all 3 replicas —
    all against a dict model built from the same seed."""
    import contextlib
    import io

    from yugabyte_tpu.client.session import YBSession
    from yugabyte_tpu.docdb.doc_key import DocKey
    from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
    from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                       MiniClusterOptions)
    from yugabyte_tpu.tools.yb_admin import AdminClient
    from yugabyte_tpu.yql.cql.executor import QLProcessor

    rng = np.random.default_rng(seed + 1)
    cluster = MiniCluster(MiniClusterOptions(
        num_masters=1, num_tservers=3, fs_root=workdir)).start()
    try:
        devices = [ts.compactionz()["device"] for ts in cluster.tservers]
        require(all(d["platform"] == platform for d in devices),
                f"tserver devices {devices}, expected platform {platform}")
        client = cluster.new_client()
        client.create_namespace("smoke")
        schema = usertable_schema(hash_key=True)
        table = client.create_table("smoke", "usertable", schema,
                                    num_tablets=n_tablets)
        cluster.wait_all_replicas_running(table.table_id)
        admin = AdminClient(cluster.master_addrs())

        def dk(i):
            return DocKey(hash_components=("user%08d" % i,))

        def f_n(row):
            d = row.to_dict(schema)
            return d["f"], d["n"]

        # model: k -> (f, n). Rounds 2 and 3 overwrite a tenth of the
        # earlier keys, so compaction has versions to collect.
        model = {}
        per_round = n_rows // 3
        acked = 0
        next_id = 0
        for rnd in range(3):
            n_round = per_round if rnd < 2 else n_rows - 2 * per_round
            n_again = n_round // 10 if rnd else 0
            fresh = np.arange(next_id, next_id + n_round - n_again)
            again = rng.integers(0, max(next_id, 1), size=n_again)
            next_id += len(fresh)
            ids = np.concatenate([fresh, again])
            f_vals = _LETTERS[rng.integers(0, 26, size=(len(ids), F_PAYLOAD))]
            n_vals = rng.integers(0, 1_000_000, size=len(ids))
            session = YBSession(client)
            for j, (i, fv, nv) in enumerate(zip(ids.tolist(), f_vals,
                                                n_vals.tolist()), 1):
                f = fv.tobytes().decode()
                session.apply(table, QLWriteOp(WriteOpKind.INSERT, dk(i),
                                               {"f": f, "n": nv}))
                model[i] = (f, nv)
                if j % WRITE_BATCH == 0:
                    session.flush()     # raises unless every op was acked
            session.flush()
            session.close()
            acked += len(ids)
            with contextlib.redirect_stdout(io.StringIO()):  # admin prints
                admin.flush_table("smoke", "usertable")
                if rnd == 2:
                    admin.compact_table("smoke", "usertable")
        require(acked >= n_rows, f"acked {acked} < {n_rows}")

        sample = rng.choice(np.fromiter(model, dtype=np.int64),
                            size=min(2000, len(model)), replace=False)
        absent = [next_id + 10 + j for j in range(64)]
        doc_keys = [dk(int(i)) for i in sample] + [dk(i) for i in absent]
        rows = client.multi_read(table, doc_keys)
        for i, row in zip(sample.tolist(), rows):
            require(row is not None, f"acknowledged key {i} not readable")
            require(f_n(row) == model[i],
                    f"key {i}: read {f_n(row)}, wrote {model[i]}")
        require(all(r is None for r in rows[len(sample):]),
                "an absent key was read back")

        ql = QLProcessor(client)
        ql.execute("USE smoke")
        rs = ql.execute("SELECT count(*), sum(n), min(n), max(n) FROM "
                        "usertable WHERE n >= ?", [AGG_THRESHOLD])
        n_vals = [nv for _f, nv in model.values() if nv >= AGG_THRESHOLD]
        want_agg = [len(n_vals), sum(n_vals), min(n_vals), max(n_vals)]
        require(rs.rows[0] == want_agg,
                f"aggregate {rs.rows[0]} != model {want_agg}")
        lo, hi = FILTER_RANGE
        got = sorted(f_n(r) for r in client.scan(
            table, filters=[["n", ">=", lo], ["n", "<", hi]]))
        want = sorted(fn for fn in model.values() if lo <= fn[1] < hi)
        require(got == want, f"range scan: {len(got)} rows vs model "
                             f"{len(want)}")

        # every acknowledged write, from all three replicas' tablets
        by_tablet = {}
        for i in sample.tolist():
            pk = table.partition_key_for(dk(i))
            t = client.meta_cache.lookup_tablet(table.table_id, pk)
            by_tablet.setdefault(t.tablet_id, []).append(i)
        replicas_checked = 0
        deadline = time.monotonic() + 60
        for tablet_id, ids in by_tablet.items():
            peers = [p for ts in cluster.tservers
                     for p in ts.tablet_manager.peers()
                     if p.tablet_id == tablet_id]
            require(len(peers) == 3, f"{tablet_id}: {len(peers)} replicas")
            for peer in peers:
                while True:
                    got = peer.tablet.multi_read([dk(i) for i in ids])
                    bad = [i for i, r in zip(ids, got)
                           if r is None or f_n(r) != model[i]]
                    if not bad:
                        break
                    # a follower applies a committed entry after the
                    # leader acknowledged it: wait for it, bounded
                    require(time.monotonic() < deadline,
                            f"{tablet_id}: a replica never served "
                            f"{len(bad)} keys, e.g. {bad[:3]}")
                    time.sleep(0.05)
                replicas_checked += 1

        d = phase.delta()
        require(d["offload_decisions_device_total"] >= 1,
                "no tserver compaction was decided on the device")
        require(d["point_read_batched_keys_total"] > 0,
                "multi_read never took the batched device path")
        require(d["pushdown_hits"] > 0, "no pushdown scan hit the device")
        caches = [ts.compactionz().get("device_cache", {})
                  for ts in cluster.tservers]
        return {"rows": n_rows, "acked_writes": acked, "tablets": n_tablets,
                "keys_read_back": len(sample),
                "replicas_checked": replicas_checked,
                "count_star": want_agg[0], "range_scan_rows": len(want),
                "tserver_devices": devices,
                "device_cache_used_bytes": [c.get("used_bytes") for c in caches],
                "device_cache_capacity_bytes": [c.get("capacity_bytes")
                                                for c in caches]}
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------- phase: dist

def _write_runs(data: YcsbRuns, root: str, n_runs: int, rows_per_run: int,
                key_space: int):
    """n_runs input SSTs through the bulk-load encoder; returns paths."""
    from yugabyte_tpu.storage.sst import write_sst_from_packed
    os.makedirs(root)
    paths = []
    for g in range(n_runs):
        run = data.run(rows_per_run, key_space, 1_000_000 * (g + 1))
        p = os.path.join(root, f"{g:06d}.sst")
        write_sst_from_packed(p, run["keys_blob"], run["key_offs"],
                              run["ht"], run["wid"], run["vals_blob"],
                              run["val_offs"])
        paths.append(p)
    return paths


def _all_devices_hold_a_shard(arr, mesh) -> bool:
    held = {s.device for s in arr.addressable_shards}
    return held == set(mesh.devices.flat)


def dist_phase(phase: Phase, devices, seed: int, rows_per_run: int,
               pool_rows_per_run: int, workdir: str) -> dict:
    """One run_compaction_job_dist_native job over the whole mesh against
    the single-device job over the same files, and one CompactionPool wave
    of four tablet jobs against the same four run one after another."""
    from jax.sharding import Mesh
    from yugabyte_tpu.common.hybrid_time import HybridTime
    from yugabyte_tpu.parallel import dist_compact
    from yugabyte_tpu.storage import SSTReader
    from yugabyte_tpu.storage.compaction import (
        run_compaction_job, run_compaction_job_dist_native)
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    from yugabyte_tpu.tserver.compaction_pool import (CompactionPool,
                                                      PoolRequest)

    require(len(devices) >= 4, f"need 4 devices, have {len(devices)}")
    mesh = Mesh(np.asarray(devices[:4]), ("shard",))
    data = YcsbRuns(seed + 2)
    cutoff = HybridTime.from_micros(10_000_000_000).value
    n_total = N_RUNS * rows_per_run
    paths = _write_runs(data, os.path.join(workdir, "dist_in"), N_RUNS,
                        rows_per_run, n_total // 2)

    # where the mesh step's outputs live: wrap the step the job calls
    shard_facts = []
    real = dist_compact.distributed_compact_with_outputs

    def watched(slab, params, mesh_, *a, **kw):
        keep, mk, src_idx, outputs = real(slab, params, mesh_, *a, **kw)
        shard_facts.append(
            _all_devices_hold_a_shard(outputs.cols_dev, mesh_)
            and _all_devices_hold_a_shard(outputs.keep_dev, mesh_))
        return keep, mk, src_idx, outputs

    dist_compact.distributed_compact_with_outputs = watched
    try:
        readers = [SSTReader(p) for p in paths]
        out_dist = os.path.join(workdir, "dist_out")
        os.makedirs(out_dist)
        ids = iter(range(100, 1 << 20))
        t0 = time.monotonic()
        res_dist = run_compaction_job_dist_native(
            readers, out_dist, lambda: next(ids), cutoff, True,
            device=devices[0], mesh=mesh)
        dist_s = time.monotonic() - t0
    finally:
        dist_compact.distributed_compact_with_outputs = real
    require(shard_facts and all(shard_facts),
            "a mesh device holds no shard of the dist step's outputs")
    out_one = os.path.join(workdir, "one_out")
    os.makedirs(out_one)
    ids = iter(range(100, 1 << 20))
    t0 = time.monotonic()
    res_one = run_compaction_job(readers, out_one, lambda: next(ids),
                                 cutoff, True, device=devices[0])
    one_s = time.monotonic() - t0
    for r in readers:
        r.close()
    require(res_dist.rows_out == res_one.rows_out, "survivor counts differ")
    require(_sst_files(res_dist.outputs) == _sst_files(res_one.outputs),
            "mesh job outputs differ from the single-device job's")

    # one pool wave of four tablet jobs vs the same four, sequentially.
    # A correctness run: the four jobs go straight to `pool.submit` from
    # this thread, in however many waves the scheduler makes of them. A
    # server's jobs reach the pool through DB.compact_all() on its
    # compaction threads: benchmarks/drivers/pool.py times that (PR 27).
    pool = CompactionPool(mesh, device=devices[0])
    shared = DeviceSlabCache(devices[0])
    try:
        tablets = {}
        for t in range(4):
            tablets[f"t{t}"] = _write_runs(
                data, os.path.join(workdir, f"pool_in{t}"), N_RUNS,
                pool_rows_per_run, N_RUNS * pool_rows_per_run // 2)
        handles = {}
        snap0 = pool.snapshot()
        t0 = time.monotonic()
        for tid, tpaths in tablets.items():
            rs = [SSTReader(p) for p in tpaths]
            outd = os.path.join(workdir, f"pool_out_{tid}")
            os.makedirs(outd)
            gen = iter(range(100, 1 << 20))
            handles[tid] = (pool.submit(tid, PoolRequest(
                inputs=rs, out_dir=outd,
                new_file_id=lambda it=gen: next(it),
                history_cutoff_ht=cutoff, is_major=True,
                input_ids=list(range(len(rs))),
                device_cache=pool.partition_for(shared, f"db-{tid}", tid))),
                rs)
        pooled = {}
        for tid, (h, rs) in handles.items():
            pooled[tid] = h.result(timeout=1200)
            for r in rs:
                r.close()
        pool_s = time.monotonic() - t0
        snap = {k: pool.snapshot()[k] - snap0[k] for k in
                ("waves", "wave_jobs", "native_completions", "wave_faults")}
        require(snap["wave_jobs"] == 4 and not snap["native_completions"]
                and not snap["wave_faults"],
                f"the four pool jobs did not all ride a device wave: {snap}")
        for tid, tpaths in tablets.items():
            rs = [SSTReader(p) for p in tpaths]
            outd = os.path.join(workdir, f"seq_out_{tid}")
            os.makedirs(outd)
            gen = iter(range(100, 1 << 20))
            seq = run_compaction_job(rs, outd, lambda it=gen: next(it),
                                     cutoff, True, device=devices[0])
            for r in rs:
                r.close()
            require(_sst_files(seq.outputs) ==
                    _sst_files(pooled[tid].outputs),
                    f"pooled job {tid} differs from its sequential run")
    finally:
        pool.shutdown()
    facts = {} if rows_per_run == FULL_ROWS_PER_RUN \
        else {"reduced": DIST_REDUCED}
    return {**facts, "rows": n_total, "rows_out": res_dist.rows_out,
            "dist_s": round(dist_s, 1), "single_device_s": round(one_s, 1),
            "byte_identical_to_single_device": True,
            "all_devices_hold_shards": True,
            "pool_jobs": 4, "pool_rows_per_job": N_RUNS * pool_rows_per_run,
            "pool_s": round(pool_s, 1),
            "pool_identical_to_sequential": True,
            "pool_waves": snap["waves"], "pool_wave_jobs": snap["wave_jobs"],
            "pool_native_completions": snap["native_completions"]}


# ----------------------------------------------------------------------- main

def run(args) -> dict:
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    require(device["platform"] == "tpu",
            f"no TPU: jax.devices()[0].platform is {device['platform']!r}")
    require(len(devices) == args.chips,
            f"--chips {args.chips} but JAX sees {len(devices)} devices")
    clock = CompileClock()
    root = tempfile.mkdtemp(prefix="chip-smoke-", dir=os.path.dirname(
        os.path.abspath(__file__)))
    try:
        if args.chips == 4:
            run_phase("dist", clock, devices[0], dist_phase, devices,
                      args.seed, DIST_ROWS_PER_RUN, POOL_ROWS_PER_RUN,
                      os.path.join(root, "dist"))
            return device
        run_phase("storage", clock, devices[0], storage_phase, devices[0],
                  args.seed, ROWS_PER_RUN, os.path.join(root, "storage"))
        run_phase("cluster", clock, devices[0], cluster_phase,
                  device["platform"], args.seed, CLUSTER_ROWS,
                  os.path.join(root, "cluster"), CLUSTER_TABLETS)
        return device
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    device = None
    try:
        device = run(args)
    finally:
        if device is None:
            # not a handler: the failure propagates (traceback, non-zero
            # exit); this only names it on stdout for whoever reads lines
            err = sys.exc_info()[1]
            print(json.dumps({"ok": False,
                              "reason": f"{type(err).__name__}: {err}"}),
                  flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
