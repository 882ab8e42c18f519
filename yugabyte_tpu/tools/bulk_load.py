"""yb-bulk-load: CSV -> table loader, in the reference's two forms.

**Through the client write path** (`load_csv`, the default):

Capability parity with the reference's bulk loader (ref:
src/yb/tools/yb_bulk_load.cc / bulk_load_tool.cc — partition input rows,
batch them per tablet, drive them in at full write-path speed). Rows ride
the ordinary client session (meta-cache routing + per-tablet batching,
client/session.py), so everything downstream — replication, indexes,
backpressure — behaves exactly as production writes do.

CSV shape: a header row naming columns; every key column of the table must
be present. Values parse by the column's schema type.

**As an import** (`import_columns` / `--import`, ref: yb_bulk_load.cc
generates SSTs by partition, and the tserver's ImportData installs them in
every replica): rows are partitioned by the table's own hash, packed per
tablet in the DocDB encoding (one liveness entry and one entry a value
column, exactly the bytes an INSERT writes), and handed to EVERY replica of
each tablet through the tserver's `import_data` call, which installs the
run as an L0 SST of the replica's regular DB (`DB.ingest_packed`). One
hybrid time covers the whole import; every replica's clock is moved past
it, so every later write is newer. The import goes around raft: all
replicas of every tablet must be up, or the import fails before it starts.
Nothing is sorted here: the native SST encoder orders a run.

Usage: python -m yugabyte_tpu.tools.bulk_load --master <host:port> \
           --namespace db --table t --csv data.csv [--batch 512] [--import]
"""

from __future__ import annotations

import argparse
import csv
import json
import struct
import sys
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from yugabyte_tpu.client.client import YBClient
from yugabyte_tpu.client.session import YBSession
from yugabyte_tpu.common.schema import DataType
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.utils.status import Status, StatusError


_INT_FAMILY = (DataType.INT32, DataType.INT64, DataType.TIMESTAMP,
               DataType.DECIMAL, DataType.DATE)


def _parse(raw: str, dtype: DataType, scale: int = 0):
    if raw == "":
        return None
    if dtype is DataType.DECIMAL:
        import decimal
        return int(decimal.Decimal(raw).scaleb(scale).to_integral_exact())
    if dtype is DataType.DATE:
        import datetime
        return (datetime.date.fromisoformat(raw.strip())
                - datetime.date(1970, 1, 1)).days
    if dtype in (DataType.INT32, DataType.INT64, DataType.TIMESTAMP):
        return int(raw)
    if dtype in (DataType.FLOAT, DataType.DOUBLE):
        return float(raw)
    if dtype == DataType.BOOL:
        return raw.strip().lower() in ("1", "true", "t", "yes")
    if dtype == DataType.BINARY:
        return bytes.fromhex(raw)
    return raw  # STRING


def load_csv(client: YBClient, namespace: str, table_name: str,
             csv_path: str, batch: int = 512) -> dict:
    """Load every CSV row as an INSERT; returns {rows, seconds, rows_per_sec}."""
    table = client.open_table(namespace, table_name)
    schema = table.schema
    key_cols = [c.name for c in
                schema.hash_columns + schema.range_columns]
    value_cols = {c.name: c.type for c in schema.value_columns
                  if not c.dropped}
    types = {c.name: c.type for c in schema.columns}
    scales = {c.name: c.scale for c in schema.columns}
    session = YBSession(client)
    n = 0
    t0 = time.time()
    with open(csv_path, newline="") as f:
        reader = csv.DictReader(f)
        missing = [k for k in key_cols if k not in (reader.fieldnames or ())]
        if missing:
            raise ValueError(f"CSV lacks key columns: {missing}")
        for row in reader:
            n_hash = schema.num_hash_key_columns
            hashed = tuple(_parse(row[k], types[k], scales[k])
                           for k in key_cols[:n_hash])
            ranged = tuple(_parse(row[k], types[k], scales[k])
                           for k in key_cols[n_hash:])
            dk = DocKey(hash_components=hashed, range_components=ranged)
            values = {c: _parse(row[c], t, scales[c])
                      for c, t in value_cols.items()
                      if c in row}
            session.apply(table, QLWriteOp(WriteOpKind.INSERT, dk,
                                           values=values))
            n += 1
            if n % batch == 0:
                session.flush()
    session.flush()
    dt = time.time() - t0
    return {"rows": n, "seconds": round(dt, 2),
            "rows_per_sec": round(n / dt, 1) if dt else 0.0}


# ------------------------------------------------------------ the import
_I64_BIAS = np.uint64(1 << 63)
_TAG_INT64, _TAG_STRING, _TAG_NULL = 0x49, 0x53, 0x24


def _int_payloads(vals: np.ndarray) -> np.ndarray:
    """[n, 9] uint8: kInt64 + the biased big-endian integer."""
    out = np.empty((len(vals), 9), dtype=np.uint8)
    out[:, 0] = _TAG_INT64
    out[:, 1:] = (vals.astype(np.int64).view(np.uint64) ^ _I64_BIAS
                  ).astype(">u8").view(np.uint8).reshape(-1, 8)
    return out


def _encode_values(col) -> tuple:
    """(blob uint8, offsets int64) of a column's encoded DocDB values: an
    integer array and a sequence of str go by numpy, anything else (and
    any None) value by value through the program's own encoder."""
    if isinstance(col, np.ndarray) and col.dtype.kind in "iu":
        return (_int_payloads(col).reshape(-1),
                np.arange(len(col) + 1, dtype=np.int64) * 9)
    if len(col) and all(isinstance(v, str) for v in col):
        raw = [v.encode("utf-8") for v in col]
        lens = np.fromiter(map(len, raw), dtype=np.int64, count=len(raw))
        body = np.frombuffer(b"".join(raw), dtype=np.uint8)
        if not (body == 0).any():       # no byte needs the 00 01 escape
            offs = np.concatenate([[0], np.cumsum(lens + 3)])
            blob = np.zeros(int(offs[-1]), dtype=np.uint8)
            blob[offs[:-1]] = _TAG_STRING
            rows = np.repeat(np.arange(len(raw), dtype=np.int64), lens)
            blob[np.arange(len(body), dtype=np.int64) + 3 * rows + 1] = body
            return blob, offs
    from yugabyte_tpu.docdb.value import Value
    enc = [Value(primitive=(v.item() if isinstance(v, np.generic) else v)
                 ).encode() for v in col]
    lens = np.fromiter(map(len, enc), dtype=np.int64, count=len(enc))
    return (np.frombuffer(b"".join(enc), dtype=np.uint8),
            np.concatenate([[0], np.cumsum(lens)]))


def _fnv1a64_fold16(mat: np.ndarray) -> np.ndarray:
    """common/partition.hash_column_compound_value over each row of a
    [n, L] byte matrix (the encoded hashed columns)."""
    h = np.full(len(mat), 0xCBF29CE484222325, dtype=np.uint64)
    prime = np.uint64(0x100000001B3)
    with np.errstate(over="ignore"):
        for j in range(mat.shape[1]):
            h = (h ^ mat[:, j].astype(np.uint64)) * prime
    h ^= h >> np.uint64(32)
    h ^= h >> np.uint64(16)
    return (h & np.uint64(0xFFFF)).astype(np.uint16)


def encode_doc_keys(schema, columns: Dict[str, Sequence]) -> tuple:
    """(key matrix [n, L] uint8, hash codes uint16) for tables whose key
    columns are all integers (every key then has one length); None when a
    key column is not (the caller encodes key by key)."""
    keys = schema.hash_columns + schema.range_columns
    if not schema.hash_columns or not all(
            isinstance(columns[k.name], np.ndarray)
            and columns[k.name].dtype.kind in "iu" for k in keys):
        return None
    n = len(columns[keys[0].name])
    hashed = np.concatenate([_int_payloads(columns[k.name])
                             for k in schema.hash_columns], axis=1)
    codes = _fnv1a64_fold16(hashed)
    parts = [np.full((n, 1), 0x47, np.uint8),              # kUInt16Hash
             codes.astype(">u2").view(np.uint8).reshape(n, 2),
             hashed, np.full((n, 1), 0x21, np.uint8)]      # kGroupEnd
    parts += [_int_payloads(columns[k.name]) for k in schema.range_columns]
    parts.append(np.full((n, 1), 0x21, np.uint8))
    return np.concatenate(parts, axis=1), codes


def _self_check(schema, columns, key_mat, codes) -> None:
    """The vectorised bytes are what the program's encoder writes."""
    keys = schema.hash_columns + schema.range_columns
    nh = schema.num_hash_key_columns
    for i in {0, len(key_mat) // 2, len(key_mat) - 1}:
        comps = [int(columns[k.name][i]) for k in keys]
        dk = DocKey(hash_components=tuple(comps[:nh]),
                    range_components=tuple(comps[nh:]))
        if dk.encode() != key_mat[i].tobytes() \
                or dk.hash_code != int(codes[i]):
            raise RuntimeError("bulk import: key encoding drifted")


def pack_tablet_run(schema, key_mat: np.ndarray,
                    columns: Dict[str, Sequence]) -> dict:
    """One tablet's rows as a packed run: for every row the liveness entry
    and one entry a value column present in `columns`, column-major."""
    from yugabyte_tpu.docdb.doc_operations import (column_key_suffix,
                                                   kLivenessColumnId)
    n, klen = key_mat.shape
    kblobs, vblobs, voffs, wids = [], [], [], []
    entries = [(kLivenessColumnId, None)] + [
        (schema.column_id(c.name), c.name) for c in schema.value_columns
        if c.name in columns]
    base = 0
    for wid, (cid, name) in enumerate(entries):
        suf = np.frombuffer(column_key_suffix(cid), dtype=np.uint8)
        kblobs.append(np.concatenate(
            [key_mat, np.tile(suf, (n, 1))], axis=1).reshape(-1))
        if name is None:
            blob = np.full(n, _TAG_NULL, np.uint8)
            offs = np.arange(n + 1, dtype=np.int64)
        else:
            blob, offs = _encode_values(columns[name])
        vblobs.append(blob)
        voffs.append(offs[:-1] + base)
        base += int(offs[-1])
        wids.append(np.full(n, wid, np.uint32))
    voffs.append(np.asarray([base], dtype=np.int64))
    total = n * len(entries)
    return {"n": total,
            "keys_blob": np.concatenate(kblobs).tobytes(),
            "key_offs": np.arange(total + 1, dtype=np.int64) * (klen + 3),
            "vals_blob": np.concatenate(vblobs).tobytes(),
            "val_offs": np.concatenate(voffs),
            "wid": np.concatenate(wids)}


def table_tablets(client: YBClient, table) -> list:
    """Every tablet of the table, in partition order."""
    out, cursor = [], b""
    while True:
        t = client.meta_cache.lookup_tablet(table.table_id, cursor)
        out.append(t)
        if not t.partition.end:
            return out
        cursor = t.partition.end


def import_columns(client: YBClient, table,
                   columns: Dict[str, Sequence],
                   timeout_s: float = 300.0) -> dict:
    """Import rows given as parallel columns (name -> integer numpy array
    or sequence of values; every key column, any value columns) into
    every replica of every tablet. Returns what was done; raises before
    anything is installed if a replica is missing."""
    schema = table.schema
    t0 = time.monotonic()
    enc = encode_doc_keys(schema, columns)
    if enc is None:
        raise ValueError("bulk import needs a hash-partitioned table whose "
                         "key columns are all integers")
    key_mat, codes = enc
    _self_check(schema, columns, key_mat, codes)
    tablets = table_tablets(client, table)
    starts = np.asarray([struct.unpack(">H", t.partition.start)[0]
                         if t.partition.start else 0 for t in tablets])
    owner = np.searchsorted(starts, codes, side="right") - 1
    messenger = client._messenger
    for t in tablets:
        if len(t.replicas) < 1 or any(not r.addr for r in t.replicas):
            raise StatusError(Status.IllegalState(
                f"tablet {t.tablet_id}: a replica has no address"))
    pack_s = 0.0
    ht = None
    rows = replicas = entries = 0

    def install(run, tablet_id, addr, at):
        return messenger.call(
            addr, "tserver", "import_data", timeout_s=timeout_s,
            tablet_id=tablet_id, ht=at, n=run["n"],
            keys_blob=run["keys_blob"], key_offs=run["key_offs"].tobytes(),
            vals_blob=run["vals_blob"], val_offs=run["val_offs"].tobytes(),
            wid=run["wid"].tobytes())["ht"]

    # a tablet's replicas take their copies side by side (the SST encoder
    # is native code on three different servers); the very first import
    # goes alone, because its replica's clock names the hybrid time
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=3,
                            thread_name_prefix="bulk-import") as pool:
        for i, t in enumerate(tablets):
            idx = np.flatnonzero(owner == i)
            if not len(idx):
                continue
            p0 = time.monotonic()
            sub = {name: (col[idx] if isinstance(col, np.ndarray)
                          else [col[j] for j in idx.tolist()])
                   for name, col in columns.items()}
            run = pack_tablet_run(schema, key_mat[idx], sub)
            pack_s += time.monotonic() - p0
            addrs = [r.addr for r in t.replicas]
            if ht is None:
                ht = install(run, t.tablet_id, addrs.pop(0), None)
            list(pool.map(lambda a: install(run, t.tablet_id, a, ht), addrs))
            replicas += len(t.replicas)
            rows += len(idx)
            entries += run["n"] * len(t.replicas)
    return {"rows": rows, "tablets": len(tablets),
            "replica_imports": replicas, "entries": entries,
            "pack_s": pack_s, "seconds": time.monotonic() - t0, "ht": ht}


def import_csv(client: YBClient, namespace: str, table_name: str,
               csv_path: str) -> dict:
    """The CSV of `load_csv`, imported."""
    table = client.open_table(namespace, table_name)
    schema = table.schema
    with open(csv_path, newline="") as f:
        reader = csv.DictReader(f)
        names = [c.name for c in schema.columns
                 if not c.dropped and c.name in (reader.fieldnames or ())]
        raw = {name: [] for name in names}
        for row in reader:
            for name in names:
                raw[name].append(row[name])
    columns = {}
    for name in names:
        c = schema.column(name)
        vals = [_parse(v, c.type, c.scale) for v in raw[name]]
        if c.type in _INT_FAMILY and all(v is not None for v in vals):
            columns[name] = np.asarray(vals, dtype=np.int64)
        else:
            columns[name] = vals
    return import_columns(client, table, columns)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="yb-bulk-load")
    ap.add_argument("--master", required=True, action="append",
                    help="master address (repeatable)")
    ap.add_argument("--namespace", required=True)
    ap.add_argument("--table", required=True)
    ap.add_argument("--csv", required=True)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--import", dest="as_import", action="store_true",
                    help="pack SSTs per tablet and install them in every "
                         "replica (tserver import_data) instead of "
                         "writing through the client path")
    args = ap.parse_args(argv)
    client = YBClient(args.master)
    try:
        if args.as_import:
            stats = import_csv(client, args.namespace, args.table,
                               args.csv)
        else:
            stats = load_csv(client, args.namespace, args.table, args.csv,
                             args.batch)
        print(json.dumps(stats))
        return 0
    except (StatusError, ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    finally:
        client.close()


if __name__ == "__main__":
    sys.exit(main())
