"""yb-admin: cluster administration CLI.

Capability parity with the reference (ref: src/yb/tools/yb-admin_cli.cc /
yb-admin_client.cc — table listing/inspection, tablet ops, flush/compact,
snapshot create/list/delete and export/import for backup-restore).

Usage: python -m yugabyte_tpu.tools.yb_admin --master <host:port> <cmd> ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from yugabyte_tpu.client.client import YBClient
from yugabyte_tpu.client.session import YBSession
from yugabyte_tpu.common.hybrid_time import HybridTime
from yugabyte_tpu.common.wire import schema_from_wire
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.utils import jsonutil
from yugabyte_tpu.utils.status import StatusError


# flush_tablet / compact_tablet return when the work is done: a major
# compaction outlasts the default RPC deadline by its nature, and its
# first run of a shape bucket on a TPU pays the kernel compile besides
_TABLET_OP_TIMEOUT_S = 1800.0


def _p(obj) -> None:
    print(json.dumps(obj, indent=2, default=lambda b: b.hex()
                     if isinstance(b, bytes) else str(b)))


class AdminClient:
    def __init__(self, master_addrs: List[str]):
        self.client = YBClient(master_addrs)
        self.m = self.client._messenger
        self.masters = master_addrs

    def master_call(self, mth, **kw):
        return self.client._master_call(mth, **kw)

    # ------------------------------------------------------------- inspect
    def list_tables(self, namespace: Optional[str]) -> None:
        _p(self.master_call("list_tables", namespace=namespace))

    def list_tservers(self) -> None:
        _p(self.master_call("list_tservers"))

    def table_info(self, namespace: str, name: str) -> None:
        meta = self.master_call("get_table", namespace=namespace, name=name)
        locs = self.master_call("get_table_locations",
                                table_id=meta["table_id"])
        _p({"table": meta, "locations": locs})

    # ----------------------------------------------------------------- ops
    def _each_leader(self, namespace: str, name: str, mth: str) -> None:
        meta = self.master_call("get_table", namespace=namespace, name=name)
        locs = self.master_call("get_table_locations",
                                table_id=meta["table_id"])
        for loc in locs:
            addrs = [r["addr"] for r in loc["replicas"]
                     if r["server_id"] == loc["leader"]]
            if addrs and addrs[0]:
                self.m.call(addrs[0], "tserver", mth,
                            timeout_s=_TABLET_OP_TIMEOUT_S,
                            tablet_id=loc["tablet_id"])
        print(f"{mth} issued to {len(locs)} tablets")

    def flush_table(self, namespace: str, name: str) -> None:
        self._each_leader(namespace, name, "flush_tablet")

    def compact_table(self, namespace: str, name: str) -> None:
        self._each_leader(namespace, name, "compact_tablet")

    def split_tablet(self, tablet_id: str) -> None:
        _p(self.master_call("split_tablet", tablet_id=tablet_id))

    # ------------------------------------------------------------ snapshots
    def create_snapshot(self, namespace: str, name: str) -> None:
        _p(self.master_call("create_table_snapshot", namespace=namespace,
                            name=name))

    def list_snapshots(self) -> None:
        _p(self.master_call("list_snapshots"))

    def delete_snapshot(self, snapshot_id: str) -> None:
        self.master_call("delete_snapshot", snapshot_id=snapshot_id)
        print(f"snapshot {snapshot_id} deleted")

    def export_snapshot(self, snapshot_id: str, out_dir: str) -> None:
        """Pull one replica's snapshot files per tablet into out_dir (ref
        yb-admin export_snapshot producing a SnapshotInfoPB + data)."""
        meta = self.master_call("get_snapshot", snapshot_id=snapshot_id)
        tservers = self.master_call("list_tservers")
        os.makedirs(out_dir, exist_ok=True)
        for tablet_id in meta["tablet_ids"]:
            exported = False
            for ts in tservers:
                try:
                    snaps = self.m.call(ts["addr"], "tserver",
                                        "list_tablet_snapshots",
                                        tablet_id=tablet_id)
                except StatusError:
                    continue
                if snapshot_id not in snaps:
                    continue
                manifest = self.m.call(ts["addr"], "tserver",
                                       "snapshot_manifest",
                                       tablet_id=tablet_id,
                                       snapshot_id=snapshot_id)
                tdir = os.path.join(out_dir, "tablets", tablet_id)
                for relpath, size in manifest:
                    out = os.path.join(tdir, relpath)
                    os.makedirs(os.path.dirname(out), exist_ok=True)
                    with open(out, "wb") as f:
                        off = 0
                        while off < size:
                            chunk = self.m.call(
                                ts["addr"], "tserver",
                                "fetch_snapshot_file",
                                tablet_id=tablet_id,
                                snapshot_id=snapshot_id,
                                relpath=relpath, offset=off,
                                length=1 << 20)
                            if not chunk:
                                break
                            f.write(chunk)
                            off += len(chunk)
                exported = True
                break
            if not exported:
                from yugabyte_tpu.utils.status import Status
                raise StatusError(Status.NotFound(
                    f"no tserver holds snapshot {snapshot_id} of "
                    f"tablet {tablet_id}"))
        with open(os.path.join(out_dir, "snapshot.json"), "w") as f:
            f.write(jsonutil.dumps(meta))
        print(f"exported snapshot {snapshot_id} "
              f"({len(meta['tablet_ids'])} tablets) to {out_dir}")

    def import_snapshot(self, export_dir: str, namespace: str,
                        name: str,
                        read_micros: Optional[int] = None) -> None:
        """Restore an exported snapshot into a NEW table: open the exported
        LSM files offline, resolve rows at the snapshot point, and bulk
        insert (ref yb-admin import_snapshot + restore flow).

        read_micros: PITR — resolve rows AT that time instead of the
        snapshot tip. The snapshot's LSM files carry full MVCC history,
        so reading at an earlier HybridTime reconstructs that exact
        state (including rows later deleted)."""
        meta = jsonutil.read_file(os.path.join(export_dir, "snapshot.json"))
        schema = schema_from_wire(meta["schema"])
        try:
            self.client.create_namespace(namespace)
        except StatusError:
            pass
        table = self.client.create_table(
            namespace, name, schema, num_tablets=len(meta["tablet_ids"]))
        from yugabyte_tpu.docdb.doc_rowwise_iterator import (
            DocRowwiseIterator)
        from yugabyte_tpu.storage.db import DB, DBOptions
        session = YBSession(self.client)
        n = 0
        key_names = [c.name for c in schema.hash_columns] + \
            [c.name for c in schema.range_columns]
        for tablet_id in meta["tablet_ids"]:
            regular = os.path.join(export_dir, "tablets", tablet_id,
                                   "regular")
            db = DB(regular, DBOptions(auto_compact=False))
            read_ht = (HybridTime.from_micros(read_micros)
                       if read_micros is not None else HybridTime.kMax)
            try:
                for row in DocRowwiseIterator(db, schema, read_ht):
                    d = row.to_dict(schema)
                    dk = DocKey(
                        hash_components=tuple(
                            d[c.name] for c in schema.hash_columns),
                        range_components=tuple(
                            d[c.name] for c in schema.range_columns))
                    values = {k: v for k, v in d.items()
                              if k not in key_names and v is not None}
                    session.apply(table, QLWriteOp(WriteOpKind.INSERT, dk,
                                                   values))
                    n += 1
                    if n % 512 == 0:
                        session.flush()
            finally:
                db.close()
        session.flush()
        print(f"imported {n} rows into {namespace}.{name}")

    # -------------------------------------------------------------- PITR
    def create_snapshot_schedule(self, namespace: str, name: str,
                                 interval_s: float,
                                 retention_s: float) -> None:
        _p(self.master_call("create_snapshot_schedule", namespace=namespace,
                            name=name, interval_s=interval_s,
                            retention_s=retention_s))

    def list_snapshot_schedules(self) -> None:
        _p(self.master_call("list_snapshot_schedules"))

    def delete_snapshot_schedule(self, schedule_id: str) -> None:
        self.master_call("delete_snapshot_schedule",
                         schedule_id=schedule_id)
        print(f"schedule {schedule_id} deleted")

    def restore_to_time(self, namespace: str, name: str,
                        restore_micros: int, new_name: str) -> None:
        """PITR restore: the earliest snapshot covering restore_micros is
        exported and re-read AT that time into a new table (ref
        yb-admin restore_snapshot_schedule <id> <time>; the reference
        restores in place — restoring into a new table keeps the live
        table available for comparison, like a clone)."""
        import tempfile
        snap = self.master_call("pick_restore_snapshot",
                                namespace=namespace, name=name,
                                restore_micros=int(restore_micros))
        export_dir = tempfile.mkdtemp(prefix="ybtpu-pitr-")
        try:
            self.export_snapshot(snap["snapshot_id"], export_dir)
            self.import_snapshot(export_dir, namespace, new_name,
                                 read_micros=int(restore_micros))
        finally:
            import shutil
            shutil.rmtree(export_dir, ignore_errors=True)
        print(f"restored {namespace}.{name} at t={restore_micros} "
              f"into {namespace}.{new_name} "
              f"(snapshot {snap['snapshot_id']})")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="yb-admin")
    ap.add_argument("--master", action="append", required=True,
                    help="master address host:port (repeatable)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("list_tservers")
    p = sub.add_parser("list_tables")
    p.add_argument("namespace", nargs="?")
    for c in ("table_info", "flush_table", "compact_table",
              "create_snapshot"):
        p = sub.add_parser(c)
        p.add_argument("namespace")
        p.add_argument("name")
    p = sub.add_parser("split_tablet")
    p.add_argument("tablet_id")
    sub.add_parser("list_snapshots")
    p = sub.add_parser("delete_snapshot")
    p.add_argument("snapshot_id")
    p = sub.add_parser("export_snapshot")
    p.add_argument("snapshot_id")
    p.add_argument("out_dir")
    p = sub.add_parser("import_snapshot")
    p.add_argument("export_dir")
    p.add_argument("namespace")
    p.add_argument("name")
    p = sub.add_parser("create_snapshot_schedule")
    p.add_argument("namespace")
    p.add_argument("name")
    p.add_argument("interval_s", type=float)
    p.add_argument("retention_s", type=float)
    sub.add_parser("list_snapshot_schedules")
    p = sub.add_parser("delete_snapshot_schedule")
    p.add_argument("schedule_id")
    p = sub.add_parser("restore_to_time")
    p.add_argument("namespace")
    p.add_argument("name")
    p.add_argument("restore_micros", type=int)
    p.add_argument("new_name")
    args = ap.parse_args(argv)
    admin = AdminClient(args.master)
    try:
        if args.cmd == "list_tservers":
            admin.list_tservers()
        elif args.cmd == "list_tables":
            admin.list_tables(args.namespace)
        elif args.cmd == "table_info":
            admin.table_info(args.namespace, args.name)
        elif args.cmd == "flush_table":
            admin.flush_table(args.namespace, args.name)
        elif args.cmd == "compact_table":
            admin.compact_table(args.namespace, args.name)
        elif args.cmd == "split_tablet":
            admin.split_tablet(args.tablet_id)
        elif args.cmd == "create_snapshot":
            admin.create_snapshot(args.namespace, args.name)
        elif args.cmd == "list_snapshots":
            admin.list_snapshots()
        elif args.cmd == "delete_snapshot":
            admin.delete_snapshot(args.snapshot_id)
        elif args.cmd == "export_snapshot":
            admin.export_snapshot(args.snapshot_id, args.out_dir)
        elif args.cmd == "import_snapshot":
            admin.import_snapshot(args.export_dir, args.namespace,
                                  args.name)
        elif args.cmd == "create_snapshot_schedule":
            admin.create_snapshot_schedule(args.namespace, args.name,
                                           args.interval_s,
                                           args.retention_s)
        elif args.cmd == "list_snapshot_schedules":
            admin.list_snapshot_schedules()
        elif args.cmd == "delete_snapshot_schedule":
            admin.delete_snapshot_schedule(args.schedule_id)
        elif args.cmd == "restore_to_time":
            admin.restore_to_time(args.namespace, args.name,
                                  args.restore_micros, args.new_name)
    finally:
        admin.client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
