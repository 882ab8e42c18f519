"""Driver for cells whose traffic is TPC-H Q1 and Q6 over `lineitem` on an
in-process RF3 `MiniCluster` (configuration `tpch-lineitem-rf3`).

Set-up creates the table through the query layer, imports the population
with the operator's bulk import (`tools/bulk_load.import_columns`: packed
SSTs into every replica), runs RF1 and RF2 once through the client path
(raft-acknowledged inserts and deletes), flushes and compacts every replica
to one file, and has every replica answer once so that its slab and value
words are resident (`_stage_every_replica`). The window is
`streams` closed-loop query streams, one statement in flight a stream,
alternating Q1 and Q6 with substitution parameters drawn from the seed,
issued as statements to the YCQL processor, which plans them onto
`YBClient.scan_aggregate`.

`correct`: every answer of the window against `reference_tpch.py` (exact),
and after the window the same two queries answered by every replica of
every tablet, combined a tserver, against the reference (exact).
"""

import gc
import sys
import threading
import time

import numpy as np

from benchmarks import datagen, datagen_tpch, reference_tpch, roofline_scan
from benchmarks.program import ZERO_COUNTERS

CONTROLS = ("rf1_order_withheld",)
KEYSPACE, TABLE = "tpch", "lineitem"


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sizes = ctx.sizes
        self.traffic = ctx.traffic
        self.cluster = None
        self.epoch = 0
        self.resends = 0
        self._note_lock = threading.Lock()
        self._seen = None

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        from yugabyte_tpu.common import schema as schema_mod
        ctx = self.ctx
        ctx.require(hasattr(schema_mod.DataType, "DECIMAL"),
                    "this program has no DECIMAL / DATE / CHAR column "
                    "types: the tpch-lineitem-rf3 deployment cannot run "
                    "on it")
        from yugabyte_tpu.integration.mini_cluster import (
            MiniCluster, MiniClusterOptions)
        from yugabyte_tpu.storage import offload_policy  # noqa: F401 (defines the flag)
        from yugabyte_tpu.tools import bulk_load
        from yugabyte_tpu.utils import flags
        from yugabyte_tpu.yql.cql.executor import QLProcessor

        dep = ctx.config["deployment"]
        ctx.require(flags.get_flag("device_offload_mode") == "auto",
                    "device_offload_mode is not at its default")
        ctx.require(hasattr(bulk_load, "import_columns"),
                    "tools/bulk_load.py has no import form")
        self.cluster = MiniCluster(MiniClusterOptions(
            num_masters=int(dep["masters"]),
            num_tservers=int(dep["tservers"]),
            fs_root=ctx.workdir)).start()
        platform = ctx.devices[0].platform
        devices = [ts.compactionz()["device"] for ts in self.cluster.tservers]
        ctx.require(all(d["platform"] == platform for d in devices),
                    f"tserver devices {devices}, expected {platform}")
        self.client = self.cluster.new_client()
        self.proc = QLProcessor(self.client)
        self.proc.execute(f"CREATE KEYSPACE {KEYSPACE}")
        n_tablets = int(self.sizes["tablets"])
        self.proc.execute(datagen_tpch.create_table_cql(KEYSPACE, TABLE,
                                                        n_tablets))
        self.table = self.client.open_table(KEYSPACE, TABLE)
        self.cluster.wait_all_replicas_running(self.table.table_id)

        t0 = time.monotonic()
        gen = datagen_tpch.Lineitem(ctx.seed,
                                    float(self.sizes["scale_factor"]))
        population = gen.initial()
        t_gen = time.monotonic()
        imported = bulk_load.import_columns(self.client, self.table,
                                            population)
        t_import = time.monotonic()
        ctx.require(imported["replica_imports"] == n_tablets * int(
            dep["replication_factor"]),
            f"replica imports: {imported['replica_imports']}")
        inserted = gen.rf1()
        deleted = gen.rf2_orderkeys()
        refresh = self._refresh(population, inserted, deleted)
        t_refresh = time.monotonic()
        withheld = None
        if ctx.control == "rf1_order_withheld":
            # the control: the reference never hears of one RF1 order
            keys = np.unique(inserted["l_orderkey"])
            withheld = int(keys[datagen.rng_for(ctx.seed, 23).integers(
                0, len(keys))])
            inserted = datagen_tpch.rows_of(
                inserted, inserted["l_orderkey"] != withheld)
        self.rows = reference_tpch.apply_refresh(population, inserted,
                                                 deleted.tolist())
        del population
        self._wait_all_applied()
        self._on_each_replica(lambda tablet: tablet.flush())
        self._on_each_replica(lambda tablet: tablet.compact())
        t_compact = time.monotonic()
        self._stage_every_replica()
        t_stage = time.monotonic()
        leaders = [[p for p in ts.tablet_manager.peers()
                    if p.raft.is_leader()] for ts in self.cluster.tservers]
        self.entries_per_query = int(sum(
            p.tablet.regular_db.approx_row_entries()
            for ps in leaders for p in ps))
        key_bytes = len(self._doc_key(1, 1).encode()) + 3
        self.query_bytes = roofline_scan.scan_query_bytes(
            self.entries_per_query, key_bytes)
        refresh["order_withheld"] = withheld
        ctx.log({"load": {
            "rows": int(len(self.rows["l_orderkey"])),
            "generate_s": t_gen - t0, "bulk_load_s": t_import - t_gen,
            "bulk_load": imported, "refresh_s": t_refresh - t_import,
            "refresh": refresh, "ops_sent_again": self.resends,
            "flush_compact_s": t_compact - t_refresh,
            "stage_replicas_s": t_stage - t_compact,
            "entries_per_query": self.entries_per_query,
            "query_bytes": self.query_bytes, "tablets": n_tablets,
            "leaders_per_tserver": [len(ps) for ps in leaders],
            "live_files_per_replica": sorted(
                p.tablet.regular_db.n_live_files
                for ts in self.cluster.tservers
                for p in ts.tablet_manager.peers())}})
        self.n_streams = int(self.traffic["streams"])
        self.table_name = f"{KEYSPACE}.{TABLE}"
        gc.collect()
        gc.freeze()

    @staticmethod
    def _doc_key(orderkey: int, linenumber: int):
        from yugabyte_tpu.docdb.doc_key import DocKey
        return DocKey(hash_components=(int(orderkey),),
                      range_components=(int(linenumber),))

    def _refresh(self, population: dict, inserted: dict, deleted) -> dict:
        """RF1 then RF2, once, through the client path: every insert and
        every delete acknowledged after raft majority commit."""
        from yugabyte_tpu.client.session import YBSession
        from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
        names = [n for n, _t in datagen_tpch.COLUMNS
                 if n not in ("l_orderkey", "l_linenumber")]
        cols = {n: (c.tolist() if isinstance(c, np.ndarray) else c)
                for n, c in inserted.items()}
        ops = [QLWriteOp(WriteOpKind.INSERT,
                         self._doc_key(cols["l_orderkey"][i],
                                       cols["l_linenumber"][i]),
                         {n: cols[n][i] for n in names})
               for i in range(len(cols["l_orderkey"]))]
        gone = np.isin(population["l_orderkey"], deleted)
        dels = [QLWriteOp(WriteOpKind.DELETE_ROW, self._doc_key(ok, ln))
                for ok, ln in zip(population["l_orderkey"][gone].tolist(),
                                  population["l_linenumber"][gone].tolist())]
        session = YBSession(self.client)
        batch = int(self.sizes["refresh_batch"])
        for group in (ops, dels):
            for start in range(0, len(group), batch):
                self._write_batch(session, group[start:start + batch])
        session.close()
        return {"orders_each_way": int(len(deleted)),
                "rows_inserted": len(ops), "rows_deleted": len(dels)}

    def _write_batch(self, session, ops) -> None:
        """One batch, every op acknowledged before the next; an INSERT or
        DELETE sent again writes the same row (as the YCSB load does)."""
        from yugabyte_tpu.client.session import SessionFlushError
        for attempt in range(6):
            for op in ops:
                session.apply(self.table, op)
            try:
                session.flush()
                return
            except SessionFlushError as e:
                ops = [op for _t, op, _e in e.per_op]
                self.resends += len(ops)
                print(f"refresh: {len(ops)} ops not acknowledged (attempt "
                      f"{attempt + 1}): {e.per_op[0][2]}", file=sys.stderr,
                      flush=True)
                time.sleep(1.0 + attempt)
        self.ctx.require(False, f"refresh: {len(ops)} ops never "
                                f"acknowledged")

    def _wait_all_applied(self) -> None:
        """Every replica has applied every committed refresh write before
        the flush: a follower that applied the tail later would hold it
        in its memtable, and would merge two sources if it led later."""
        deadline = time.monotonic() + 120
        by_tablet = {}
        for ts in self.cluster.tservers:
            for p in ts.tablet_manager.peers():
                by_tablet.setdefault(p.tablet_id, []).append(p)
        for tablet_id, peers in by_tablet.items():
            while True:
                progress = [p.raft.commit_progress() for p in peers]
                top = max(c for c, _a in progress)
                if all(a >= top for _c, a in progress):
                    break
                self.ctx.require(time.monotonic() < deadline,
                                 f"{tablet_id}: a replica never caught up")
                time.sleep(0.05)

    def _stage_every_replica(self) -> None:
        """Every replica answers one query of each shape class in set-up,
        so its slab and value words are resident (the compaction's
        write-through leaves the slab; the value words attach on a first
        pushdown scan, which decodes the whole file on the host). A
        deployment's followers serve such scans too; and leadership moves
        in this in-process cluster whenever the host stalls 300 ms
        (PERF.md section 7): a leader elected inside the window then
        answers from a resident slab instead of staging ~500K entries
        inside a request (61 such stagings took one run from 4.3 to 2.7
        queries/s, PERF.md, PR 32)."""
        specs = self._replica_specs({"delta": 90}, {"year": 1994,
                                                    "discount": 6,
                                                    "quantity": 24})
        for ts in self.cluster.tservers:
            for p in ts.tablet_manager.peers():
                for spec in specs:
                    self.ctx.require(
                        p.tablet.scan_aggregate(spec=spec) is not None,
                        f"{p.tablet_id}: a replica answered in rows in "
                        f"set-up")

    def _replica_specs(self, p1: dict, p6: dict):
        """Q1 and Q6 as compiled specs, for a replica asked directly."""
        from yugabyte_tpu.docdb import scan_spec as SS
        day = datagen_tpch.days
        schema = self.table.schema
        one = lambda c: [["col", c]]                        # noqa: E731
        q1 = SS.compile_group_aggregate(
            schema, [["l_shipdate", "<=", day(1998, 12, 1) - p1["delta"]]],
            [["sum", one("l_quantity")], ["sum", one("l_extendedprice")],
             ["sum", [["col", "l_extendedprice"], ["1-", "l_discount"]]],
             ["sum", [["col", "l_extendedprice"], ["1-", "l_discount"],
                      ["1+", "l_tax"]]],
             ["sum", one("l_discount")], ["count", None]],
            ["l_returnflag", "l_linestatus"])[0]
        q6 = SS.compile_group_aggregate(
            schema, [["l_shipdate", ">=", day(p6["year"], 1, 1)],
                     ["l_shipdate", "<", day(p6["year"] + 1, 1, 1)],
                     ["l_discount", ">=", p6["discount"] - 1],
                     ["l_discount", "<=", p6["discount"] + 1],
                     ["l_quantity", "<", p6["quantity"] * 100]],
            [["sum", [["col", "l_extendedprice"], ["col", "l_discount"]]]],
            [])[0]
        return q1, q6

    def _on_each_replica(self, call) -> None:
        for ts in self.cluster.tservers:
            for p in ts.tablet_manager.peers():
                call(p.tablet)

    # ------------------------------------------------------------ the loop
    def _scan_counters(self) -> dict:
        from yugabyte_tpu.ops.scan_group import group_metrics
        return {k: c.value() for k, c in group_metrics().items()}

    def _note(self, tracer) -> None:
        """The grouped kernel's own counters are not in the harness's
        snapshot: their increments since the last query are noted with
        the query, so the traced span gets what moved inside it."""
        with self._note_lock:
            cur = self._scan_counters()
            last = self._seen or cur
            self._seen = cur
            tracer.note(bench_queries=1,
                        bench_rows_in=self.entries_per_query,
                        bench_min_device_bytes=self.query_bytes,
                        bench_scan_dispatches=cur["dispatches"]
                        - last["dispatches"],
                        bench_scan_stage_misses=cur["stage_miss"]
                        - last["stage_miss"])

    def run(self, seconds: float, tracer) -> dict:
        """All streams query until `seconds` have passed; the window
        closes when every stream's query in flight has been answered."""
        self.epoch += 1
        stop = threading.Event()
        logs = [_StreamLog() for _ in range(self.n_streams)]
        threads = [threading.Thread(target=self._stream, name=f"tpch-{w}",
                                    args=(w, stop, logs[w], tracer),
                                    daemon=True)
                   for w in range(self.n_streams)]
        before = self._scan_counters()
        with self._note_lock:
            self._seen = before
        t0 = time.monotonic()
        tracer.timed_start()
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(300)
            self.ctx.require(not t.is_alive(), "a query stream never "
                                               "settled")
        t1 = time.monotonic()
        tracer.timed_stop()
        for log in logs:
            if log.error is not None:
                raise log.error
            for what in log.gave_up[:3]:
                print(f"window: {what}"[:600], file=sys.stderr, flush=True)
        after = self._scan_counters()
        return {"logs": logs, "seconds": t1 - t0,
                "scan_counters": {k: after[k] - before[k] for k in after}}

    def _stream(self, wid: int, stop, log, tracer) -> None:
        try:
            rng = datagen.rng_for(self.ctx.seed, 1000 * self.epoch + 40 + wid)
            turn = wid          # streams start on alternating queries
            while not stop.is_set():
                kind = ("q1", "q6")[turn % 2]
                turn += 1
                if kind == "q1":
                    params = datagen_tpch.q1_params(rng)
                    text = datagen_tpch.q1_statement(self.table_name, params)
                else:
                    params = datagen_tpch.q6_params(rng)
                    text = datagen_tpch.q6_statement(self.table_name, params)
                t0 = time.monotonic()
                try:
                    with self.ctx.span(kind):
                        rs = self.proc.execute(text)
                except Exception as e:  # the client gave the query up
                    log.gave_up.append(f"{kind}: {type(e).__name__}: {e}")
                    log.samples.append((time.monotonic() - t0) * 1e3)
                    continue
                t1 = time.monotonic()
                log.samples.append((t1 - t0) * 1e3)
                log.answers.append((kind, params, rs.rows,
                                    dict(getattr(rs, "pushdown", {}))))
                self._note(tracer)
        except BaseException as e:  # handed to the main thread, which raises
            log.error = e

    # ------------------------------------------------------------- results
    def metrics(self, window: dict, counters: dict) -> dict:
        lat = sorted(s for log in window["logs"] for s in log.samples)
        answered = sum(len(log.answers) for log in window["logs"])
        self.ctx.log({"queries": len(lat), "answered": answered,
                      "window_s": window["seconds"],
                      "query_p50_ms": lat[len(lat) // 2] if lat else None,
                      "scan_counters": window["scan_counters"],
                      "client_retries": self.client.retry_budget.spent_total,
                      "raft_terms": sorted(
                          p.raft.current_term for p in
                          self.cluster.tservers[0].tablet_manager.peers())})
        out = {"ops_per_s": answered / window["seconds"]}
        if lat:
            out["read_p95_ms"] = float(np.percentile(
                np.asarray(lat), 95, method="higher"))
        return out

    def tally(self, window: dict, counters: dict) -> dict:
        """A query the client gave up, a query any of whose tablets
        answered from rows instead of a device partial, and any movement
        of a counter that must stay at zero."""
        logs = window["logs"]
        attempted = sum(len(log.samples) for log in logs)
        gave_up = sum(len(log.gave_up) for log in logs)
        from_rows = sum(1 for log in logs for a in log.answers
                        if a[3].get("from_rows", 0)
                        or not a[3].get("tablets", 0))
        zero = int(sum(counters[name] for name in ZERO_COUNTERS))
        self.ctx.log({"queries_given_up": gave_up,
                      "queries_with_a_tablet_answered_from_rows": from_rows,
                      "zero_counters_moved": zero})
        return {"attempted": int(attempted),
                "failed": int(min(attempted, gave_up + from_rows + zero))}

    def _reference_rows(self, kind: str, params: dict) -> list:
        if kind == "q1":
            return reference_tpch.q1_rows(
                reference_tpch.q1_raw(self.rows, params["delta"]))
        return reference_tpch.q6_rows(reference_tpch.q6_raw(
            self.rows, params["year"], params["discount"],
            params["quantity"]))

    def verify(self, window: dict) -> dict:
        ctx = self.ctx
        answers = [a for log in window["logs"] for a in log.answers]
        cache = {}
        differing = 0
        for kind, params, rows, _push in answers:
            key = (kind, tuple(sorted(params.items())))
            if key not in cache:
                cache[key] = self._reference_rows(kind, params)
            differing += [list(r) for r in rows] != cache[key]
        wrong, replicas, from_rows = self._check_replicas()
        ctx.log({"answers_checked": len(answers),
                 "replicas_checked": replicas,
                 "distinct_parameter_sets": len(cache),
                 "replica_tablets_answered_from_rows": from_rows})
        return {"answers_differing_from_reference": (differing, 0),
                "replica_answers_wrong": (wrong + from_rows, 0)}

    def _check_replicas(self):
        """One Q1 and one Q6 (parameters from the seed) answered by every
        replica of every tablet, each on its own device partial; the
        partials of a tserver's replicas combine to the whole answer,
        which is held to the reference in raw integers."""
        from yugabyte_tpu.docdb import scan_spec as SS
        rng = datagen.rng_for(self.ctx.seed, 5)
        p1, p6 = datagen_tpch.q1_params(rng), datagen_tpch.q6_params(rng)
        q1, q6 = self._replica_specs(p1, p6)
        want1 = {k: (g["rows"], g["sums"]) for k, g in
                 reference_tpch.q1_raw(self.rows, p1["delta"]).items()}
        raw6 = reference_tpch.q6_raw(self.rows, p6["year"], p6["discount"],
                                     p6["quantity"])
        want6 = {(): (raw6["rows"], [raw6["sum"]])} if raw6["rows"] else {}
        wrong = replicas = from_rows = 0
        for ts in self.cluster.tservers:
            peers = list(ts.tablet_manager.peers())
            for spec, want in ((q1, want1), (q6, want6)):
                parts = [p.tablet.scan_aggregate(spec=spec) for p in peers]
                from_rows += sum(part is None for part in parts)
                got = {tuple(g["key"]): (g["rows"],
                                         [t["sum"] for t in g["terms"]])
                       for g in SS.combine_group_partials(
                           [part for part in parts if part])["groups"]}
                wrong += got != want
            replicas += len(peers)
        return wrong, replicas, from_rows

    def close(self) -> None:
        if self.cluster is not None:
            self.cluster.shutdown()


class _StreamLog:
    def __init__(self):
        self.samples = []       # latency_ms of every query sent
        self.answers = []       # (kind, params, rows, pushdown)
        self.gave_up = []
        self.error = None
