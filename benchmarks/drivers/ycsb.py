"""Driver for cells whose traffic is a YCSB core workload against an
in-process RF3 `MiniCluster`. The cluster part is `chip_smoke.cluster_phase`
(PR 22); the loop and its op-weighted latency accounting are copied from
`integration/load_generator.py::YcsbLoadGenerator`, with YCSB's own request
distribution and record shape in place of that generator's 80/20 hot set and
single 64-byte value, and every draw made from the seed.

Closed loop: `clients.threads` driver threads, each with `clients.in_flight`
operations per tick; a tick's updates ride one `YBSession` flush on a side
thread while its reads ride one `YBClient.multi_read`, and the thread's next
tick is sent when both have settled.
"""

import gc
import sys
import threading
import time

import numpy as np

from benchmarks import datagen, reference

CONTROLS = ("acked_write_dropped",)
NAMESPACE = "ycsb"


def weighted_percentile(samples, q: float):
    """Percentile over operations: each (latency_ms, n_ops) sample stands
    for n_ops operations that all experienced latency_ms."""
    if not samples:
        return None
    lat = np.asarray([s[0] for s in samples], dtype=np.float64)
    w = np.asarray([s[1] for s in samples], dtype=np.float64)
    order = np.argsort(lat, kind="stable")
    cum = np.cumsum(w[order])
    return float(lat[order][np.searchsorted(cum, q / 100.0 * cum[-1])])


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sizes = ctx.sizes
        self.traffic = ctx.traffic
        self.cluster = None
        self.epoch = 0
        self.load_resends = 0

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        from yugabyte_tpu.client.session import YBSession
        from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
        from yugabyte_tpu.docdb.doc_key import DocKey
        from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
        from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                           MiniClusterOptions)
        from yugabyte_tpu.storage import offload_policy  # noqa: F401 (defines the flag)
        from yugabyte_tpu.utils import flags

        ctx = self.ctx
        shape, dep = ctx.config["shape"], ctx.config["deployment"]
        ctx.require(flags.get_flag("device_offload_mode") == "auto",
                    "device_offload_mode is not at its default")
        n = int(self.sizes["recordcount"])
        self.n_fields = int(shape["fieldcount"])
        self.field_len = int(shape["fieldlength"])
        self.fields = [f"field{i}" for i in range(self.n_fields)]
        self.schema = Schema(
            columns=[ColumnSchema("k", DataType.STRING)]
            + [ColumnSchema(f, DataType.STRING) for f in self.fields],
            num_hash_key_columns=1, num_range_key_columns=0)
        self.names = datagen.ycsb_key_names(n)
        self.doc_keys = [DocKey(hash_components=(name,))
                         for name in self.names]
        self.history = reference.FieldHistory(self.n_fields)

        self.cluster = MiniCluster(MiniClusterOptions(
            num_masters=int(dep["masters"]),
            num_tservers=int(dep["tservers"]),
            fs_root=ctx.workdir)).start()
        platform = ctx.devices[0].platform
        devices = [ts.compactionz()["device"] for ts in self.cluster.tservers]
        ctx.require(all(d["platform"] == platform for d in devices),
                    f"tserver devices {devices}, expected {platform}")
        self.client = self.cluster.new_client()
        self.client.create_namespace(NAMESPACE)
        self.table = self.client.create_table(
            NAMESPACE, "usertable", self.schema,
            num_tablets=int(self.sizes["tablets"]))
        self.cluster.wait_all_replicas_running(self.table.table_id)
        self.tablet_ids = sorted({p.tablet_id for ts in self.cluster.tservers
                                  for p in ts.tablet_manager.peers()})
        ctx.require(len(self.tablet_ids) == int(self.sizes["tablets"]),
                    f"tablets on the tservers: {self.tablet_ids}")

        rng = datagen.rng_for(ctx.seed, 2)
        rounds = int(self.sizes["load_rounds"])
        batch = int(self.sizes["load_batch"])
        t0 = time.monotonic()
        for rnd in range(rounds):
            lo, hi = n * rnd // rounds, n * (rnd + 1) // rounds
            session = YBSession(self.client)
            for start in range(lo, hi, batch):
                stop = min(start + batch, hi)
                vals = datagen.letter_strings(
                    rng, (stop - start) * self.n_fields, self.field_len)
                ops = []
                for j, rec in enumerate(range(start, stop)):
                    row = vals[j * self.n_fields:(j + 1) * self.n_fields]
                    self.history.load(rec, row)
                    ops.append(QLWriteOp(
                        WriteOpKind.INSERT, self.doc_keys[rec],
                        dict(zip(self.fields, row))))
                self._load_batch(session, ops)
            session.close()
            self._on_each_replica(lambda tablet: tablet.flush())
            if rnd == rounds - 1:
                self._on_each_replica(lambda tablet: tablet.compact())
        leaders = [[p for p in ts.tablet_manager.peers()
                    if p.raft.is_leader()] for ts in self.cluster.tservers]
        ctx.log({"load": {
            "records": n, "seconds": time.monotonic() - t0,
            "ops_sent_again": self.load_resends,
            "tablets": len(self.tablet_ids),
            "leaders_per_tserver": [len(ps) for ps in leaders],
            "live_files_per_replica": sorted(
                p.tablet.regular_db.n_live_files
                for ts in self.cluster.tservers
                for p in ts.tablet_manager.peers())}})

        c = self.traffic["clients"]
        self.n_threads, self.in_flight = int(c["threads"]), int(c["in_flight"])
        self.chooser = datagen.key_chooser(
            self.traffic["request_distribution"], n)
        ops = self.traffic["operations"]
        self.read_share = float(ops.get("read", 0.0))
        ctx.require(abs(self.read_share + float(ops.get("update", 0.0)) - 1.0)
                    < 1e-9, "this driver knows reads and updates only")
        # the model's million strings and keys are the harness's, not the
        # program's garbage: keep the collector from walking them in the window
        gc.collect()
        gc.freeze()

    def _load_batch(self, session, ops) -> None:
        """One batch of the load, every op acknowledged before the next. The
        load is set-up, and an INSERT sent again writes the same row: where
        the client gives up on some ops (an election while the host stood
        still, its retry budget spent) they are sent again, a few times,
        before the run is given up."""
        from yugabyte_tpu.client.session import SessionFlushError
        for attempt in range(6):
            for op in ops:
                session.apply(self.table, op)
            try:
                session.flush()
                return
            except SessionFlushError as e:
                ops = [op for _t, op, _e in e.per_op]
                self.load_resends += len(ops)
                print(f"load: {len(ops)} ops not acknowledged (attempt "
                      f"{attempt + 1}): {e.per_op[0][2]}", file=sys.stderr,
                      flush=True)
                time.sleep(1.0 + attempt)
        self.ctx.require(False, f"load: {len(ops)} ops never acknowledged")

    def _on_each_replica(self, call) -> None:
        """`tablet.flush()` / `tablet.compact()` on every replica of every
        tablet, so whichever replica leads during the window holds the same
        files. `yb_admin flush_table` / `compact_table` reach only the peer
        the master believes to lead, skip a tablet silently when it knows
        none, and leadership moves during the load: with them one to six of
        six leaders held a compacted SST, differently in every run (PERF.md,
        Findings PR 24)."""
        for ts in self.cluster.tservers:
            for p in ts.tablet_manager.peers():
                call(p.tablet)

    # ------------------------------------------------------------ the loop
    def run(self, seconds: float, tracer) -> dict:
        """All threads tick until `seconds` have passed; the window closes
        when every thread's tick in flight has settled."""
        self.epoch += 1
        stop = threading.Event()
        logs = [_ThreadLog() for _ in range(self.n_threads)]
        threads = [threading.Thread(target=self._worker, name=f"ycsb-{w}",
                                    args=(w, stop, logs[w]), daemon=True)
                   for w in range(self.n_threads)]
        t0 = time.monotonic()
        tracer.timed_start()
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(120)
            self.ctx.require(not t.is_alive(), "a driver thread never settled")
        t1 = time.monotonic()
        tracer.timed_stop()
        for log in logs:
            if log.error is not None:
                raise log.error
        # after the clock has stopped: warm-up loops write too, and later
        # reads may see their values
        for log in logs:
            for w0, w1, updates in log.writes:
                for r, f, v in updates:
                    self.history.wrote(r, f, v, w0, w1)
            # a batch the client gave up on: outcome unknown to the model
            for w0, _w1, updates in log.unsettled:
                for r, f, v in updates:
                    self.history.gave_up(r, f, v, w0)
            for what in log.gave_up[:3]:
                print(f"window: {what}"[:600], file=sys.stderr, flush=True)
        return {"logs": logs, "seconds": t1 - t0}

    def _worker(self, wid: int, stop, log) -> None:
        try:
            self._ticks(wid, stop, log)
        except BaseException as e:  # handed to the main thread, which raises
            log.error = e

    def _ticks(self, wid: int, stop, log) -> None:
        from yugabyte_tpu.client.session import SessionFlushError, YBSession
        from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
        ctx = self.ctx
        rng = datagen.rng_for(ctx.seed, 1000 * self.epoch + 10 + wid)
        session = YBSession(self.client)
        drop_every = 97 if ctx.control == "acked_write_dropped" else 0
        seq = 0
        while not stop.is_set():
            with ctx.span("batch_build"):
                n = self.in_flight
                recs = self.chooser.draw(rng, n).tolist()
                is_read = (rng.random(n) < self.read_share).tolist()
                fields = rng.integers(0, self.n_fields, size=n).tolist()
                reads = [r for r, rd in zip(recs, is_read) if rd]
                tails = iter(datagen.letter_strings(
                    rng, n - len(reads), self.field_len))
                updates = []
                for r, rd, f in zip(recs, is_read, fields):
                    if not rd:
                        seq += 1     # every written value is written once
                        head = "%d.%d.%d:" % (self.epoch, wid, seq)
                        updates.append((r, f, head + next(tails)[len(head):]))
            writer = None
            if updates:
                def flush_updates(updates=updates):
                    t0 = time.monotonic()
                    sent = {}           # id(op) -> its update
                    for r, f, v in updates:
                        log.n_updates += 1
                        if drop_every and log.n_updates % drop_every == 0:
                            continue        # control: acked, never sent
                        op = QLWriteOp(WriteOpKind.UPDATE, self.doc_keys[r],
                                       {self.fields[f]: v})
                        sent[id(op)] = (op, (r, f, v))
                        session.apply(self.table, op)
                    lost = []
                    try:
                        with ctx.span("session_flush"):
                            session.flush()
                    except SessionFlushError as e:
                        # the ops it lists were given up, the others acked
                        lost = [sent[id(op)][1] for _t, op, _e in e.per_op]
                        log.failed += len(lost)
                        log.gave_up.append(f"flush: {e}")
                    t1 = time.monotonic()
                    acked = updates
                    if lost:
                        log.unsettled.append((t0, t1, lost))
                        gone = set(lost)
                        acked = [u for u in updates if u not in gone]
                    log.writes.append((t0, t1, acked))
                    log.write_samples.append(((t1 - t0) * 1e3, len(updates)))
                writer = threading.Thread(target=flush_updates, daemon=True)
                writer.start()
            if reads:
                t0 = time.monotonic()
                try:
                    with ctx.span("multi_read"):
                        rows = self.client.multi_read(
                            self.table, [self.doc_keys[r] for r in reads])
                except Exception as e:  # the client gave the batch up
                    rows = None
                    log.failed += len(reads)
                    log.gave_up.append(f"multi_read: {type(e).__name__}: {e}")
                t1 = time.monotonic()
                if rows is not None:
                    log.reads.append((t0, t1, reads, rows))
                log.read_samples.append(((t1 - t0) * 1e3, len(reads)))
            if writer is not None:
                writer.join()
        session.close()

    # ------------------------------------------------------------- results
    def metrics(self, window: dict, counters: dict) -> dict:
        reads = [s for log in window["logs"] for s in log.read_samples]
        writes = [s for log in window["logs"] for s in log.write_samples]
        failed = sum(log.failed for log in window["logs"])
        done = sum(s[1] for s in reads) + sum(s[1] for s in writes) - failed
        self.ctx.log({"read_batches": len(reads), "update_batches": len(writes),
                      "reads": sum(s[1] for s in reads),
                      "updates": sum(s[1] for s in writes),
                      "read_p50_ms": weighted_percentile(reads, 50),
                      "update_p50_ms": weighted_percentile(writes, 50),
                      "window_s": window["seconds"],
                      "batches_given_up": sum(len(log.gave_up)
                                              for log in window["logs"]),
                      "client_retries": self.client.retry_budget.spent_total,
                      "client_retries_denied":
                          self.client.retry_budget.exhausted_total,
                      "raft_terms": sorted(
                          p.raft.current_term for p in
                          self.cluster.tservers[0].tablet_manager.peers())})
        out = {"ops_per_s": done / window["seconds"]}
        if reads:
            out["read_p95_ms"] = weighted_percentile(reads, 95)
        if writes:
            out["update_p95_ms"] = weighted_percentile(writes, 95)
        return out

    def tally(self, window: dict, counters: dict) -> dict:
        logs = window["logs"]
        attempted = sum(s[1] for log in logs
                        for s in log.read_samples + log.write_samples)
        self.ctx.log({
            "point_read_device_fallback_total":
                counters["point_read_device_fallback_total"],
            "point_read_batched_keys_total":
                counters["point_read_batched_keys_total"]})
        return {"attempted": int(attempted),
                "failed": int(sum(log.failed for log in logs))}

    def verify(self, window: dict) -> dict:
        """Every read of the window against the history of writes, and after
        the window a seeded sample of the written records, the hottest with
        it, read back from all three replicas of each one's tablet."""
        ctx = self.ctx
        hist = self.history
        bad_reads = n_reads = 0
        for log in window["logs"]:
            for t0, t1, recs, rows in log.reads:
                for rec, row in zip(recs, rows):
                    n_reads += 1
                    got = row.to_dict(self.schema) if row is not None else {}
                    bad_reads += any(
                        not hist.admissible(rec, f, got.get(name), t0, t1)
                        for f, name in enumerate(self.fields))
        written = sorted({r for log in window["logs"]
                          for _a, _b, ups in log.writes for r, _f, _v in ups})
        ctx.require(written or self.read_share == 1.0,
                    "no write was acknowledged")
        rng = datagen.rng_for(ctx.seed, 3)
        k = int(self.traffic["check"]["replica_sample"])
        hot = self.chooser.scatter[:16].tolist()
        pool = np.asarray(written if written else range(len(self.names)))
        sample = sorted(set(rng.choice(pool, size=min(k, len(pool)),
                                       replace=False).tolist()) | set(hot))
        by_tablet = {}
        for rec in sample:
            pk = self.table.partition_key_for(self.doc_keys[rec])
            t = self.client.meta_cache.lookup_tablet(self.table.table_id, pk)
            by_tablet.setdefault(t.tablet_id, []).append(rec)
        want = {(rec, f): hist.final_values(rec, f) for rec in sample
                for f in range(self.n_fields)}
        replica_bad = replicas = 0
        deadline = time.monotonic() + 60
        for tablet_id, recs in by_tablet.items():
            peers = [p for ts in self.cluster.tservers
                     for p in ts.tablet_manager.peers()
                     if p.tablet_id == tablet_id]
            ctx.require(len(peers) == int(
                ctx.config["deployment"]["replication_factor"]),
                f"{tablet_id}: {len(peers)} replicas")
            for peer in peers:
                while True:
                    rows = peer.tablet.multi_read(
                        [self.doc_keys[r] for r in recs])
                    bad = 0
                    for rec, row in zip(recs, rows):
                        got = row.to_dict(self.schema) if row is not None \
                            else {}
                        bad += any(
                            got.get(name) not in want[(rec, f)]
                            for f, name in enumerate(self.fields))
                    # a follower applies a committed entry after the leader
                    # acknowledged it: late is not wrong, wait up to a minute
                    if not bad or time.monotonic() > deadline:
                        break
                    time.sleep(0.05)
                replica_bad += bad
                replicas += 1
        ctx.log({"reads_checked": n_reads, "records_read_back": len(sample),
                 "replicas_checked": replicas})
        return {"reads_not_admissible": (bad_reads, 0),
                "replica_records_wrong": (replica_bad, 0)}

    def close(self) -> None:
        if self.cluster is not None:
            self.cluster.shutdown()


class _ThreadLog:
    def __init__(self):
        self.reads = []           # (t0, t1, [record], [row])
        self.writes = []          # (t0, t1, [(record, field, value)]) acked
        self.unsettled = []       # (t0, t1, [update]) the client gave up on
        self.read_samples = []    # (latency_ms, n_ops)
        self.write_samples = []
        self.failed = 0
        self.gave_up = []         # what the client said when it gave up
        self.n_updates = 0
        self.error = None
