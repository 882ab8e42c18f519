"""PR 17 — telemetry timebase: time-series store bounds, serve-path
latency attribution and exemplar click-through.

Covers the acceptance criteria:
  - the time-series store's memory is PROVABLY bounded: each ring holds
    at most `capacity` points and the series count is hard-capped, so
    total points <= capacity x metric_count (asserted), with drops
    counted rather than grown past the cap;
  - rate/delta queries return per-second units over the trailing window;
  - serve-path attribution: over a live MiniCluster, the per-stage
    histograms sum to >= 90% of the end-to-end histogram for BOTH the
    batched-write and the multi_read path, and the real (non-residual)
    server stages demonstrably carry mass;
  - e2e histograms carry trace-id exemplars that round-trip to a trace
    visible on /tracez (the /servez -> /tracez click-through);
  - /timeseriesz serves the sampler's window over HTTP;
  - the sampler's per-tick cost stays under 1% of the default interval.
"""

import json
import time
import urllib.request

import pytest

from yugabyte_tpu.client.session import YBSession
from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                   MiniClusterOptions)
from yugabyte_tpu.utils import latency
from yugabyte_tpu.utils.metrics import serve_path_metrics
from yugabyte_tpu.utils.timeseries import (TimeSeriesStore, _Ring,
                                           timeseries_store)

SCHEMA = Schema(
    columns=[ColumnSchema("k", DataType.STRING),
             ColumnSchema("v", DataType.STRING)],
    num_hash_key_columns=1)


def dk(k: str) -> DocKey:
    return DocKey(hash_components=(k,))


def ins(k: str, v: str) -> QLWriteOp:
    return QLWriteOp(WriteOpKind.INSERT, dk(k), {"v": v})


# ---------------------------------------------------------------------------
# TimeSeriesStore: bounded memory, rate units
# ---------------------------------------------------------------------------

class TestTimeSeriesStore:
    def test_memory_bound_capacity_times_metric_count(self):
        s = TimeSeriesStore(interval_s=5.0, capacity=8, max_metrics=5)
        tick = {"n": 0}

        def src():
            tick["n"] += 1
            # 10 series against a 5-series cap: half must be dropped
            return {f"m{i}": float(tick["n"] * i) for i in range(10)}

        s.register_source("t", src)
        for _ in range(50):
            s.sample_once()
        # the provable bound: capacity x metric_count, metric_count
        # itself capped at max_metrics
        assert s.metric_count() == 5
        assert s.memory_bound_points() == 8 * 5
        assert s.total_points() <= s.memory_bound_points()
        assert s.page()["dropped_series_total"] > 0
        for name in s.series_names():
            assert len(s.window(name)) <= 8

    def test_ring_wraps_keeping_newest(self):
        r = _Ring(4)
        for i in range(10):
            r.push(float(i), float(i * 100))
        assert len(r) == 4
        assert r.points() == [(6.0, 600.0), (7.0, 700.0),
                              (8.0, 800.0), (9.0, 900.0)]

    def test_rate_and_delta_units(self):
        s = TimeSeriesStore(capacity=16)
        r = _Ring(16)
        # a counter advancing 50 over 10 seconds = 5.0/s
        r.push(1000.0, 100.0)
        r.push(1010.0, 150.0)
        s._rings["c"] = r
        assert s.delta("c") == pytest.approx(50.0)
        assert s.rate("c") == pytest.approx(5.0)
        # window trimming: only the trailing 5s -> single point -> 0
        assert s.rate("c", window_s=5.0) == 0.0

    def test_source_error_is_contained_and_counted(self):
        s = TimeSeriesStore(capacity=4)

        def broken():
            raise RuntimeError("scrape boom")

        s.register_source("ok", lambda: {"good": 1.0})
        s.register_source("bad", broken)
        s.sample_once()
        assert "ok.good" in s.series_names()
        assert s.page()["scrape_errors_total"] == 1

    def test_sampler_tick_under_one_percent_of_interval(self):
        # the <1% overhead budget: one self-scrape of the process store
        # (ROOT registry + bucket-health source) must cost well under
        # 50ms = 1% of the default 5s interval
        s = timeseries_store()
        s.sample_once()  # warm (entity/histogram creation)
        t0 = time.monotonic()
        n = 5
        for _ in range(n):
            s.sample_once()
        mean_s = (time.monotonic() - t0) / n
        assert mean_s < 0.05, f"sample tick {mean_s*1e3:.1f}ms >= 1% of 5s"


# ---------------------------------------------------------------------------
# Serve-path attribution over a live cluster
# ---------------------------------------------------------------------------

@pytest.fixture()
def cluster(tmp_path):
    c = MiniCluster(MiniClusterOptions(
        num_tservers=3, fs_root=str(tmp_path / "cluster"))).start()
    yield c
    c.shutdown()


def _make_table(cluster, name):
    client = cluster.new_client()
    client.create_namespace("tele")
    table = client.create_table("tele", name, SCHEMA, num_tablets=2)
    cluster.wait_for_table_leaders("tele", name)
    return client, table


def _stage_sums(op):
    ent = serve_path_metrics()
    table = latency._STAGE_TABLES[op]
    e2e = ent.histogram(latency._E2E_HISTOGRAMS[op]).snapshot_dict()
    stages = {stage: ent.histogram(name).snapshot_dict()
              for stage, name in table.items()}
    return e2e, stages


class TestServePathAttribution:
    def test_write_and_read_stages_sum_to_90pct_of_e2e(self, cluster):
        client, table = _make_table(cluster, "attr")
        s = YBSession(client)
        keys = [f"k{i:03d}" for i in range(48)]
        for k in keys:
            s.apply(table, ins(k, f"v-{k}"))
        s.flush()
        rows = client.multi_read(table, [dk(k) for k in keys])
        assert sum(r is not None for r in rows) == len(keys)

        for op in (latency.OP_WRITE, latency.OP_MULTI_READ):
            e2e, stages = _stage_sums(op)
            assert e2e["count"] > 0, f"{op}: no finalized budgets"
            total = sum(float(st["sum"]) for st in stages.values())
            ratio = total / float(e2e["sum"])
            assert ratio >= 0.90, (
                f"{op}: stages sum to {ratio:.1%} of e2e "
                f"({ {k: round(float(v['sum']), 3) for k, v in stages.items()} })")
            # the mass must not all hide in the wire_transfer residual:
            # genuinely measured stages have to carry weight too
            residual = float(
                stages[latency.STAGE_WIRE_TRANSFER]["sum"])
            assert total - residual > 0.0

        # write path: the server-side decomposition demonstrably ran
        _, wstages = _stage_sums(latency.OP_WRITE)
        assert wstages[latency.STAGE_RAFT_REPLICATE]["count"] > 0
        assert wstages[latency.STAGE_RPC_QUEUE]["count"] > 0
        assert wstages[latency.STAGE_SERVER_OTHER]["count"] > 0
        # read path: rows resolved through the storage read stages
        _, rstages = _stage_sums(latency.OP_MULTI_READ)
        storage_ms = (float(rstages[latency.STAGE_ROW_ASSEMBLY]["sum"])
                      + float(rstages[latency.STAGE_HOST_FALLBACK]["sum"])
                      + float(rstages[latency.STAGE_DEVICE_DISPATCH]["sum"]))
        assert storage_ms > 0.0

    def test_servez_attribution_block(self, cluster):
        client, table = _make_table(cluster, "attr2")
        s = YBSession(client)
        for i in range(8):
            s.apply(table, ins(f"a{i}", "v"))
        s.flush()
        page = cluster.tservers[0].servez()
        attr = page["attribution"]
        assert set(attr) == {latency.OP_WRITE, latency.OP_MULTI_READ,
                             latency.OP_SCAN}
        wr = attr[latency.OP_WRITE]
        assert wr["e2e"]["count"] > 0
        for stage, snap in wr["stages"].items():
            assert "pct_of_e2e" in snap
        # percentages of e2e sum to ~100 within clamp slack
        pct = sum(snap["pct_of_e2e"] for snap in wr["stages"].values())
        assert pct >= 90.0

    def test_e2e_exemplar_round_trips_to_tracez(self, cluster):
        from yugabyte_tpu.utils.trace import tracez_page
        client, table = _make_table(cluster, "exem")
        s = YBSession(client)
        s.apply(table, ins("e1", "v"))
        s.flush()
        ent = serve_path_metrics()
        exems = ent.histogram(
            latency._E2E_HISTOGRAMS[latency.OP_WRITE]).exemplars()
        assert exems, "write e2e histogram carries no exemplars"
        tids = {e["trace_id"] for e in exems if e.get("trace_id")}
        assert tids, "exemplars carry no trace ids"
        # click-through: at least one exemplar's trace is on /tracez
        page_tids = {t["trace_id"] for t in tracez_page()["traces"]}
        assert tids & page_tids, (
            f"no exemplar trace id {tids} found on /tracez")
        # and the exemplars survive JSON exposition (not prometheus —
        # the text format has no exemplar grammar, by design)
        from yugabyte_tpu.utils.metrics import (ROOT_REGISTRY,
                                                registries_to_json_obj,
                                                registries_to_prometheus)
        blob = json.dumps(registries_to_json_obj([ROOT_REGISTRY]))
        assert sorted(tids)[0] in blob
        expo = registries_to_prometheus([ROOT_REGISTRY])
        assert sorted(tids)[0] not in expo


# ---------------------------------------------------------------------------
# /timeseriesz over HTTP
# ---------------------------------------------------------------------------

def test_timeseriesz_endpoint_smoke(cluster):
    client, table = _make_table(cluster, "tsz")
    s = YBSession(client)
    for i in range(4):
        s.apply(table, ins(f"t{i}", "v"))
    s.flush()
    store = timeseries_store()
    store.sample_once()  # don't wait out the 5s sampler interval
    ts = cluster.tservers[0]
    with urllib.request.urlopen(
            f"http://{ts.webserver.address}/timeseriesz", timeout=10) as r:
        page = json.loads(r.read())
    assert page["server_id"] == ts.server_id
    assert page["metric_count"] > 0
    assert page["memory_bound_points"] == \
        page["ring_capacity"] * page["metric_count"]
    assert page["metrics"], "no series sampled"
    name, series = next(iter(page["metrics"].items()))
    assert {"points", "last", "window", "rate_per_s", "spark"} <= set(series)
    # the cluster's own serve-path counters are in the window
    assert any(k.startswith("root.") for k in page["metrics"])
