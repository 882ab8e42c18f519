"""The one span rail (utils/trace.py::span): the primitive itself, the
compaction job's stage tree (job = sum of disjoint stages + job_other) and
the serve path's sub-stages (slices inside device_dispatch / server_other,
outside measured_ms())."""

import threading
import time

import numpy as np
import pytest

from yugabyte_tpu.utils import latency, trace
from yugabyte_tpu.utils.metrics import (_PIPELINE_STAGES, pipeline_span,
                                        pipeline_stage_totals)
from yugabyte_tpu.utils.trace import span

# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------


class Sink:
    def __init__(self):
        self.calls = []

    def __call__(self, inclusive_ms, self_ms):
        self.calls.append((inclusive_ms, self_ms))


def test_nesting_and_self_time():
    outer, inner = Sink(), Sink()
    with span("t/outer", outer) as o:
        assert trace.current_span() is o
        time.sleep(0.02)
        with span("t/inner", inner) as i:
            assert i.parent is o and trace.current_span() is i
            time.sleep(0.03)
        with span("t/inner", inner):
            time.sleep(0.01)
        assert trace.current_span() is o
    assert trace.current_span() is None
    (o_incl, o_self), = outer.calls
    assert len(inner.calls) == 2
    inner_incl = sum(c[0] for c in inner.calls)
    # leaves: self == inclusive; the parent's self time is what its
    # children leave, so the self times sum to the root's duration
    assert all(c[0] == pytest.approx(c[1]) for c in inner.calls)
    assert o_incl >= 60.0 - 1.0 and inner_incl >= 40.0 - 1.0
    assert o_self == pytest.approx(o_incl - inner_incl, abs=1e-6)
    assert o.ns == pytest.approx(o_incl * 1e6) and o.ms == o_incl


def test_explicit_parent_across_a_thread():
    """A new thread starts with no ambient span: the hand-off passes the
    waiting span, and the worker's time counts as its child time."""
    root_sink, seen = Sink(), {}
    with span("t/root", root_sink) as root:
        def work():
            seen["ambient"] = trace.current_span()
            with span("t/worker", parent=root) as w:
                seen["parent"] = w.parent
                time.sleep(0.03)
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert seen["ambient"] is None and seen["parent"] is root
    (incl, self_ms), = root_sink.calls
    assert incl >= 29.0 and self_ms <= incl - 29.0


def test_root_span_takes_no_parent():
    with span("t/a") as a:
        with span("t/detached", parent=None) as d:
            time.sleep(0.005)
        assert d.parent is None
    assert a.child_ns == 0 and a.self_ns == a.ns


def test_exception_exit_records_and_restores():
    sink = Sink()
    with pytest.raises(KeyError):
        with span("t/outer") as o:
            with span("t/boom", sink):
                raise KeyError("x")
    assert len(sink.calls) == 1 and sink.calls[0][0] >= 0.0
    assert o.child_ns > 0 and trace.current_span() is None


def test_no_profiler_counters_alone(monkeypatch):
    """A process without JAX (no TraceAnnotation) still gets the counters."""
    monkeypatch.setattr(trace, "_annotation", False)
    sink = Sink()
    with span("t/plain", sink) as s:
        assert s._ann is None
    assert len(sink.calls) == 1


def test_span_cost_without_a_profiler_session():
    """Budget: under 5 us a span with the profiler off; asserted at a
    loose multiple so a loaded CI host does not flake."""
    before = pipeline_stage_totals()["pace"]
    with pipeline_span("pace"):      # resolve imports and the sink
        pass
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with pipeline_span("pace"):
            pass
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    assert per_span_us < 50.0, f"{per_span_us:.1f} us a span"
    assert pipeline_stage_totals()["pace"] > before


def test_pipeline_span_stages():
    """self time under the span's own stage, inclusive under `inclusive`,
    nothing under stage=None."""
    b = pipeline_stage_totals()
    with pipeline_span("ingest", inclusive="host"):
        with pipeline_span("raw_read"):
            time.sleep(0.01)
        with pipeline_span("shadow_oracle", stage=None, parent=None):
            time.sleep(0.002)
    a = pipeline_stage_totals()
    d = {k: a[k] - b[k] for k in a}
    assert d["raw_read"] >= 9.0 and d["host"] >= d["raw_read"]
    assert d["ingest"] == pytest.approx(d["host"] - d["raw_read"], abs=1e-6)
    assert "shadow_oracle" not in a
    assert set(k for k, v in d.items() if v) == {"host", "ingest",
                                                 "raw_read"}


def test_every_pipeline_span_in_the_source_is_a_listed_stage():
    """`pipeline_stage_totals()` (and so /compactionz and the benchmark)
    carries exactly `_PIPELINE_STAGES`: a span whose stage is not listed
    would fall out of `job = sum of stages + job_other`."""
    import os
    import re
    import yugabyte_tpu
    root = os.path.dirname(yugabyte_tpu.__file__)
    call = re.compile(r'pipeline_span\(\s*"(\w+)"([^)]*)\)')
    pool_call = re.compile(r'pool_span\(\s*"(\w+)"\)')  # stage pool_<name>
    found = set()
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as f:
                    text = f.read()
                    found.update("pool_" + n for n in pool_call.findall(text))
                    for stage, rest in call.findall(text):
                        m = re.search(r'inclusive="(\w+)"', rest)
                        if m:
                            found.add(m.group(1))
                        m = re.search(r'stage=(None|"\w+")', rest)
                        if m is None:
                            found.add(stage)
                        elif m.group(1) != "None":
                            found.add(m.group(1).strip('"'))
    assert len(found) > 30
    assert found <= set(_PIPELINE_STAGES), found - set(_PIPELINE_STAGES)
    assert set(_PIPELINE_STAGES) - found == set(), \
        set(_PIPELINE_STAGES) - found


# ---------------------------------------------------------------------------
# the compaction job's stage tree
# ---------------------------------------------------------------------------

LEGACY_DISJOINT = ("device", "write", "shadow", "decode", "encode")
NOT_IN_THE_SUM = ("host", "job", "job_other")


def _codec_job_deltas(tmp_path):
    """One device-codec major compaction through DB.compact_all() at a
    rehearsal size; the stage deltas around it."""
    import jax
    from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
    from yugabyte_tpu.storage import DB, DBOptions
    from yugabyte_tpu.storage import integrity  # noqa: F401 (flag defs)
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    from yugabyte_tpu.utils import flags
    dev = jax.devices()[0]
    cutoff = 10_000_000 << 12
    db = DB(str(tmp_path / "db"), DBOptions(auto_compact=False))
    rng = np.random.default_rng(5)
    wid = 0
    for run in range(4):
        items = []
        for k in sorted(set(rng.integers(0, 3000, size=1500).tolist())):
            wid += 1
            items.append((b"Suser%08d\x00\x00!" % k,
                          DocHybridTime(HybridTime((1000 + run) << 12), wid),
                          b"v" * 40))
        db.write_batch(items)
        db.flush()
    db.close()
    # a freshly opened DB: cold device cache, empty run cache, so the job
    # takes the device codec (as every job of the benchmark's cell does)
    db = DB(str(tmp_path / "db"), DBOptions(
        device=dev, device_cache=DeviceSlabCache(dev), auto_compact=False,
        retention_policy=lambda: cutoff))
    old = flags.get_flag("shadow_verify_sample")
    flags.set_flag("shadow_verify_sample", 1.0)   # the shadow stage too
    try:
        before = pipeline_stage_totals()
        db.compact_all()
        after = pipeline_stage_totals()
    finally:
        flags.set_flag("shadow_verify_sample", old)
    assert db.background_error is None
    assert len(db.versions.live_files()) == 1
    db.close()
    return {k: after[k] - before[k] for k in after}


@pytest.mark.requires_native("compaction_engine")
def test_codec_job_is_the_sum_of_its_disjoint_stages(tmp_path, monkeypatch):
    from yugabyte_tpu.ops import block_codec
    monkeypatch.setenv("YBTPU_DEVICE_CODEC", "1")
    f0 = block_codec.codec_metrics()["encode_fallbacks"].value()
    d = _codec_job_deltas(tmp_path)
    assert block_codec.codec_metrics()["encode_fallbacks"].value() == f0, \
        "the job left the device-codec path"
    assert set(d) == set(_PIPELINE_STAGES)
    parts = {k: v for k, v in d.items() if k not in NOT_IN_THE_SUM}
    total = sum(parts.values()) + d["job_other"]
    assert d["job"] > 0
    assert total == pytest.approx(d["job"], rel=0.01), (d["job"], total, d)
    # every stage of the path the job took moved
    moved = {k for k, v in parts.items() if v > 0}
    for stage in ("routing", "raw_read", "raw_parse", "stage_input",
                  "value_concat", "ingest", "merge_stage", "merge_launch",
                  "decision_unpack", "survivor_select", "survivor_concat",
                  "survivor_positions", "span_gather", "lindex_fit",
                  "value_gather", "cache_install", "version_install",
                  "reader_open", "input_delete") + LEGACY_DISJOINT:
        assert stage in moved, f"stage {stage!r} did not move: {d}"
    # the legacy `host` slice overlaps the ingest stages: in no sum
    assert d["host"] >= d["raw_read"] + d["raw_parse"] + d["merge_launch"]
    assert d["job_other"] < 0.25 * d["job"]


# ---------------------------------------------------------------------------
# the serve path's sub-stages
# ---------------------------------------------------------------------------


def test_budget_subs_stay_outside_measured_ms_and_ride_the_wire():
    b = latency.LatencyBudget(latency.OP_MULTI_READ)
    b.record(latency.STAGE_DEVICE_DISPATCH, 10.0)
    b.record_sub("device_wait", 6.0)
    b.record_sub("query_pack", 1.0)
    b.record_sub("request_decode", 0.5)
    assert b.measured_ms() == 10.0
    assert b.sub_ms(latency.STAGE_DEVICE_DISPATCH) == 7.0
    assert b.sub_ms(latency.STAGE_SERVER_OTHER) == 0.5
    wire = b.to_wire()
    client = latency.LatencyBudget(latency.OP_MULTI_READ)
    client.merge(wire)
    client.merge({"sub": {"device_wait": "junk", 3: 1.0}, "sub2": {}})
    assert client.stages == {latency.STAGE_DEVICE_DISPATCH: 10.0}
    assert client.subs == {"device_wait": 6.0, "query_pack": 1.0,
                           "request_decode": 0.5}
    # an old peer's merge (numbers only) drops the nested map
    assert {k for k, v in wire.items() if isinstance(v, float)} == \
        {latency.STAGE_DEVICE_DISPATCH}


def test_sub_stage_tables_agree():
    subs = set(latency._READ_SUB_HISTOGRAMS) | set(
        latency._WRITE_SUB_HISTOGRAMS) | set(latency._SCAN_SUB_HISTOGRAMS)
    assert subs == set(latency._SUB_OF)
    assert set(latency._SUB_OF.values()) == {latency.STAGE_DEVICE_DISPATCH,
                                             latency.STAGE_SERVER_OTHER}
    for op, table in latency._SUB_TABLES.items():
        assert not set(table) & set(latency._STAGE_TABLES[op])
        assert all(name.startswith(f"serve_path_{op}_")
                   and name.endswith(f"_{sub}_ms")
                   for sub, name in table.items())


def test_stage_and_sub_spans_record_into_the_ambient_budget():
    b = latency.LatencyBudget(latency.OP_MULTI_READ)
    token = latency.use_budget(b)
    try:
        with latency.stage_span(latency.STAGE_DEVICE_DISPATCH):
            with latency.sub_span("device_wait"):
                time.sleep(0.01)
            with latency.sub_span("query_pack"):
                pass
    finally:
        latency.clear_budget(token)
    with latency.sub_span("device_wait"):     # no budget: nothing, no raise
        pass
    dd = b.stages[latency.STAGE_DEVICE_DISPATCH]
    assert b.measured_ms() == dd
    assert b.subs["device_wait"] >= 9.0
    assert b.sub_ms(latency.STAGE_DEVICE_DISPATCH) == pytest.approx(
        dd, abs=1e-6)        # named slices + device_dispatch_other == stage


@pytest.fixture()
def cluster(tmp_path):
    from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                       MiniClusterOptions)
    c = MiniCluster(MiniClusterOptions(
        num_tservers=3, fs_root=str(tmp_path / "cluster"))).start()
    yield c
    c.shutdown()


def _serve_sums(op):
    page = latency.serve_path_attribution_page()[op]
    return (float(page["e2e"]["sum"]),
            {k: float(v["sum"]) for k, v in page["stages"].items()},
            {k: v.get("of") for k, v in page["stages"].items()})


def _delta(after, before):
    return {k: after[k] - before.get(k, 0.0) for k in after}


def test_serve_path_sub_stages_on_a_live_cluster(cluster):
    from yugabyte_tpu.client.session import YBSession
    from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
    from yugabyte_tpu.docdb.doc_key import DocKey
    from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
    schema = Schema(columns=(ColumnSchema("k", DataType.STRING),
                             ColumnSchema("v", DataType.STRING)),
                    num_hash_key_columns=1)
    client = cluster.new_client()
    client.create_namespace("sp")
    table = client.create_table("sp", "t", schema, num_tablets=2)
    cluster.wait_for_table_leaders("sp", "t")
    keys = [f"k{i:03d}" for i in range(48)]
    w0 = _serve_sums(latency.OP_WRITE)
    s = YBSession(client)
    for k in keys:
        s.apply(table, QLWriteOp(WriteOpKind.INSERT,
                                 DocKey(hash_components=(k,)),
                                 {"v": "v-" + k}))
    s.flush()
    # flush the tablets so the reads meet SSTs: the device point-read path
    for ts in cluster.tservers:
        for tid in ts.tablet_manager.tablet_ids():
            ts.tablet_manager.get_tablet(tid).tablet.flush()
    r0 = _serve_sums(latency.OP_MULTI_READ)
    rows = client.multi_read(table, [DocKey(hash_components=(k,))
                                     for k in keys])
    assert sum(r is not None for r in rows) == len(keys)
    r1 = _serve_sums(latency.OP_MULTI_READ)
    w1 = _serve_sums(latency.OP_WRITE)

    # ---- the write: sub-stages of server_other sum to no more than it,
    # and with server_other_rest to exactly it
    e2e = w1[0] - w0[0]
    st = _delta(w1[1], w0[1])
    of = w1[2]
    stages = {k: v for k, v in st.items() if of[k] is None}
    subs = {k: v for k, v in st.items()
            if of[k] == latency.STAGE_SERVER_OTHER}
    assert set(subs) == {"request_decode", "admission", "docop_encode",
                         "write_lock_wait", "batch_encode",
                         latency.SUB_SERVER_REST}
    # (a send can overlap the server's first stages: a little over 100%)
    assert e2e > 0 and sum(stages.values()) <= e2e * 1.05
    assert sum(stages.values()) >= 0.90 * e2e       # still telescopes
    named = sum(v for k, v in subs.items() if k != latency.SUB_SERVER_REST)
    other = stages[latency.STAGE_SERVER_OTHER]
    assert 0 < named <= other + 0.01
    assert sum(subs.values()) == pytest.approx(other, abs=0.02)
    assert subs["docop_encode"] > 0 and subs["request_decode"] > 0

    # ---- the read: sub-stages of device_dispatch sum to it
    e2e = r1[0] - r0[0]
    st = _delta(r1[1], r0[1])
    of = r1[2]
    stages = {k: v for k, v in st.items() if of[k] is None}
    dd_subs = {k: v for k, v in st.items()
               if of[k] == latency.STAGE_DEVICE_DISPATCH}
    so_subs = {k: v for k, v in st.items()
               if of[k] == latency.STAGE_SERVER_OTHER}
    # (a send can overlap the server's first stages: a little over 100%)
    assert e2e > 0 and sum(stages.values()) <= e2e * 1.05
    assert sum(stages.values()) >= 0.90 * e2e
    dd = stages[latency.STAGE_DEVICE_DISPATCH]
    assert dd > 0, "the reads did not take the device point-read path"
    named = sum(v for k, v in dd_subs.items()
                if k != latency.SUB_DISPATCH_OTHER)
    assert 0 < named <= dd + 0.01
    assert sum(dd_subs.values()) == pytest.approx(dd, abs=0.02)
    for sub in ("stage_lookup", "query_pack", "device_enqueue",
                latency.SUB_DEVICE_WAIT, "chunk_combine"):
        assert dd_subs[sub] > 0, (sub, dd_subs)
    assert sum(so_subs.values()) == pytest.approx(
        stages[latency.STAGE_SERVER_OTHER], abs=0.02)
    assert so_subs["key_build"] > 0 and so_subs["response_encode"] > 0

    # ---- the server map still sums to queue wait + handler wall: every
    # handler budget closed with server_other = wall - in_handler, so the
    # sub-stages changed nothing of measured_ms()
    b = latency.LatencyBudget(latency.OP_WRITE)
    b.record(latency.STAGE_RPC_QUEUE, 1.0)
    b.record(latency.STAGE_APPLY, 2.0)
    b.record_sub("docop_encode", 0.5)
    assert b.measured_ms() == 3.0

    # /servez lists the sub-stages, marked with their stage
    page = cluster.tservers[0].servez()["attribution"]
    assert page[latency.OP_WRITE]["stages"]["docop_encode"]["of"] == \
        latency.STAGE_SERVER_OTHER
    assert page[latency.OP_MULTI_READ]["stages"][
        latency.SUB_DEVICE_WAIT]["of"] == latency.STAGE_DEVICE_DISPATCH
    assert "of" not in page[latency.OP_WRITE]["stages"][
        latency.STAGE_SERVER_OTHER]
    client.close()


def test_handler_budget_sums_to_queue_wait_plus_handler_wall():
    """Messenger._invoke with an attribution header: the server map
    (stages, not sub-stages) is queue wait + handler wall, and the
    sub-stages of server_other sum to it with server_other_rest."""
    from yugabyte_tpu.rpc.messenger import LAT_HEADER_KEY, Messenger

    class Svc:
        def work(self):
            with latency.sub_span("request_decode"):
                time.sleep(0.01)
            latency.record_stage(latency.STAGE_APPLY, 3.0)
            time.sleep(0.005)
            return 7

    m = Messenger("span-test")
    try:
        m.register_service("svc", Svc())
        t0 = time.monotonic()
        resp = m._invoke("svc", "work", {}, lat_op=latency.OP_WRITE,
                         queue_ms=2.0)
        wall_ms = (time.monotonic() - t0) * 1e3
    finally:
        m.shutdown()
    assert resp["ret"] == 7
    lat = resp[LAT_HEADER_KEY]
    subs = lat.pop(latency.SUB_WIRE_KEY)
    assert lat[latency.STAGE_RPC_QUEUE] == 2.0
    in_handler = sum(lat.values()) - 2.0
    assert 15.0 <= in_handler <= wall_ms + 0.01
    other = lat[latency.STAGE_SERVER_OTHER]
    assert other == pytest.approx(in_handler - 3.0, abs=0.01)
    assert subs["request_decode"] >= 9.0
    assert subs["request_decode"] + subs[latency.SUB_SERVER_REST] == \
        pytest.approx(other, abs=0.01)
