"""Observability layer: cross-node trace propagation, Prometheus
exposition correctness, /compactionz, endpoint smoke tests, and the
metric-name lint wiring (this PR's tentpole + satellites).

The trace tests exercise the full distributed path: a client write's span
context rides the RPC wire header (rpc/codec.py), is adopted by the
inbound tserver handler (rpc/messenger.py), propagates through the raft
replicate fan-out (consensus/raft.py) to peer servers, and all hops group
under one trace_id in /tracez.
"""

import json
import os
import re
import sys
import time
import urllib.error
import urllib.request

import pytest

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
from yugabyte_tpu.docdb.doc_key import DocKey, SubDocKey
from yugabyte_tpu.docdb.value import Value
from yugabyte_tpu.rpc import codec
from yugabyte_tpu.utils import flags
from yugabyte_tpu.utils import trace as trace_mod
from yugabyte_tpu.utils.metrics import (MetricRegistry,
                                        registries_to_prometheus)
from yugabyte_tpu.utils.trace import TRACE, Trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = Schema([ColumnSchema("k", DataType.STRING),
                 ColumnSchema("v", DataType.INT64)], 1, 0)


# ---------------------------------------------------------------------------
# Prometheus text-format grammar validation (line-by-line)
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _parse_labels(s: str):
    """Parse `k="v",k2="v2"` honoring backslash escapes; returns dict or
    raises ValueError."""
    out = {}
    i = 0
    while i < len(s):
        eq = s.index("=", i)
        name = s[i:eq]
        if not _LABEL_RE.match(name):
            raise ValueError(f"bad label name {name!r}")
        if s[eq + 1] != '"':
            raise ValueError("label value not quoted")
        j = eq + 2
        val = []
        while True:
            c = s[j]
            if c == "\\":
                if s[j + 1] not in ('"', "\\", "n"):
                    raise ValueError(f"bad escape \\{s[j + 1]}")
                val.append(s[j:j + 2])
                j += 2
            elif c == '"':
                break
            elif c == "\n":
                raise ValueError("raw newline in label value")
            else:
                val.append(c)
                j += 1
        out[name] = "".join(val)
        i = j + 1
        if i < len(s):
            if s[i] != ",":
                raise ValueError(f"junk after label value: {s[i:]!r}")
            i += 1
    return out


def validate_prometheus_text(text: str):
    """Line-by-line validation of the exposition grammar: HELP/TYPE
    comments, sample syntax, label escaping, one TYPE per family emitted
    before (and contiguous with) its samples. Returns a list of error
    strings (empty = valid)."""
    errors = []
    types = {}          # family -> type
    family_done = set() # families whose sample block has ended
    current_family = None

    def family_of(name):
        if name in types:
            return name
        for suffix in ("_sum", "_count", "_bucket"):
            if name.endswith(suffix):
                base = name[: -len(suffix)]
                if types.get(base) in ("summary", "histogram"):
                    return base
        return None

    lines = text.split("\n")
    if lines and lines[-1] == "":
        lines.pop()
    for ln, line in enumerate(lines, 1):
        if not line:
            errors.append(f"line {ln}: empty line inside exposition")
            continue
        if line.startswith("#"):
            parts = line.split(" ", 3)
            if len(parts) < 3 or parts[1] not in ("HELP", "TYPE"):
                errors.append(f"line {ln}: malformed comment {line!r}")
                continue
            kind, name = parts[1], parts[2]
            if not _NAME_RE.match(name):
                errors.append(f"line {ln}: bad metric name {name!r}")
                continue
            if kind == "TYPE":
                if len(parts) != 4 or parts[3] not in (
                        "counter", "gauge", "summary", "histogram",
                        "untyped"):
                    errors.append(f"line {ln}: bad TYPE line {line!r}")
                    continue
                if name in types:
                    errors.append(f"line {ln}: duplicate TYPE for {name}")
                    continue
                types[name] = parts[3]
            continue
        # sample line: name[{labels}] value
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})? (\S+)$", line)
        if m is None:
            errors.append(f"line {ln}: unparseable sample {line!r}")
            continue
        name, _braced, labels, value = m.groups()
        if labels is not None:
            try:
                _parse_labels(labels)
            except (ValueError, IndexError) as e:
                errors.append(f"line {ln}: {e}")
        try:
            float(value)
        except ValueError:
            if value not in ("NaN", "+Inf", "-Inf"):
                errors.append(f"line {ln}: bad sample value {value!r}")
        fam = family_of(name)
        if fam is None:
            errors.append(f"line {ln}: sample {name} has no TYPE")
            continue
        if fam in family_done and fam != current_family:
            errors.append(f"line {ln}: family {fam} not contiguous")
        if current_family is not None and fam != current_family:
            family_done.add(current_family)
        current_family = fam
    return errors


class TestPrometheusExposition:
    def test_type_help_and_escaping(self):
        reg = MetricRegistry()
        # attributes with every character the escaper must handle
        ent = reg.entity("tablet", "t9", {
            "table_name": 'we"ird\\na"me', "note": "line1\nline2"})
        ent.counter("evil_rows_total", "rows with \\ and\nnewlines").increment(3)
        ent.gauge("evil_depth_count", "a gauge").set(1.5)
        h = ent.histogram("evil_latency_ms", "histo")
        for v in (1, 5, 9):
            h.increment(v)
        # the same family from a SECOND entity must share one TYPE line
        reg.entity("tablet", "t10").counter("evil_rows_total").increment(1)
        text = reg.to_prometheus()
        errs = validate_prometheus_text(text)
        assert not errs, "\n".join(errs)
        assert "# TYPE evil_rows_total counter" in text
        assert text.count("# TYPE evil_rows_total counter") == 1
        assert "# HELP evil_rows_total" in text
        assert '\\"ird\\\\na\\"me' in text      # escaped label value
        assert "line1\\nline2" in text
        assert "# TYPE evil_latency_ms summary" in text
        assert "evil_latency_ms_min" in text and "evil_latency_ms_max" in text
        # min/max carry real observed bounds
        assert re.search(r"evil_latency_ms_min\{[^}]*\} 1(\.0)?\b", text)
        assert re.search(r"evil_latency_ms_max\{[^}]*\} 9(\.0)?\b", text)

    def test_to_json_min_max(self):
        reg = MetricRegistry()
        h = reg.entity("server", "x").histogram("j_latency_ms")
        h.increment(2.0)
        h.increment(8.0)
        data = json.loads(reg.to_json())
        m = data[0]["metrics"][0]
        assert m["min"] == 2.0 and m["max"] == 8.0

    def test_multi_registry_merge_dedupes(self):
        reg = MetricRegistry()
        reg.entity("server", "a").counter("merge_a_total").increment()
        text = registries_to_prometheus([reg, reg])
        assert text.count("merge_a_total{") == 1
        assert not validate_prometheus_text(text)


# ---------------------------------------------------------------------------
# Trace-header codec round-trip (incl. absent-header back-compat)
# ---------------------------------------------------------------------------

class TestTraceHeaderCodec:
    def test_roundtrip(self):
        ctx = {"trace_id": "ab" * 8, "span_id": "cd" * 4, "sampled": True}
        wire = codec.trace_to_wire(ctx)
        req = {"id": 1, "svc": "s", "mth": "m", "args": {},
               codec.TRACE_HEADER_KEY: wire}
        decoded = codec.loads(codec.dumps(req))
        got = codec.trace_from_wire(decoded[codec.TRACE_HEADER_KEY])
        assert got == {"trace_id": "ab" * 8, "span_id": "cd" * 4,
                       "sampled": True}

    def test_absent_header_backward_compat(self):
        # an old peer's request has no trace key: decode yields None ctx
        req = {"id": 1, "svc": "s", "mth": "m", "args": {"x": 1}}
        decoded = codec.loads(codec.dumps(req))
        assert codec.trace_from_wire(
            decoded.get(codec.TRACE_HEADER_KEY)) is None
        # malformed headers degrade to untraced, never raise
        assert codec.trace_from_wire("garbage") is None
        assert codec.trace_from_wire({"span_id": "x"}) is None
        assert codec.trace_to_wire(None) is None

    def test_messenger_adopts_wire_context(self):
        from yugabyte_tpu.rpc.messenger import Messenger

        class Svc:
            def probe(self):
                TRACE("inside handler")
                t = trace_mod.current_trace()
                return {"trace_id": t.trace_id,
                        "parent_span_id": t.parent_span_id}

        server = Messenger("obs-server")
        server.register_service("obs", Svc())
        client = Messenger("obs-client")
        try:
            with Trace("obs-root") as root:
                ret = client.call(server.address, "obs", "probe")
            assert ret["trace_id"] == root.trace_id
            assert ret["parent_span_id"] == root.span_id
            # untraced caller: handler starts a fresh root
            ret2 = client.call(server.address, "obs", "probe")
            assert ret2["trace_id"] != root.trace_id
            assert ret2["parent_span_id"] is None
        finally:
            client.shutdown()
            server.shutdown()


# ---------------------------------------------------------------------------
# Webserver: 404 only for missing routes; handler bugs are 500
# ---------------------------------------------------------------------------

def test_webserver_handler_keyerror_is_500():
    from yugabyte_tpu.server.webserver import Webserver

    ws = Webserver(MetricRegistry())
    ws.register("/boom", lambda: {}["missing"])  # handler raises KeyError
    try:
        base = f"http://{ws.address}"
        with pytest.raises(urllib.error.HTTPError) as e500:
            urllib.request.urlopen(base + "/boom", timeout=5)
        assert e500.value.code == 500
        with pytest.raises(urllib.error.HTTPError) as e404:
            urllib.request.urlopen(base + "/no-such-route", timeout=5)
        assert e404.value.code == 404
    finally:
        ws.shutdown()


# ---------------------------------------------------------------------------
# /compactionz source stats at the DB level
# ---------------------------------------------------------------------------

def test_compaction_stats_versions_gcd(tmp_path):
    from yugabyte_tpu.storage.db import DB, DBOptions

    db = DB(str(tmp_path / "db"),
            DBOptions(auto_compact=False,
                      retention_policy=lambda: 1 << 62))
    key = SubDocKey(DocKey(range_components=("row",)),
                    (("col", 0),)).encode(include_ht=False)
    for v in range(4):
        db.write_batch([(key, DocHybridTime(HybridTime((v + 1) << 12), 0),
                         Value(primitive=v).encode())])
        db.flush()
    db.compact_all()
    stats = db.compaction_stats.to_dict()
    db.close()
    assert stats["flushes"] == 4
    assert stats["flush_bytes_written"] > 0
    assert stats["compactions"] == 1
    assert stats["compaction_bytes_read"] > 0
    assert stats["compaction_bytes_written"] > 0
    # 4 versions of one key at a cutoff above all of them: only the
    # visible version survives a major compaction
    assert stats["compaction_rows_in"] == 4
    assert stats["compaction_rows_out"] == 1
    assert stats["versions_gcd"] == 3
    assert stats["write_amplification"] > 1.0


FLUSH_STAGES = ("flush_pack", "flush_sst_write", "flush_slab_build",
                "flush_device_stage", "flush_install")


@pytest.mark.requires_native("compaction_engine")
def test_a_flush_is_the_sum_of_its_five_stages(tmp_path):
    """DB.flush's stages on the span rail (what /compactionz and the
    benchmark's stage_ms_per_job show of a flush): all five move on a
    flush with a device cache, and together they are no more than the
    flush()'s wall (self times on one thread) and most of it."""
    import jax
    from yugabyte_tpu.storage.db import DB, DBOptions
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    from yugabyte_tpu.utils.metrics import pipeline_stage_totals
    dev = jax.devices()[0]
    db = DB(str(tmp_path / "db"), DBOptions(
        auto_compact=False, device=dev, device_cache=DeviceSlabCache(dev)))

    def flush_rows(first):
        db.write_batch([
            (SubDocKey(DocKey(range_components=("row%06d" % i,)),
                       (("col", 1),)).encode(include_ht=False),
             DocHybridTime(HybridTime((1 + i) << 12), 0),
             Value(primitive="v%039d" % i).encode())
            for i in range(first, first + 4096)])
        before = pipeline_stage_totals()
        t0 = time.monotonic()
        assert db.flush() is not None
        wall_ms = (time.monotonic() - t0) * 1e3
        after = pipeline_stage_totals()
        return wall_ms, {k: after[k] - before[k] for k in after}

    flush_rows(0)           # the staging program's compile, imports
    wall_ms, moved = flush_rows(4096)
    db.close()
    assert all(moved[s] > 0 for s in FLUSH_STAGES), moved
    total = sum(moved[s] for s in FLUSH_STAGES)
    assert 0.5 * wall_ms <= total <= wall_ms, (wall_ms, moved)


# ---------------------------------------------------------------------------
# Live mini-cluster: endpoint smoke + /compactionz + kernel histograms
# ---------------------------------------------------------------------------

def _get(addr: str, path: str) -> bytes:
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=10) as r:
        return r.read()


def test_endpoint_smoke_and_compactionz(tmp_path):
    from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
    from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                       MiniClusterOptions)

    import yugabyte_tpu.storage.offload_policy  # defines the mode flag
    old_rf = flags.get_flag("replication_factor")
    old_mode = flags.get_flag("device_offload_mode")
    flags.set_flag("replication_factor", 1)
    # route the compaction through the device kernel so kernel-dispatch
    # histograms demonstrably exist in this server's exposition
    flags.set_flag("device_offload_mode", "device")
    mc = MiniCluster(MiniClusterOptions(
        num_masters=1, num_tservers=1,
        fs_root=str(tmp_path / "fs"))).start()
    try:
        client = mc.new_client()
        client.create_namespace("obs")
        t = client.create_table("obs", "t", SCHEMA, num_tablets=1)
        ts = mc.tservers[0]
        # several flushed runs of overlapping keys -> a real compaction
        for rnd in range(3):
            for i in range(20):
                client.write(t, [QLWriteOp(
                    WriteOpKind.INSERT, DocKey(hash_components=(f"k{i}",)),
                    {"v": i + rnd})])
            for tid in ts.tablet_manager.tablet_ids():
                ts.tablet_manager.get_tablet(tid).tablet.flush()
        for tid in ts.tablet_manager.tablet_ids():
            ts.tablet_manager.get_tablet(tid).tablet.compact()

        addr = ts.webserver.address
        # tserver /healthz: liveness status + the bucket-health board
        hz = json.loads(_get(addr, "/healthz"))
        assert hz["status"] == "ok"
        assert hz["native_unavailable"] == {}, "a native library is absent"
        bh = hz["bucket_health"]
        assert set(bh["states"]) == {"cold", "warming", "healthy",
                                     "degraded", "quarantined",
                                     "probation"}
        assert isinstance(bh["keys"], list)
        assert isinstance(bh["quarantine"], list)
        for path in ("/metrics", "/rpcz", "/tracez", "/threadz",
                     "/compactionz", "/integrityz"):
            payload = json.loads(_get(addr, path))
            assert payload is not None, path

        iz = json.loads(_get(addr, "/integrityz"))
        assert iz["shadow_verify"]["sample"] == flags.get_flag(
            "shadow_verify_sample")
        assert iz["scrub"]["interval_s"] == flags.get_flag(
            "scrub_interval_s")
        assert isinstance(iz["quarantined_files"], list)
        assert all("scrub" in t and "failed_corrupt" in t
                   for t in iz["tablets"])

        cz = json.loads(_get(addr, "/compactionz"))
        totals = cz["totals"]
        assert totals["flush_bytes_written"] > 0
        assert totals["compaction_bytes_read"] > 0
        assert totals["compaction_bytes_written"] > 0
        assert totals["write_amplification"] > 1.0
        # the job's span rail: its wall, its named stages, and what no
        # stage names (the residual)
        stages = cz["pipeline"]
        assert stages["stage_job_ms"] > 0
        assert "stage_job_other_ms" in stages
        assert "stage_version_install_ms" in stages
        # a flush's stages and whose columns its device slab came from
        assert stages["stage_flush_sst_write_ms"] > 0
        assert "stage_flush_slab_build_ms" in stages
        assert stages["flush_slab_native_total"] > 0
        assert "flush_slab_python_total" in stages

        prom = _get(addr, "/prometheus-metrics").decode()
        errs = validate_prometheus_text(prom)
        assert not errs, "\n".join(errs[:20])
        # kernel-dispatch instrumentation made it into the exposition
        assert "kernel_run_merge_dispatch_total" in prom \
            or "kernel_merge_gc_dispatch_total" in prom
        assert "kernel_run_merge_batch_rows" in prom \
            or "kernel_merge_gc_batch_rows" in prom
        # per-method inbound RPC histograms (service entity carries method)
        assert "rpc_inbound_call_duration_ms" in prom
        # WAL tier histograms
        assert "wal_fsync_duration_ms" in prom
        client.close()
    finally:
        mc.shutdown()
        flags.set_flag("replication_factor", old_rf)
        flags.set_flag("device_offload_mode", old_mode)


# ---------------------------------------------------------------------------
# Cross-node trace propagation on a replicated write
# ---------------------------------------------------------------------------

def test_write_trace_stitches_across_cluster(tmp_path):
    from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
    from yugabyte_tpu.integration.mini_cluster import (MiniCluster,
                                                       MiniClusterOptions)

    old_rf = flags.get_flag("replication_factor")
    flags.set_flag("replication_factor", 3)
    mc = MiniCluster(MiniClusterOptions(
        num_masters=1, num_tservers=3,
        fs_root=str(tmp_path / "fs"))).start()
    try:
        client = mc.new_client()
        client.create_namespace("tr")
        t = client.create_table("tr", "t", SCHEMA, num_tablets=1)
        mc.wait_all_replicas_running(t.table_id)
        mc.wait_for_table_leaders("tr", "t")  # don't race the election
        with Trace("test-write-root") as root:
            client.write(t, [QLWriteOp(
                WriteOpKind.INSERT, DocKey(hash_components=("kx",)),
                {"v": 7})])
        tid = root.trace_id

        def spans_for(trace_id):
            return [s for s in trace_mod.tracez()
                    if s["trace_id"] == trace_id]

        # replicate acks from the majority land before write() returns;
        # give the slowest peer's span a moment to be recorded too
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            names = {s["name"] for s in spans_for(tid)}
            if ("tserver.write" in names
                    and any(n.startswith("raft.append_entries:")
                            for n in names)
                    and "consensus.update_consensus" in names):
                break
            time.sleep(0.05)
        spans = spans_for(tid)
        names = {s["name"] for s in spans}
        # hop 1: the client root span itself
        assert "client.write" in names, names
        # hop 2: the coordinating tserver's write handler (adopted ctx)
        assert "tserver.write" in names, names
        # hop 3: the leader's per-peer replication spans
        assert any(n.startswith("raft.append_entries:") for n in names), names
        # hop 4: the raft peers' inbound AppendEntries handler spans
        assert "consensus.update_consensus" in names, names

        # parent/child stitching: the tserver.write handler is a child of
        # the client.write span
        by_name = {s["name"]: s for s in spans}
        client_span = by_name["client.write"]
        assert by_name["tserver.write"]["parent_span_id"] == \
            client_span["span_id"]

        # the grouped /tracez view on the coordinating tserver shows the
        # whole multi-hop trace under one trace_id with per-hop timings
        leader_addr = None
        for ts in mc.tservers:
            for tb in ts.tablet_manager.tablet_ids():
                peer = ts.tablet_manager.get_tablet(tb)
                if peer.raft.is_leader():
                    leader_addr = ts.webserver.address
        assert leader_addr is not None
        tz = json.loads(_get(leader_addr, "/tracez"))
        groups = [g for g in tz["traces"] if g["trace_id"] == tid]
        assert groups and groups[0]["n_spans"] >= 4
        assert all(sp["duration_ms"] >= 0 for sp in groups[0]["spans"])
        client.close()
    finally:
        mc.shutdown()
        flags.set_flag("replication_factor", old_rf)


# ---------------------------------------------------------------------------
# CI wiring for tools/lint_metric_names.py (like lint_swallowed_errors)
# ---------------------------------------------------------------------------

def test_metric_names_conform():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import lint_metric_names as lint
    finally:
        sys.path.pop(0)
    offenses = lint.check_paths(REPO_ROOT)
    assert not offenses, "\n".join(
        f"{p}:{ln}: {msg}" for p, ln, msg in offenses)


def test_metric_name_lint_catches_offenses(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import lint_metric_names as lint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad.py"
    bad.write_text(
        "e.counter('CamelCase')\n"
        "e.counter('missing_suffix')\n"
        "e.histogram('latency')\n"
        "e.gauge('depth_ok_depth')\n"
        "e.counter('waived')  # lint: metric-name-ok\n"
        "e.counter(dynamic_name)\n")
    offenses = lint.check_file(str(bad))
    msgs = [m for _p, _l, m in offenses]
    assert len(offenses) == 3, msgs
    assert any("not snake_case" in m for m in msgs)
    assert any("'missing_suffix'" in m for m in msgs)
    assert any("'latency'" in m for m in msgs)
