"""The cells of PR 32, rehearsed whole on the CPU backend: a sound run is
`correct` with nothing failed, each control reads `correct: false`, a fault
under the timed path is caught, the generator keeps the specification's
domains, the reference agrees with a row-by-row count, and every new
per-layer entry has its file and a reader that can read it."""

import datetime
import decimal
import importlib
import json
import os

import numpy as np
import pytest

from benchmarks import datagen, datagen_tpch, reference_tpch
from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESIDENT, TPCH = "compact-resident.kv64", "tpch-q1q6.rf3"


def rehearse(capsys, workload, seed, seconds, *extra):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--rehearse", *extra])
    out = capsys.readouterr()
    lines = [json.loads(l) for l in out.out.splitlines()
             if l.startswith("{")]
    line = lines[-1]
    assert list(line)[-1] == "compared" and line["rehearsal"]
    assert not line["metrics"]          # a CPU run never prints a rate
    assert rc == (0 if line["correct"] else 1)
    return line, lines


# ------------------------------------------------------------ the cells

def test_the_resident_chain_is_correct_and_every_job_stays_resident(capsys):
    line, lines = rehearse(capsys, RESIDENT, 2**31 + 117, 1, "--trace", "1")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 3
    assert all(c["value"] == 0 for c in line["compared"].values())
    tally = next(l for l in lines if "window_jobs" in l)
    n = tally["window_jobs"]
    assert tally["device_decisions"] == n and tally["pallas_merges"] == n
    assert tally["jobs_off_device"] == 0
    assert tally["jobs_that_left_the_resident_path"] == 0
    deltas = next(l for l in lines if "traced_deltas" in l)["traced_deltas"]
    assert deltas["bench_jobs"] == 2
    assert 0 < deltas["bench_flush_wall_ms"] < deltas["bench_chains_wall_ms"]
    assert deltas["bench_jobs_wall_ms"] < deltas["bench_chains_wall_ms"]
    for stage in ("raw_read", "raw_parse", "decode"):
        assert not deltas.get(
            f"compaction_pipeline_stage_{stage}_total_ms")


def test_the_resident_cells_control_is_not_correct(capsys):
    line, _ = rehearse(capsys, RESIDENT, 41, 1, "--control",
                       "history_cutoff_zero")
    assert not line["correct"]
    assert line["compared"]["jobs_differing_from_native"]["value"] \
        == line["attempted"]
    assert line["compared"]["rows_differing_from_reference"]["value"] > 0
    assert line["compared"]["native_rows_differing_from_reference"][
        "value"] == 0


@pytest.fixture
def digest_sample(request):
    """`resident_digest_sample` at the test's parameter (1.0, every
    write-through install digest-checked, unless parametrised; None leaves
    the default), restored after."""
    from yugabyte_tpu.storage import integrity  # noqa: F401 (defines the flag)
    from yugabyte_tpu.utils import flags
    old = flags.get_flag("resident_digest_sample")
    value = getattr(request, "param", 1.0)
    if value is not None:
        flags.set_flag("resident_digest_sample", value)
    yield
    flags.set_flag("resident_digest_sample", old)


def test_a_digest_checked_job_is_let_off_the_blocks_its_check_decodes(
        capsys, digest_sample):
    """Every job's outputs digest-checked: each check decodes its file's
    blocks, and none of those makes the job a failed one."""
    line, lines = rehearse(capsys, RESIDENT, 2**31 + 121, 1)
    assert line["correct"] and line["failed"] == 0
    tally = next(l for l in lines if "window_jobs" in l)
    assert tally["jobs_the_digest_check_sampled"] == tally["window_jobs"]
    assert tally["digest_blocks_exempted"] > 0
    assert tally["jobs_that_left_the_resident_path"] == 0


@pytest.mark.parametrize("digest_sample", [None, 1.0], indirect=True,
                         ids=["default_digest_sample", "every_job_checked"])
def test_fault_a_flushs_write_through_dropped_is_a_failed_job(
        capsys, monkeypatch, digest_sample):
    """One flush of the window does not reach the slab cache: its job finds
    an input missing and ingests it from the file. The answer is still
    right; the job left the resident path, and is counted, also where the
    digest check's own blocks are let off."""
    from yugabyte_tpu.storage.device_cache import DeviceSlabCache
    real = DeviceSlabCache.stage
    calls = []

    def dropped(self, key, slab, *a, **kw):
        calls.append(key)
        if len(calls) == 30:            # past set-up and the warm-up chains
            return None
        return real(self, key, slab, *a, **kw)

    monkeypatch.setattr(DeviceSlabCache, "stage", dropped)
    line, lines = rehearse(capsys, RESIDENT, 43, 2)
    assert len(calls) > 30 and line["correct"]
    assert line["failed"] >= 1
    tally = next(l for l in lines if "window_jobs" in l)
    assert tally["jobs_that_left_the_resident_path"] >= 1
    assert any(m.get("slab_cache_misses")
               for m in tally["decode_meters_moved"])


def test_fault_a_digest_mismatch_is_a_failed_job(capsys, monkeypatch,
                                                 digest_sample):
    """One digest check of the window finds the write-through entry
    diverged from its file: the program drops the entry, the files on disk
    are right, and the job is counted under `failed`."""
    from yugabyte_tpu.storage import integrity
    real = integrity.verify_resident_entry
    calls = []

    def diverged(staged, base_path):
        calls.append(base_path)
        errors = real(staged, base_path)     # the check's decode is made
        if len(calls) == 8:                  # past the warm-up chains
            return ["planted divergence"]
        return errors

    monkeypatch.setattr(integrity, "verify_resident_entry", diverged)
    line, lines = rehearse(capsys, RESIDENT, 53, 2)
    assert len(calls) > 8 and line["correct"]
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert line["failed"] >= 1
    tally = next(l for l in lines if "window_jobs" in l)
    assert any(m.get("resident_digest_mismatch_total") == 1
               for m in tally["decode_meters_moved"])


def test_tpch_is_correct_and_every_tablet_answers_from_the_device(capsys):
    line, lines = rehearse(capsys, TPCH, 2**31 + 119, 2, "--trace", "1")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 8
    assert all(c["value"] == 0 for c in line["compared"].values())
    window = next(l for l in lines if "scan_counters" in l)
    assert window["answered"] == line["attempted"]
    c = window["scan_counters"]
    # every query is one dispatch a tablet (two tablets in the rehearsal)
    assert c["dispatches"] == 2 * line["attempted"] and c["stage_miss"] == 0
    load = next(l for l in lines if "load" in l)["load"]
    assert load["bulk_load"]["replica_imports"] == 6
    assert load["refresh"]["rows_inserted"] > 0 \
        and load["refresh"]["rows_deleted"] > 0
    assert set(load["live_files_per_replica"]) == {1}
    checked = next(l for l in lines if "replicas_checked" in l)
    assert checked["replicas_checked"] == 6
    assert checked["replica_tablets_answered_from_rows"] == 0
    compiles = next(l for l in lines if "compiles_in_window" in l)
    assert compiles["compiles_in_window"] == 0
    deltas = next(l for l in lines if "traced_deltas" in l)["traced_deltas"]
    # (the traced span opens and closes mid-query: a query's dispatches may
    # fall on either side of its note)
    assert abs(deltas["bench_scan_dispatches"]
               - 2 * deltas["bench_queries"]) <= 8
    for stage in ("device_dispatch", "device_wait", "device_enqueue",
                  "query_pack", "stage_lookup", "partial_build"):
        assert 0 < deltas[f"serve_path_scan_{stage}_ms"] \
            < deltas["serve_path_scan_e2e_ms"]
    assert not deltas.get("serve_path_scan_host_fallback_ms")


def test_the_tpch_cells_control_is_not_correct(capsys):
    """One RF1 order withheld from the reference: the answers differ by
    its lines, in the window and on every replica."""
    line, lines = rehearse(capsys, TPCH, 47, 2, "--control",
                           "rf1_order_withheld")
    assert not line["correct"]
    assert line["compared"]["answers_differing_from_reference"]["value"] >= 1
    assert line["compared"]["replica_answers_wrong"]["value"] >= 3
    load = next(l for l in lines if "load" in l)["load"]
    assert load["refresh"]["order_withheld"] is not None


def test_fault_one_tablets_partial_altered_is_not_correct(capsys,
                                                          monkeypatch):
    """Every seventh device partial comes back with one more row in its
    first group: a well-formed answer, and a wrong one."""
    from yugabyte_tpu.ops import scan_group
    real = scan_group.group_aggregate_sources
    calls = []

    def altered(*a, **kw):
        out = real(*a, **kw)
        calls.append(1)
        if len(calls) % 7 == 0 and out["groups"]:
            out["groups"][0]["rows"] += 1
        return out

    monkeypatch.setattr(scan_group, "group_aggregate_sources", altered)
    line, _ = rehearse(capsys, TPCH, 53, 2)
    assert len(calls) > 14 and not line["correct"]
    assert line["compared"]["answers_differing_from_reference"]["value"] >= 1


def test_fault_a_tablet_answering_from_rows_is_a_failed_query(capsys,
                                                              monkeypatch):
    from yugabyte_tpu.docdb.scan_spec import PushdownUnsupported
    from yugabyte_tpu.ops import scan_group
    real = scan_group.group_aggregate_sources
    calls = []

    def refused(*a, **kw):
        calls.append(1)
        if len(calls) > 24 and len(calls) % 9 == 0:     # past set-up
            raise PushdownUnsupported("overflow")
        return real(*a, **kw)

    monkeypatch.setattr(scan_group, "group_aggregate_sources", refused)
    line, lines = rehearse(capsys, TPCH, 59, 2)
    # the rows path answers exactly; the query still counts as failed
    assert line["compared"]["answers_differing_from_reference"]["value"] == 0
    assert line["failed"] >= 1
    tally = next(l for l in lines if "queries_given_up" in l)
    assert tally["queries_with_a_tablet_answered_from_rows"] >= 1


# -------------------------------------------- generator and reference

def test_lineitem_keeps_the_specifications_domains():
    gen = datagen_tpch.Lineitem(7, 0.002)
    li = gen.initial()
    n = len(li["l_orderkey"])
    assert gen.n_orders == 3000 and 3000 <= n <= 21000
    assert set(np.unique(li["l_orderkey"] % 32)) <= set(range(1, 9))
    assert li["l_linenumber"].min() == 1 and li["l_linenumber"].max() <= 7
    assert 100 <= li["l_quantity"].min() and li["l_quantity"].max() <= 5000
    assert li["l_discount"].min() == 0 and li["l_discount"].max() == 10
    assert li["l_tax"].min() == 0 and li["l_tax"].max() == 8
    retail = (90000 + (li["l_partkey"] // 10) % 20001
              + 100 * (li["l_partkey"] % 1000))
    assert (li["l_extendedprice"] == li["l_quantity"] // 100 * retail).all()
    assert (li["l_receiptdate"] - li["l_shipdate"]).min() >= 1
    assert (li["l_receiptdate"] - li["l_shipdate"]).max() <= 30
    rf, ls = np.asarray(li["l_returnflag"]), np.asarray(li["l_linestatus"])
    late = li["l_receiptdate"] > datagen_tpch.CURRENTDATE
    assert set(rf[late]) == {"N"} and set(rf[~late]) == {"R", "A"}
    assert ((ls == "O") == (li["l_shipdate"]
                            > datagen_tpch.CURRENTDATE)).all()
    assert set(li["l_shipinstruct"]) == set(datagen_tpch.INSTRUCTIONS)
    assert set(li["l_shipmode"]) == set(datagen_tpch.MODES)
    assert all(1 <= len(c) <= 43 for c in li["l_comment"])
    again = datagen_tpch.Lineitem(7, 0.002).initial()
    assert all((np.asarray(li[k]) == np.asarray(again[k])).all()
               for k in li)
    rf1 = gen.rf1()
    assert set(np.unique(rf1["l_orderkey"] % 32)) <= set(range(9, 17))
    assert not np.isin(rf1["l_orderkey"], li["l_orderkey"]).any()
    assert np.isin(gen.rf2_orderkeys(), li["l_orderkey"]).all()
    assert len(np.unique(rf1["l_orderkey"])) == gen.n_refresh == 3
    assert "decimal(15,2)" in datagen_tpch.create_table_cql("k", "t", 18)


def test_the_reference_agrees_with_a_row_by_row_count():
    gen = datagen_tpch.Lineitem(11, 0.001)
    rows = reference_tpch.apply_refresh(gen.initial(), gen.rf1(),
                                        gen.rf2_orderkeys().tolist())
    assert not np.isin(rows["l_orderkey"], gen.rf2_orderkeys()).any()
    rng = datagen.rng_for(3, 1)
    p1, p6 = datagen_tpch.q1_params(rng), datagen_tpch.q6_params(rng)
    cutoff = (datetime.date(1998, 12, 1)
              - datetime.timedelta(days=p1["delta"])
              - datetime.date(1970, 1, 1)).days
    want, revenue, n6 = {}, 0, 0
    lo = (datetime.date(p6["year"], 1, 1) - datetime.date(1970, 1, 1)).days
    hi = (datetime.date(p6["year"] + 1, 1, 1)
          - datetime.date(1970, 1, 1)).days
    for i in range(len(rows["l_orderkey"])):
        q, p, d, t, s = (int(rows[c][i]) for c in (
            "l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_shipdate"))
        if s <= cutoff:
            g = want.setdefault((rows["l_returnflag"][i],
                                 rows["l_linestatus"][i]), [0] * 6)
            for j, v in enumerate((q, p, p * (100 - d),
                                   p * (100 - d) * (100 + t), d, 1)):
                g[j] += v
        if lo <= s < hi and p6["discount"] - 1 <= d <= p6["discount"] + 1 \
                and q < p6["quantity"] * 100:
            revenue += p * d
            n6 += 1
    raw = reference_tpch.q1_raw(rows, p1["delta"])
    assert {k: g["sums"] + [g["rows"]] for k, g in raw.items()} == want
    assert reference_tpch.q6_raw(rows, **p6) == {"rows": n6, "sum": revenue}
    first = reference_tpch.q1_rows(raw)[0]
    key = (first[0], first[1])
    assert first[2] == decimal.Decimal(want[key][0]).scaleb(-2)
    assert first[5] == decimal.Decimal(want[key][3]).scaleb(-6)
    assert first[6] == (decimal.Decimal(want[key][0])
                        / want[key][5]).scaleb(-2)


# ------------------------------------------------------------- the lint

NEW_METRICS = (
    "flush_share.resident", "job_rows_per_s.resident", "rpc_share.scan",
    "device_dispatch_share.scan", "device_wait_share.scan",
    "device_enqueue_share.scan", "query_pack_share.scan",
    "stage_lookup_share.scan", "partial_build_share.scan",
    "device_dispatch_other_share.scan", "host_fallback_share.scan",
    "stage_miss_share.scan", "device_busy_ms_per_mrow.scan",
    "scan_query_roofline")


def test_every_new_per_layer_entry_has_its_file_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[RESIDENT]["config"] == "kv64-tablet"
    assert cells[TPCH]["config"] == "tpch-lineitem-rf3"
    assert cells[RESIDENT]["chips"] == cells[TPCH]["chips"] == 1
    for name in NEW_METRICS:
        m = by_name[name]
        cell = RESIDENT if name.endswith(".resident") else TPCH
        assert m["workloads"] == [cell], name
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module("benchmarks.readers."
                                         + spec["reader"])
        # nothing to read (the parent has no such span or counter) is
        # None, never an exception
        assert reader.read(spec, {"deltas": {}, "trace": None, "window": {},
                                  "device_kind": "TPU v5 lite"}) is None
    assert by_name["scan_query_roofline"]["unit"] == "%"
    # the new cells ride the accepted metrics they report
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert RESIDENT in e2e["compaction_rows_per_s"]
    assert TPCH in e2e["ops_per_s"] and TPCH in e2e["read_p95_ms"]
    for name in ("device_idle_share.serve", "idle_unspanned_share.serve"):
        assert TPCH in by_name[name]["workloads"]
    for name, m in by_name.items():
        if name.endswith(".compact") or name == "compact_job_roofline":
            assert RESIDENT in m["workloads"], name


def test_the_scan_roofline_counts_the_staged_bytes():
    from benchmarks import roofline, roofline_scan
    # lineitem's 26-byte column keys: 7 key words + 8 fixed rows + 4 value
    # rows, four bytes each
    assert roofline_scan.entry_bytes(26) == (8 + 7 + 4) * 4 == 76
    assert roofline_scan.scan_query_bytes(9_000_000, 26) == 684_000_000
    # under a second's worth of HBM traffic at the chip's peak
    assert roofline.bytes_bound_s(684_000_000, "TPU v5 lite") \
        == pytest.approx(684e6 / 819e9)
