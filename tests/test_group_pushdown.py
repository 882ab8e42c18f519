"""The typed, grouped aggregate pushdown (ops/scan_group.py) against plain
Python integers: TPC-H Q1's and Q6's every aggregate over seeded rows,
across MVCC snapshots, NULLs, deletes, empty groups; a refused spec answered
exactly by the rows path; a sum the accumulator cannot hold refused, never
wrapped."""

import random

import pytest

from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
from yugabyte_tpu.docdb import scan_spec as SS
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.ops import scan_group
from yugabyte_tpu.storage import offload_policy
from yugabyte_tpu.storage.device_cache import DeviceSlabCache
from yugabyte_tpu.tablet.tablet import Tablet, TabletOptions
from yugabyte_tpu.utils import flags

D = DataType
DEC = dict(type_params=(15, 2))
SCHEMA = Schema(columns=[
    ColumnSchema("ok", D.INT64), ColumnSchema("ln", D.INT32),
    ColumnSchema("qty", D.DECIMAL, **DEC),
    ColumnSchema("price", D.DECIMAL, **DEC),
    ColumnSchema("disc", D.DECIMAL, **DEC),
    ColumnSchema("tax", D.DECIMAL, **DEC),
    ColumnSchema("rf", D.CHAR, type_params=(1,)),
    ColumnSchema("ls", D.CHAR, type_params=(1,)),
    ColumnSchema("ship", D.DATE),
    ColumnSchema("cmt", D.STRING)],
    num_hash_key_columns=1, num_range_key_columns=1)

Q1 = [["sum", "qty"], ["sum", "price"],
      ["sum", [["col", "price"], ["1-", "disc"]]],
      ["sum", [["col", "price"], ["1-", "disc"], ["1+", "tax"]]],
      ["avg", "qty"], ["avg", "price"], ["avg", "disc"], ["count", None]]
Q6 = [["sum", [["col", "price"], ["col", "disc"]]]]
Q6_WHERE = [["ship", ">=", 8500], ["ship", "<", 9500], ["disc", ">=", 2],
            ["disc", "<=", 7], ["qty", "<", 2400]]
_CMP = {"<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
        ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
        "=": lambda a, b: a == b, "!=": lambda a, b: a != b}


def dk(o, l):
    return DocKey(hash_components=(o,), range_components=(l,))


@pytest.fixture(autouse=True)
def _flags():
    prior = flags.get_flag("scan_pushdown_min_rows")
    flags.set_flag("scan_pushdown_min_rows", 0)
    offload_policy.bucket_quarantine().clear()
    yield
    flags.set_flag("scan_pushdown_min_rows", prior)
    offload_policy.bucket_quarantine().clear()


@pytest.fixture
def tablet(tmp_path):
    import jax
    dev = jax.devices()[0]
    t = Tablet("t-group", str(tmp_path), SCHEMA, options=TabletOptions(
        auto_compact=False, device=dev,
        device_cache=DeviceSlabCache(device=dev), block_entries=32))
    yield t
    t.close()


def lineitem_row(rng):
    return {"qty": rng.randint(1, 50) * 100,
            "price": rng.randint(90000, 10_000_000),
            "disc": rng.choice([None, rng.randint(0, 10)]),
            "tax": rng.randint(0, 8), "rf": rng.choice("RAN"),
            "ls": rng.choice([None, "O", "F"]),
            "ship": rng.randint(8000, 10500),
            "cmt": "x" * rng.randint(1, 30)}


def workload(t, seed, phases=3, ops=120):
    """Inserts, row deletes (RF2's shape), overwrites and NULLs across
    flushes; the last phase stays in the memtable. One snapshot a phase."""
    rng = random.Random(seed)
    snaps = []
    for phase in range(phases):
        for _ in range(ops):
            o, l = rng.randint(1, 40), rng.randint(1, 4)
            r = rng.random()
            if r < 0.75:
                t.write([QLWriteOp(WriteOpKind.INSERT, dk(o, l),
                                   lineitem_row(rng))])
            elif r < 0.9:
                t.write([QLWriteOp(WriteOpKind.DELETE_ROW, dk(o, l))])
            else:
                t.write([QLWriteOp(WriteOpKind.UPDATE, dk(o, l),
                                   {"qty": -rng.randint(1, 50) * 100})])
        snaps.append(t.clock.now())
        if phase < phases - 1:
            t.flush()
    return snaps


def rows_answer(t, filters, spec, read_ht=None):
    """The rows path's answer: decoded rows, the executor's NULL rule (a
    NULL fails every operator), Python integers."""
    dicts = []
    for r in t.scan(read_ht, use_device=False):
        d = r.to_dict(SCHEMA)
        if all(d.get(c) is not None and _CMP[op](d[c], v)
               for c, op, v in filters):
            dicts.append(d)
    return SS.group_partial_from_dicts(spec, dicts)


def canon(partial, minmax):
    out = {}
    for g in SS.combine_group_partials([partial])["groups"]:
        terms = [dict(t) for t in g["terms"]]
        if not minmax:
            for st in terms:
                st["min"] = st["max"] = None
        out[tuple(g["key"])] = (g["rows"], terms)
    return out


def check(t, filters, aggs, group_by, read_ht=None):
    spec, why = SS.compile_group_aggregate(SCHEMA, filters, aggs, group_by)
    assert spec is not None, why
    got = t.scan_aggregate(read_ht, spec=spec)
    assert got is not None, "the grouped pushdown fell back"
    assert canon(got, spec.wants_minmax) == canon(
        rows_answer(t, filters, spec, read_ht), spec.wants_minmax)
    return got


@pytest.mark.parametrize("seed", [1, 2])
def test_q1_and_q6_every_aggregate_across_snapshots(tablet, seed):
    snaps = workload(tablet, seed)
    before = scan_group.group_metrics()["dispatches"].value()
    for ht in [None] + snaps:
        q1 = check(tablet, [["ship", "<=", 10000]], Q1, ["rf", "ls"], ht)
        check(tablet, Q6_WHERE, Q6, [], ht)
    # NULL linestatus is a group of its own, NULL discounts leave their
    # terms' nonnull counts under the group's row count
    keys = [tuple(g["key"]) for g in q1["groups"]]
    assert any(k[1] is None for k in keys) and len(keys) >= 6
    assert any(g["terms"][2]["nonnull"] < g["rows"] for g in q1["groups"])
    assert scan_group.group_metrics()["dispatches"].value() - before == 8


def test_min_max_count_col_and_char_predicates(tablet):
    workload(tablet, 3)
    check(tablet, [], [["min", "qty"], ["max", [["col", "qty"],
                                                ["col", "price"]]],
                       ["count", None], ["sum", "ship"], ["min", "ship"]],
          ["rf"])
    check(tablet, [["rf", "=", "R"]], [["count", None], ["count", "disc"]],
          ["ls"])
    check(tablet, [["rf", "!=", "N"], ["ls", ">=", "O"]],
          [["sum", "qty"]], [])


def test_empty_result_and_a_group_one_tablet_alone_holds(tablet, tmp_path):
    workload(tablet, 4)
    spec, _ = SS.compile_group_aggregate(
        SCHEMA, [["ship", ">", 99999]], Q1, ["rf", "ls"])
    assert tablet.scan_aggregate(spec=spec) == {"groups": []}
    # two tablets' partials: group ("Z", "F") exists on the second only
    import jax
    dev = jax.devices()[0]
    other = Tablet("t-other", str(tmp_path / "other"), SCHEMA,
                   options=TabletOptions(
                       auto_compact=False, device=dev,
                       device_cache=DeviceSlabCache(device=dev)))
    try:
        row = lineitem_row(random.Random(9))
        row.update(rf="Z", ls="F", disc=5)
        other.write([QLWriteOp(WriteOpKind.INSERT, dk(1000, 1), row)])
        spec, _ = SS.compile_group_aggregate(SCHEMA, [], Q1, ["rf", "ls"])
        a, b = tablet.scan_aggregate(spec=spec), \
            other.scan_aggregate(spec=spec)
        merged = canon(SS.combine_agg_partials([a, b]), False)
        assert merged[("Z", "F")][0] == 1
        want = canon(rows_answer(tablet, [], spec), False)
        want[("Z", "F")] = canon(rows_answer(other, [], spec),
                                 False)[("Z", "F")]
        assert merged == want
    finally:
        other.close()


def test_a_delete_hides_its_lines(tablet):
    rng = random.Random(5)
    for l in (1, 2, 3):
        row = lineitem_row(rng)
        row.update(rf="R", ls="F", disc=4)
        tablet.write([QLWriteOp(WriteOpKind.INSERT, dk(7, l), row)])
    tablet.flush()
    before = tablet.clock.now()
    tablet.write([QLWriteOp(WriteOpKind.DELETE_ROW, dk(7, 2))])
    spec, _ = SS.compile_group_aggregate(SCHEMA, [], Q1, ["rf", "ls"])
    assert tablet.scan_aggregate(spec=spec)["groups"][0]["rows"] == 2
    assert tablet.scan_aggregate(before, spec=spec)["groups"][0]["rows"] == 3
    check(tablet, [], Q1, ["rf", "ls"])


def test_a_sum_past_the_accumulator_is_refused_never_wrapped(tablet):
    """At an inflated scale (prices near 10^13) price x (100 - disc) x
    (100 + tax) over a few rows passes 2^63: the dispatch is refused by
    reason `overflow`, and the rows path answers exactly."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    rng = random.Random(6)
    for l in range(1, 9):
        row = lineitem_row(rng)
        row.update(price=10 ** 15 - l, disc=0, tax=8, rf="N", ls="O")
        tablet.write([QLWriteOp(WriteOpKind.INSERT, dk(1, l), row)])
    spec, _ = SS.compile_group_aggregate(SCHEMA, [], Q1, ["rf", "ls"])
    counter = ROOT_REGISTRY.entity("server", "scan_pushdown").counter(
        "scan_pushdown_fallback_overflow_total", "")
    before = counter.value()
    assert tablet.scan_aggregate(spec=spec) is None
    assert counter.value() == before + 1
    exact = rows_answer(tablet, [], spec)["groups"][0]
    charge = exact["terms"][3]["sum"]
    assert charge == sum((10 ** 15 - l) * 100 * 108 for l in range(1, 9))
    assert charge >= 1 << 63             # a 64-bit sum would have wrapped
    # the same rows' sums that DO fit are answered on the device
    check(tablet, [], [["sum", "price"], ["sum", "qty"], ["count", None]],
          ["rf"])


def test_more_groups_than_slots_is_refused_to_the_rows_path(tablet):
    rng = random.Random(8)
    for o in range(1, scan_group.GROUP_SLOTS + 4):
        row = lineitem_row(rng)
        row["ship"] = 9000 + o              # every row a group of its own
        tablet.write([QLWriteOp(WriteOpKind.INSERT, dk(o, 1), row)])
    spec, _ = SS.compile_group_aggregate(SCHEMA, [], [["count", None]],
                                         ["ship"])
    assert tablet.scan_aggregate(spec=spec) is None
    spec, _ = SS.compile_group_aggregate(
        SCHEMA, [["ship", "<=", 9000 + scan_group.GROUP_SLOTS]],
        [["count", None]], ["ship"])
    assert len(tablet.scan_aggregate(spec=spec)["groups"]) \
        == scan_group.GROUP_SLOTS


def test_compile_refuses_what_the_kernel_cannot_answer():
    c = SS.compile_group_aggregate
    assert c(SCHEMA, [], [["sum", "cmt"]], [])[1] == "agg_type"
    assert c(SCHEMA, [], [["sum", "rf"]], [])[1] == "agg_type"
    assert c(SCHEMA, [], [["count", None]], ["cmt"])[1] == "group_type"
    assert c(SCHEMA, [], [["count", None]], ["rf", "ls", "ship"])[1] \
        == "group_width"
    assert c(SCHEMA, [["cmt", "=", "x"]], [["count", None]], [])[1] == "type"
    assert c(SCHEMA, [], [["sum", [["col", "qty"]] * 4]], [])[1] \
        == "agg_width"
    assert c(SCHEMA, [], [["sum", [["2*", "qty"]]]], [])[1] == "agg_type"
    # the scalar kernel keeps the shapes it had; typed columns move over
    assert not SS.wants_group_kernel(SCHEMA, [], [["sum", "ok"]], None)
    assert SS.wants_group_kernel(SCHEMA, [], [["sum", "qty"]], None)
    assert SS.wants_group_kernel(SCHEMA, [["ship", "<", 9]],
                                 [["count", None]], None)
    spec, why = c(SCHEMA, [["ship", "<=", 9]], Q1, ["rf", "ls"])
    assert why == "" and len(spec.terms) == 5 and len(spec.cids) == 7
    assert [a.scale for a in spec.aggregates] == [2, 2, 4, 6, 2, 2, 2, 0]


def test_docs_longer_than_the_short_path_take_every_step(tablet):
    """A row overwritten forty times is a doc of fifty entries: past the
    four-step short path of the segmented sum, so the full ladder runs;
    the newest versions alone are summed."""
    from yugabyte_tpu.ops import scan_group as sg
    rng = random.Random(12)
    for o in (1, 2, 3):
        row = lineitem_row(rng)
        row.update(rf="A", ls="F", disc=3)
        tablet.write([QLWriteOp(WriteOpKind.INSERT, dk(o, 1), row)])
    for i in range(40):
        tablet.write([QLWriteOp(WriteOpKind.UPDATE, dk(2, 1),
                                {"qty": 100 * (i + 1), "tax": i % 9})])
    assert 40 + 9 > sg._SHORT_DOC
    got = check(tablet, [], Q1, ["rf", "ls"])
    (g,) = got["groups"]
    assert g["rows"] == 3
    tablet.flush()
    check(tablet, [["qty", ">=", 4000]], Q1, ["rf", "ls"])
