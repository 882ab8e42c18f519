"""The bulk import (tools/bulk_load.import_columns -> the tserver's
import_data -> DB.ingest_packed on every replica) on an RF3 cluster: rows
read back through YBClient, from every replica, then a write, a flush, a
compaction and a tserver restart over the imported files; and the typed,
grouped SELECT through the YCQL processor over imported rows plus
replicated writes and deletes, device partials and the rows path alike."""

import datetime
import decimal

import numpy as np
import pytest

from yugabyte_tpu.client.session import YBSession
from yugabyte_tpu.docdb import scan_spec as SS
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.integration.mini_cluster import (
    MiniCluster, MiniClusterOptions)
from yugabyte_tpu.tools import bulk_load
from yugabyte_tpu.utils import flags
from yugabyte_tpu.yql.cql.executor import QLProcessor

N_ORDERS = 90
D = decimal.Decimal


def dk(o, l):
    return DocKey(hash_components=(int(o),), range_components=(int(l),))


def make_columns(seed=11):
    rng = np.random.default_rng(seed)
    per = rng.integers(1, 5, size=N_ORDERS)
    n = int(per.sum())
    order = np.repeat(np.arange(1, N_ORDERS + 1) * 4, per)
    first = np.cumsum(per) - per
    line = np.arange(n) - np.repeat(first, per) + 1
    return {"ok": order.astype(np.int64), "ln": line.astype(np.int64),
            "qty": (rng.integers(1, 51, size=n) * 100).astype(np.int64),
            "price": rng.integers(90000, 9_000_000, size=n).astype(np.int64),
            "disc": rng.integers(0, 11, size=n).astype(np.int64),
            "tax": rng.integers(0, 9, size=n).astype(np.int64),
            "rf": [("R", "A", "N")[i] for i in rng.integers(0, 3, size=n)],
            "ls": [("O", "F")[i] for i in rng.integers(0, 2, size=n)],
            "ship": rng.integers(8100, 10400, size=n).astype(np.int64),
            "note": ["note %d" % i for i in range(n)]}


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    flags.set_flag("replication_factor", 3)
    prior = flags.get_flag("scan_pushdown_min_rows")
    flags.set_flag("scan_pushdown_min_rows", 0)
    c = MiniCluster(MiniClusterOptions(
        num_masters=1, num_tservers=3,
        fs_root=str(tmp_path_factory.mktemp("bulk")))).start()
    client = c.new_client()
    proc = QLProcessor(client)
    proc.execute("CREATE KEYSPACE w")
    proc.execute(
        "CREATE TABLE w.li (ok bigint, ln int, qty decimal(15,2), "
        "price decimal(15,2), disc decimal(15,2), tax decimal(15,2), "
        "rf char(1), ls char(1), ship date, note varchar, "
        "PRIMARY KEY ((ok), ln)) WITH tablets = 3")
    table = client.open_table("w", "li")
    c.wait_all_replicas_running(table.table_id)
    c.wait_for_table_leaders("w", "li")
    cols = make_columns()
    stats = bulk_load.import_columns(client, table, cols)
    yield c, client, proc, table, cols, stats
    flags.set_flag("scan_pushdown_min_rows", prior)
    c.shutdown()


def expected_rows(cols):
    names = [n for n in cols if n not in ("ok", "ln")]
    return {(int(cols["ok"][i]), int(cols["ln"][i])):
            {n: (int(cols[n][i]) if isinstance(cols[n], np.ndarray)
                 else cols[n][i]) for n in names}
            for i in range(len(cols["ok"]))}


def replicas_of(cluster, table):
    return [p for ts in cluster.tservers for p in ts.tablet_manager.peers()
            if p.tablet.schema.columns[0].name == "ok"]


def test_imported_rows_read_back_through_the_client_and_every_replica(env):
    cluster, client, _proc, table, cols, stats = env
    want = expected_rows(cols)
    assert stats["rows"] == len(want) and stats["tablets"] == 3
    assert stats["replica_imports"] == 9
    assert stats["entries"] == 9 * len(want) * 3    # 8 values + liveness
    keys = list(want)
    rows = client.multi_read(table, [dk(*k) for k in keys])
    for k, row in zip(keys, rows):
        got = row.to_dict(table.schema)
        assert {n: got[n] for n in want[k]} == want[k], k
    assert sum(1 for _ in client.scan(table)) == len(want)
    # every replica holds every row of its tablet, byte for byte the same
    peers = replicas_of(cluster, table)
    assert len(peers) == 9
    for p in peers:
        mine = [k for k in keys
                if client.meta_cache.lookup_tablet(
                    table.table_id, table.partition_key_for(dk(*k))
                ).tablet_id == p.tablet_id]
        got = p.tablet.multi_read([dk(*k) for k in mine])
        assert [r.to_dict(table.schema)["price"] for r in got] \
            == [want[k]["price"] for k in mine]
    # followers answer the client too (bounded-staleness reads)
    for p in peers:
        p.grant_vouch(0)
    for _ in range(6):
        rows = client.multi_read(table, [dk(*k) for k in keys[:40]],
                                 follower_read=True)
        assert [r.to_dict(table.schema)["qty"] for r in rows] \
            == [want[k]["qty"] for k in keys[:40]]


def test_an_import_into_the_wrong_tablet_is_refused(env):
    """The tserver holds every imported key to the replica's own range."""
    cluster, client, _proc, table, cols, _stats = env
    from yugabyte_tpu.rpc.messenger import RemoteError
    from yugabyte_tpu.tserver.tablet_service import _cmp_keys_to_bound
    key_mat, _codes = bulk_load.encode_doc_keys(table.schema, cols)
    tablets = bulk_load.table_tablets(client, table)
    owner_of_row0 = client.meta_cache.lookup_tablet(
        table.table_id, table.partition_key_for(
            dk(cols["ok"][0], cols["ln"][0])))
    other = next(t for t in tablets
                 if t.tablet_id != owner_of_row0.tablet_id)
    run = bulk_load.pack_tablet_run(
        table.schema, key_mat[:1], {n: c[:1] for n, c in cols.items()})
    with pytest.raises(RemoteError, match="outside tablet range"):
        client._messenger.call(
            other.replicas[0].addr, "tserver", "import_data",
            tablet_id=other.tablet_id, ht=None, n=run["n"],
            keys_blob=run["keys_blob"], key_offs=run["key_offs"].tobytes(),
            vals_blob=run["vals_blob"], val_offs=run["val_offs"].tobytes(),
            wid=run["wid"].tobytes())
    blob = np.frombuffer(b"abcabdab", dtype=np.uint8)
    offs = np.asarray([0, 3, 6, 8])
    assert _cmp_keys_to_bound(blob, offs, b"abd").tolist() == [-1, 0, -1]
    assert _cmp_keys_to_bound(blob, offs, b"ab").tolist() == [0, 0, 0]
    assert _cmp_keys_to_bound(blob, offs, b"aa").tolist() == [1, 1, 1]


def q1(proc, where="ship <= date '1998-12-01' - interval '90' day"):
    return proc.execute(
        "select rf, ls, sum(qty) as sum_qty, sum(price) as sum_base, "
        "sum(price*(1-disc)) as sum_disc_price, "
        "sum(price*(1-disc)*(1+tax)) as sum_charge, avg(qty) as avg_qty, "
        "avg(disc) as avg_disc, count(*) as n from w.li "
        f"where {where} group by rf, ls order by rf, ls")


def q1_reference(rows: dict, cutoff: int) -> list:
    groups = {}
    for r in rows.values():
        if r["ship"] <= cutoff:
            groups.setdefault((r["rf"], r["ls"]), []).append(r)
    out = []
    for (rf, ls), rs in sorted(groups.items()):
        n = len(rs)
        qty = sum(r["qty"] for r in rs)
        disc = sum(r["disc"] for r in rs)
        out.append([
            rf, ls, D(qty).scaleb(-2), D(sum(r["price"] for r in rs)
                                         ).scaleb(-2),
            D(sum(r["price"] * (100 - r["disc"]) for r in rs)).scaleb(-4),
            D(sum(r["price"] * (100 - r["disc"]) * (100 + r["tax"])
                  for r in rs)).scaleb(-6),
            (D(qty) / D(n)).scaleb(-2), (D(disc) / D(n)).scaleb(-2), n])
    return out


CUTOFF = (datetime.date(1998, 12, 1) - datetime.date(1970, 1, 1)).days - 90


def test_writes_deletes_flush_compaction_and_restart_over_imported_files(env):
    cluster, client, proc, table, cols, _stats = env
    want = expected_rows(cols)
    # replicated writes and deletes land ABOVE the import's hybrid time
    session = YBSession(client)
    gone = [k for k in want if k[0] in (4, 8, 12)]
    for k in gone:
        session.apply(table, QLWriteOp(WriteOpKind.DELETE_ROW, dk(*k)))
        del want[k]
    new = {"qty": 700, "price": 123456, "disc": 3, "tax": 2, "rf": "N",
           "ls": "O", "ship": 9000, "note": "fresh"}
    for k in ((6, 1), (6, 2), (402, 1)):     # between imported keys
        session.apply(table, QLWriteOp(WriteOpKind.INSERT, dk(*k),
                                       dict(new)))
        want[k] = dict(new)
    over = next(k for k in want if k[0] == 16)
    session.apply(table, QLWriteOp(WriteOpKind.UPDATE, dk(*over),
                                   {"qty": 4900}))
    want[over]["qty"] = 4900
    session.flush()
    session.close()
    rs = q1(proc)
    assert [list(r) for r in rs.rows] == q1_reference(want, CUTOFF)
    assert rs.pushdown == {"tablets": 3, "from_rows": 0}
    for p in replicas_of(cluster, table):
        p.tablet.flush()
        p.tablet.compact()
        assert p.tablet.regular_db.n_live_files == 1
    assert [list(r) for r in q1(proc).rows] == q1_reference(want, CUTOFF)
    cluster.restart_tablet_server(1)
    cluster.wait_all_replicas_running(table.table_id)
    cluster.wait_for_table_leaders("w", "li")
    assert [list(r) for r in q1(proc).rows] == q1_reference(want, CUTOFF)
    keys = list(want)
    for p in replicas_of(cluster, table):
        mine = [k for k in keys
                if client.meta_cache.lookup_tablet(
                    table.table_id, table.partition_key_for(dk(*k))
                ).tablet_id == p.tablet_id]
        got = p.tablet.multi_read([dk(*k) for k in mine] + [dk(*gone[0])])
        assert got[-1] is None
        assert [r.to_dict(table.schema)["qty"] for r in got[:-1]] \
            == [want[k]["qty"] for k in mine]
    env[4]["_live"] = want


def test_a_refused_pushdown_is_answered_by_the_rows_path_alike(
        env, monkeypatch):
    _cluster, _client, proc, _table, cols, _stats = env
    want = cols.get("_live") or expected_rows(cols)
    pushed = q1(proc)
    assert pushed.pushdown["from_rows"] == 0
    from yugabyte_tpu.ops import scan_group

    def refuse(*_a, **_kw):
        raise SS.PushdownUnsupported("overflow")

    monkeypatch.setattr(scan_group, "group_aggregate_sources", refuse)
    from_rows = q1(proc)
    assert from_rows.pushdown == {"tablets": 3, "from_rows": 3}
    assert from_rows.rows == pushed.rows == q1_reference(want, CUTOFF)
    q6 = ("select sum(price*disc) as revenue, count(*) as n from w.li where "
          "ship >= date '1994-01-01' and ship < date '1994-01-01' + "
          "interval '1' year and disc between 0.02 and 0.07 and qty < 24")
    a = proc.execute(q6)
    monkeypatch.undo()
    b = proc.execute(q6)
    lo = (datetime.date(1994, 1, 1) - datetime.date(1970, 1, 1)).days
    sel = [r for r in want.values() if lo <= r["ship"] < lo + 365
           and 2 <= r["disc"] <= 7 and r["qty"] < 2400]
    revenue = D(sum(r["price"] * r["disc"] for r in sel)).scaleb(-4) \
        if sel else None
    assert a.rows == b.rows == [[revenue, len(sel)]]
    assert a.pushdown["from_rows"] == 3 and b.pushdown["from_rows"] == 0


def test_typed_cells_through_plain_statements(env):
    _cluster, _client, proc, _table, _cols, _stats = env
    proc.execute("INSERT INTO w.li (ok, ln, qty, price, disc, tax, rf, ls, "
                 "ship, note) VALUES (9001, 1, 17.00, 1234.56, 0.05, 0.08, "
                 "'A', 'F', date '1995-03-15', 'typed')")
    (row,) = proc.execute("SELECT qty, price, disc, ship, rf FROM w.li "
                          "WHERE ok = 9001 AND ln = 1").rows
    assert row == [D("17.00"), D("1234.56"), D("0.05"),
                   datetime.date(1995, 3, 15), "A"]
    with pytest.raises(Exception, match="decimals"):
        proc.execute("INSERT INTO w.li (ok, ln, qty) VALUES (9001, 2, "
                     "1.005)")
    proc.execute("DELETE FROM w.li WHERE ok = 9001 AND ln = 1")
