"""QLProcessor: analyze + execute parsed YCQL statements over the client.

Capability parity with the reference (ref: src/yb/yql/cql/ql/ — analyzer in
ptree/, executor in exec/executor.cc, QLProcessor ql_processor.h:65 with its
parse-tree cache for prepared statements). Semantics carried over:

- INSERT is an upsert; UPDATE touches only assigned columns.
- SELECT with the full primary key is a point read; with only the hash key
  it scans one partition; otherwise a (filtered) full scan.
- BEGIN TRANSACTION ... END TRANSACTION runs its DML atomically through a
  snapshot-isolated distributed transaction, retried on conflict like the
  reference's CQL transaction retry loop.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from yugabyte_tpu.client.client import YBClient, YBTable
from yugabyte_tpu.client.transaction import (
    TransactionError, TransactionManager)
from yugabyte_tpu.common import jsonb
from yugabyte_tpu.common.hybrid_time import HybridTime
from yugabyte_tpu.common.schema import (
    ColumnSchema, DataType, Schema, SortingType)
from yugabyte_tpu.docdb.doc_key import DocKey
from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
from yugabyte_tpu.utils.status import Status, StatusError
from yugabyte_tpu.yql import bfunc
from yugabyte_tpu.yql import index_maintenance as IM
from yugabyte_tpu.yql.cql import grouped as _grouped
from yugabyte_tpu.yql.cql import parser as P

_CQL_TYPES = {
    "TEXT": DataType.STRING, "VARCHAR": DataType.STRING,
    "INT": DataType.INT32, "BIGINT": DataType.INT64,
    "COUNTER": DataType.INT64, "SMALLINT": DataType.INT32,
    "DOUBLE": DataType.DOUBLE, "FLOAT": DataType.FLOAT,
    "BOOLEAN": DataType.BOOL, "BLOB": DataType.BINARY,
    "TIMESTAMP": DataType.TIMESTAMP, "UUID": DataType.STRING,
    "TIMEUUID": DataType.STRING, "VARINT": DataType.INT64,
    "JSONB": DataType.JSONB,
    # exact fixed-point, calendar and fixed-length text (TPC-H's types):
    # DECIMAL(p,s) / CHAR(n) carry their parameters in the type text
    "DECIMAL": DataType.DECIMAL, "NUMERIC": DataType.DECIMAL,
    "DATE": DataType.DATE, "CHAR": DataType.CHAR,
}


def _split_type_params(cql_t: str):
    """'DECIMAL(15,2)' -> ('DECIMAL', (15, 2)); 'INT' -> ('INT', None)."""
    if cql_t.endswith(")") and "(" in cql_t and "<" not in cql_t:
        base, _, rest = cql_t.partition("(")
        return base, tuple(int(x) for x in rest[:-1].split(","))
    return cql_t, None


_CQL_AGGS = ("count", "sum", "avg", "min", "max")


def _extract_cql_aggregates(items):
    """[(func, col_or_None)] when EVERY select item is an aggregate call
    over a bare column (or COUNT(*)); None when no item is. Mixing
    aggregates and plain columns is invalid in CQL (no GROUP BY)."""
    def is_agg(i):
        return (isinstance(i, P.FuncCall) and i.name.lower() in _CQL_AGGS
                and len(i.args) == 1
                and (i.args[0] == "*"
                     or isinstance(i.args[0], P.ColumnRef)))
    flags = [is_agg(i) for i in items]
    if not any(flags):
        return None
    if not all(flags):
        raise StatusError(Status.InvalidArgument(
            "aggregates cannot be mixed with plain columns (no GROUP "
            "BY in CQL)"))
    out = []
    for i in items:
        col = None if i.args[0] == "*" else i.args[0].name
        if i.name.lower() != "count" and col is None:
            raise StatusError(Status.InvalidArgument(
                f"{i.name.lower()}(*) is not valid"))
        out.append((i.name.lower(), col))
    return out


def _row_token(row_dict: dict, columns) -> Optional[int]:
    """The row's partition token: the 16-bit hash of its hash-column
    group (ref: token() in the CQL grammar; partition hashing in
    common/partition.py)."""
    vals = tuple(row_dict.get(c) for c in columns)
    if any(v is None for v in vals):
        return None
    return DocKey(hash_components=vals).hash_code


def _jsonb_canonical(v) -> str:
    """Canonicalize a JSONB literal (common/jsonb.py) with CQL errors."""
    try:
        return jsonb.canonicalize(v)
    except ValueError as e:
        raise StatusError(Status.InvalidArgument(f"invalid json: {e}"))


_jsonb_navigate = jsonb.navigate


def _parse_collection_type(t: str):
    """'MAP<TEXT,INT>' -> ("map","TEXT","INT"); 'FROZEN<...>' unwraps.
    None for scalar types (ref: common/ql_type.h)."""
    if t.startswith("FROZEN<") and t.endswith(">"):
        t = t[7:-1]
    for kind in ("LIST", "SET", "MAP"):
        if t.startswith(kind + "<") and t.endswith(">"):
            inner = t[len(kind) + 1:-1].split(",")
            return (kind.lower(),) + tuple(x.strip() for x in inner)
    return None


def _collection_to_storage(coll: tuple, v):
    """CQL literal -> the subdocument dict stored under the column
    (set elements -> {elem: True}; list -> {index: elem})."""
    if v is P.MARKER or (isinstance(v, (list, tuple, set, frozenset))
                         and any(x is P.MARKER for x in v)) \
            or (isinstance(v, dict)
                and any(k is P.MARKER or x is P.MARKER
                        for k, x in v.items())):
        # bind markers inside collection values are not plumbed through
        # the typed prepared-statement path — fail loudly, not with a
        # sentinel stored as data
        raise StatusError(Status.NotSupported(
            "bind markers in collection values: inline the literal"))
    kind = coll[0]
    if kind == "map":
        if not isinstance(v, dict):
            raise StatusError(Status.InvalidArgument(
                f"expected a map literal, got {type(v).__name__}"))
        return dict(v)
    if kind == "set":
        if isinstance(v, dict) and not v:
            v = set()  # '{}' parses as an empty map literal
        if not isinstance(v, (set, frozenset, list, tuple)):
            raise StatusError(Status.InvalidArgument(
                f"expected a set literal, got {type(v).__name__}"))
        return {e: True for e in v}
    if not isinstance(v, (list, tuple)):
        raise StatusError(Status.InvalidArgument(
            f"expected a list literal, got {type(v).__name__}"))
    return {i: e for i, e in enumerate(v)}


def _collection_from_storage(coll: tuple, d):
    """Stored subdocument dict -> the CQL-shaped value (map dict,
    sorted-element set-as-list, index-ordered list)."""
    if not isinstance(d, dict):
        return d
    kind = coll[0]
    if kind == "map":
        return d
    if kind == "set":
        try:
            return sorted(d.keys())
        except TypeError:
            return list(d.keys())
    return [d[k] for k in sorted(d.keys(),
                                 key=lambda x: (not isinstance(x, int), x))]


@dataclass
class ResultSet:
    columns: List[str] = field(default_factory=list)
    rows: List[List[object]] = field(default_factory=list)
    # column DataTypes (parallel to columns; None where unknown) and the
    # source (keyspace, table) — consumed by the binary protocol front end
    # for Rows result metadata
    types: List[Optional[DataType]] = field(default_factory=list)
    source: Tuple[str, str] = ("", "")
    # opaque continuation token: more rows may remain; resume by re-running
    # the same statement with paging_state=this (ref CQL paging protocol)
    paging_state: Optional[bytes] = None

    def dicts(self) -> List[dict]:
        return [dict(zip(self.columns, r)) for r in self.rows]


def _encode_page_state(lower: bytes, cursor: bytes, read_ht: int,
                       remaining: Optional[int]) -> bytes:
    """Opaque SELECT continuation: resume doc-key bound, partition cursor,
    pinned snapshot read time and LIMIT budget left."""
    import struct as _s
    rem = -1 if remaining is None else remaining
    return (_s.pack(">QqII", read_ht, rem, len(lower), len(cursor))
            + lower + cursor)


def _decode_page_state(tok: bytes):
    import struct as _s
    read_ht, rem, nl, nc = _s.unpack(">QqII", tok[:24])
    lower = tok[24:24 + nl]
    cursor = tok[24 + nl:24 + nl + nc]
    return lower, cursor, read_ht, (None if rem < 0 else rem)


class QLProcessor:
    """One per CQL connection in the reference; safe to share here."""

    def __init__(self, client: YBClient,
                 txn_manager: Optional[TransactionManager] = None,
                 local_addr: Optional[Tuple[str, int]] = None):
        self._client = client
        self._txn_manager = txn_manager or TransactionManager(client)
        self._keyspace: Optional[str] = None
        # (host, port) of the CQL endpoint this processor serves —
        # reported by the system.local vtable
        self.local_addr = local_addr
        # (keyspace, table) -> (handle, cached-at monotonic time); see
        # the TTL logic in _table()
        self._tables: Dict[Tuple[str, str], Tuple[YBTable, float]] = {}
        self._stmt_cache: Dict[str, P.Statement] = {}
        self._lock = threading.Lock()

    # -------------------------------------------------------------- helpers
    def _resolve_ks(self, ks: Optional[str]) -> str:
        ks = ks or self._keyspace
        if ks is None:
            raise StatusError(Status.InvalidArgument(
                "no keyspace specified (USE <keyspace> or qualify)"))
        return ks

    def _table(self, ks: Optional[str], name: str) -> YBTable:
        """Table-handle cache with a TTL: index DDL elsewhere must become
        visible to this session's writes within the TTL (the schema-version
        propagation window; the reference invalidates on version-mismatch
        errors from the tserver, ref table_schema_version checks)."""
        from yugabyte_tpu.utils import flags as _flags
        ks = self._resolve_ks(ks)
        ttl = _flags.get_flag("table_cache_ttl_ms") / 1000.0
        now = time.monotonic()
        with self._lock:
            entry = self._tables.get((ks, name))
            if entry is not None and now - entry[1] < ttl:
                return entry[0]
        t = self._client.open_table(ks, name)
        with self._lock:
            self._tables[(ks, name)] = (t, now)
        return t

    def _bind_where(self, where, params: List[object],
                    cursor: List[int]):
        """Bind a WHERE conjunction, descending into IN lists (their
        elements may each be a '?' marker)."""
        out = []
        for c, op, v in where:
            if isinstance(v, list):
                out.append((c, op, [self._bind(x, params, cursor)
                                    for x in v]))
            else:
                out.append((c, op, self._bind(v, params, cursor)))
        return out

    @staticmethod
    def _bind(value, params: List[object], cursor: List[int]):
        if value is P.MARKER:
            if cursor[0] >= len(params):
                raise StatusError(Status.InvalidArgument(
                    "not enough bind parameters"))
            v = params[cursor[0]]
            cursor[0] += 1
            return v
        if isinstance(value, P.FuncCall):
            # constant builtin in a value position: now(), uuid(),
            # intasblob(7)... (ref bfql standard functions)
            args = [QLProcessor._bind(a, params, cursor)
                    for a in value.args]
            if any(isinstance(a, P.ColumnRef) for a in args):
                raise StatusError(Status.InvalidArgument(
                    f"{value.name}: column references are not allowed "
                    f"in value expressions"))
            try:
                v, _t = bfunc.evaluate(value.name, args)
            except bfunc.BFError as e:
                raise StatusError(Status.InvalidArgument(str(e)))
            return v
        return value

    # ------------------------------------------------- select-item builtins
    def _item_label(self, item) -> str:
        if isinstance(item, P.FuncCall):
            inner = ", ".join(self._item_label(a) for a in item.args)
            return f"{item.name.lower()}({inner})"
        if isinstance(item, P.ColumnRef):
            return item.name
        if isinstance(item, P.TokenRef):
            return f"token({', '.join(item.columns)})"
        if isinstance(item, P.JsonOp):
            out = item.column
            for i, step in enumerate(item.path):
                arrow = "->>" if (item.as_text
                                  and i == len(item.path) - 1) else "->"
                out += f"{arrow}{step!r}" if isinstance(step, int) \
                    else f"{arrow}'{step}'"
            return out
        return str(item)

    def _item_type(self, item, known, as_column: bool = True):
        """as_column: a bare str is a column name only at the TOP of a
        select item; inside function ARGUMENTS plain strings are string
        literals (columns there are P.ColumnRef)."""
        if isinstance(item, P.FuncCall):
            try:
                d = bfunc.resolve(item.name,
                                  [self._item_type(a, known, False)
                                   for a in item.args])
            except bfunc.BFError as e:
                raise StatusError(Status.InvalidArgument(str(e)))
            return d.ret_type if d.ret_type is not bfunc.ANY else None
        if isinstance(item, P.ColumnRef):
            return known.get(item.name)
        if isinstance(item, P.JsonOp):
            if known.get(item.column) is not DataType.JSONB:
                raise StatusError(Status.InvalidArgument(
                    f"{item.column} is not a jsonb column"))
            return DataType.STRING if item.as_text else DataType.JSONB
        if isinstance(item, P.TokenRef):
            return DataType.INT64
        if isinstance(item, str) and as_column:
            return known.get(item)
        return bfunc.infer_type(item)

    def _compile_item(self, item, known, as_column: bool = True):
        """Compile one select item to fn(row_dict, row) -> value.

        Builtin signatures resolve ONCE per statement (types are fixed),
        not per row (ref: the analyzer binds PTExpr opcodes at prepare
        time). writetime/ttl read Row metadata like the reference's
        TSOpcode path. as_column: see _item_type."""
        if isinstance(item, str) and as_column:
            return lambda d, row, _c=item: d.get(_c)
        if isinstance(item, P.ColumnRef):
            return lambda d, row, _c=item.name: d.get(_c)
        if isinstance(item, P.JsonOp):
            return lambda d, row, _j=item: _jsonb_navigate(
                d.get(_j.column), _j.path, _j.as_text)
        if isinstance(item, P.TokenRef):
            return lambda d, row, _c=item.columns: _row_token(d, _c)
        if isinstance(item, P.FuncCall):
            name = item.name.lower()
            if name == "writetime":
                return lambda d, row: (row.write_ht.physical_micros
                                       if row is not None else None)
            if name == "ttl":
                # per-cell TTL is not retained on the read path
                return lambda d, row: None
            arg_fns = [self._compile_item(a, known, False)
                       for a in item.args]
            types = [self._item_type(a, known, False) for a in item.args]
            try:
                decl = bfunc.resolve(item.name, types)
            except bfunc.BFError as e:
                raise StatusError(Status.InvalidArgument(str(e)))
            if decl.fn is None:
                raise StatusError(Status.InvalidArgument(
                    f"{name} is not valid here"))

            def ev(d, row, _decl=decl, _fns=arg_fns, _n=name):
                try:
                    return _decl.fn(*[f(d, row) for f in _fns])
                except bfunc.BFError as e:
                    raise StatusError(Status.InvalidArgument(str(e)))
                except Exception as e:
                    raise StatusError(Status.InvalidArgument(f"{_n}: {e}"))
            return ev
        return lambda d, row, _v=item: _v

    def _doc_key_from_where(self, table: YBTable,
                            where: List[Tuple[str, str, object]]
                            ) -> Tuple[Optional[DocKey], List]:
        """Split WHERE into a (possibly partial) primary key + residual
        filters (ref ptree analyzer's where-clause classification)."""
        schema = table.schema
        eq: Dict[str, object] = {}
        residual = []
        key_names = {c.name for c in schema.hash_columns} | \
            {c.name for c in schema.range_columns}
        for col, op, val in where:
            if op == "=" and col in key_names and col not in eq:
                eq[col] = val
            else:
                residual.append((col, op, val))
        hash_vals = [eq.get(c.name) for c in schema.hash_columns]
        range_vals = [eq.get(c.name) for c in schema.range_columns]
        if any(v is None for v in hash_vals):
            # No complete hash key: everything is residual filtering.
            return None, where
        while range_vals and range_vals[-1] is None:
            range_vals.pop()
        if any(v is None for v in range_vals):
            raise StatusError(Status.InvalidArgument(
                "range key columns must be constrained left-to-right"))
        return DocKey(hash_components=tuple(hash_vals),
                      range_components=tuple(range_vals)), residual

    _WIRE_LITERALS = (int, float, str, bytes, bool, type(None))

    @classmethod
    def _wire_filters(cls, schema, residual) -> Optional[List[List]]:
        """The subset of residual predicates worth shipping to the
        tserver scan (device-compilable triples run in the fused
        filtered kernel there; the rest evaluate host-side server-side
        before rows cross the wire). Safe by construction: for every
        shipped op the server's FILTER_OPS semantics are a SUPERSET of
        _match's (they differ only on NULLs, where the server may keep
        a row _match drops), and _match re-checks the full residual
        client-side — so pushdown can narrow the wire, never the
        result."""
        out = []
        for c, op, v in residual:
            if not isinstance(c, str) \
                    or op not in ("=", "!=", "<", "<=", ">", ">=", "in"):
                continue
            try:
                col = schema.column(c)
            except KeyError:
                continue
            if col.collection is not None:
                # server-side row dicts hold the STORAGE form of
                # collections; only the executor converts to CQL shapes,
                # so a collection comparison must stay client-side
                continue
            if op == "in":
                if not isinstance(v, (list, tuple)) or not all(
                        isinstance(x, cls._WIRE_LITERALS) for x in v):
                    continue
            elif not isinstance(v, cls._WIRE_LITERALS):
                continue
            out.append([c, op, list(v) if op == "in" else v])
        return out or None

    @staticmethod
    def _match(row_dict: dict, residual: List[Tuple[str, str, object]]
               ) -> bool:
        import operator
        ops = {"=": operator.eq, "!=": operator.ne, "<": operator.lt,
               ">": operator.gt, "<=": operator.le, ">=": operator.ge}
        for col, op, val in residual:
            if isinstance(col, P.JsonOp):
                have = _jsonb_navigate(row_dict.get(col.column),
                                       col.path, col.as_text)
            elif isinstance(col, P.TokenRef):
                have = _row_token(row_dict, col.columns)
            else:
                have = row_dict.get(col)
            if have is None:
                return False
            if op == "in":
                if have not in val:
                    return False
            elif not ops[op](have, val):
                return False
        return True

    # -------------------------------------------------------------- execute
    def execute(self, text: str, params: Sequence[object] = (),
                page_size: Optional[int] = None,
                paging_state: Optional[bytes] = None) -> ResultSet:
        """Parse (with statement-cache, ref QLProcessor prepared stmts) and
        run one statement.

        page_size/paging_state: result paging for SELECT (ref the CQL
        paging protocol + pgsql_operation.cc:1040 paging state) — at most
        page_size rows return, with ResultSet.paging_state set when more
        may remain; resuming with that opaque token continues the scan at
        the pinned snapshot read time."""
        with self._lock:
            stmt = self._stmt_cache.get(text)
        if stmt is None:
            stmt = P.parse(text)
            # Cache only parameterized statements (the reference caches
            # PREPARED statements); inline-literal texts are unique per
            # call and would grow the cache without bound.
            if "?" in text:
                with self._lock:
                    if len(self._stmt_cache) > 4096:
                        self._stmt_cache.clear()
                    self._stmt_cache[text] = stmt
        return self._execute_stmt(stmt, list(params), page_size=page_size,
                                  paging_state=paging_state)

    def _execute_stmt(self, stmt: P.Statement, params: List[object],
                      page_size: Optional[int] = None,
                      paging_state: Optional[bytes] = None) -> ResultSet:
        cursor = [0]
        if isinstance(stmt, P.CreateKeyspace):
            try:
                self._client.create_namespace(stmt.name)
            except StatusError as e:
                if not (stmt.if_not_exists
                        and e.status.code.name == "ALREADY_PRESENT"):
                    raise
            return ResultSet()
        if isinstance(stmt, P.UseKeyspace):
            self._keyspace = stmt.name
            return ResultSet()
        if isinstance(stmt, P.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, P.DropTable):
            ks = self._resolve_ks(stmt.keyspace)
            try:
                self._client.delete_table(ks, stmt.name)
            except StatusError as e:
                if not (stmt.if_exists
                        and e.status.code.name == "NOT_FOUND"):
                    raise
            with self._lock:
                self._tables.pop((ks, stmt.name), None)
            return ResultSet()
        if isinstance(stmt, P.AlterTable):
            return self._alter_table(stmt)
        if isinstance(stmt, P.CreateIndex):
            return self._create_index(stmt)
        if isinstance(stmt, P.Select):
            ks = stmt.keyspace or self._keyspace
            if ks in ("system", "system_schema"):
                if stmt.columns and _extract_cql_aggregates(
                        stmt.columns) is not None:
                    raise StatusError(Status.NotSupported(
                        "aggregates over system tables"))
                return self._select_system(ks, stmt, params, cursor)
            rs = self._select(stmt, params, cursor, page_size=page_size,
                              page_state=paging_state)
            if not hasattr(rs, "pushdown"):     # grouped cells are typed
                _grouped.typed_result(
                    self._table(stmt.keyspace, stmt.table).schema, rs)
            return rs
        if isinstance(stmt, (P.Insert, P.Update, P.Delete)):
            if getattr(stmt, "if_not_exists", False) \
                    or getattr(stmt, "if_exists", False) \
                    or getattr(stmt, "conditions", None):
                return self._conditional_dml(stmt, params, cursor)
            table, op = self._dml_to_op(stmt, params, cursor)
            ks = self._resolve_ks(getattr(stmt, "keyspace", None))
            IM.write_with_indexes(
                self._client, self._txn_manager, table, op,
                lambda name, _ks=ks: self._table(_ks, name))
            return ResultSet()
        if isinstance(stmt, P.Transaction):
            return self._run_transaction(stmt, params)
        if isinstance(stmt, P.Truncate):
            return self._truncate(stmt)
        raise StatusError(Status.NotSupported(f"statement {type(stmt)}"))

    def _select_distinct(self, stmt: P.Select, params, cursor,
                         page_size=None, page_state=None) -> ResultSet:
        """SELECT DISTINCT over the partition key: CQL restricts DISTINCT
        to EXPLICIT partition key columns (no '*'), without ORDER BY —
        one output row per partition (ref: the grammar's distinct
        restriction in ql). Pages by offset into the distinct set (the
        set is bounded by the partition count)."""
        table = self._table(stmt.keyspace, stmt.table)
        schema = table.schema
        hash_names = [c.name for c in schema.hash_columns]
        if stmt.columns is None:
            raise StatusError(Status.InvalidArgument(
                "SELECT DISTINCT * is not valid: name the partition "
                f"key columns {hash_names}"))
        if stmt.order_by:
            raise StatusError(Status.InvalidArgument(
                "ORDER BY is not valid with SELECT DISTINCT"))
        want = stmt.columns
        if [c for c in want if not isinstance(c, str)] \
                or list(want) != hash_names:
            raise StatusError(Status.InvalidArgument(
                f"SELECT DISTINCT is only valid on the partition key "
                f"columns {hash_names}"))
        inner = P.Select(stmt.keyspace, stmt.table, list(hash_names),
                         stmt.where, None)
        rs = self._select(inner, params, cursor)
        seen = []
        seen_set = set()
        for row in rs.rows:
            t = tuple(row)
            if t not in seen_set:
                seen_set.add(t)
                seen.append(list(row))
                if stmt.limit is not None and len(seen) >= stmt.limit:
                    break
        off = 0
        if page_state:
            try:
                if not page_state.startswith(b"DIST:"):
                    raise ValueError(page_state)
                off = int(page_state[5:])
            except ValueError:
                raise StatusError(Status.InvalidArgument(
                    "malformed paging state"))
        out = ResultSet(columns=list(hash_names),
                        types=[schema.column(c).type
                               for c in hash_names],
                        source=rs.source)
        if page_size is not None:
            out.rows = seen[off:off + page_size]
            if off + page_size < len(seen):
                out.paging_state = b"DIST:%d" % (off + page_size)
        else:
            out.rows = seen[off:]
        return out

    def _select_aggregate(self, stmt: P.Select, aggs, params, cursor
                          ) -> ResultSet:
        """CQL aggregates: COUNT(*)/COUNT(col)/SUM/AVG/MIN/MAX over the
        whole (filtered) result — YCQL has no GROUP BY, so the output is
        exactly one row (ref: the CQL aggregate surface in the
        reference's ql; Cassandra 2.2 aggregate semantics — AVG over an
        int column is integer division).

        When the whole (WHERE, aggregate-list) pair is inside the device
        subset (docdb/scan_spec.py), the scalars come back from ONE
        fused segment-reduce dispatch per tablet instead of every row
        surfacing to this process (ROADMAP item 5); tablets that cannot
        push return rows, which fold into the same accumulator with
        identical semantics. The output row is assembled from the stats
        by ONE shared code path either way."""
        table = self._table(stmt.keyspace, stmt.table)
        stats = None
        if not stmt.order_by:
            stats = self._try_pushdown_aggregate(stmt, aggs, params,
                                                 cursor, table)
        if stats is None:
            cols_needed = sorted({c for _f, c in aggs if c is not None})
            if not cols_needed:
                # COUNT(*)-only: project one key column, not the whole row
                cols_needed = [table.schema.hash_columns[0].name]
            # LIMIT applies to the RESULT rows (exactly one for an
            # aggregate), not to the scan feeding it: `SELECT COUNT(*)
            # ... LIMIT 1` must count every matching row, so the inner
            # scan is unlimited
            inner = P.Select(stmt.keyspace, stmt.table,
                             cols_needed, stmt.where, None,
                             order_by=stmt.order_by)
            rs = self._select(inner, params, cursor)
            stats = self._agg_stats_from_dicts(aggs, rs.dicts())
        return self._assemble_aggregate(aggs, table, stats)

    @staticmethod
    def _agg_stats_from_dicts(aggs, dicts) -> dict:
        """Host-path accumulator: per aggregated column, the non-null
        value list (assembly reduces it per requested function)."""
        cols: Dict[str, dict] = {}
        for _fname, col in aggs:
            if col is None or col in cols:
                continue
            vals = [d.get(col) for d in dicts if d.get(col) is not None]
            cols[col] = {"nonnull": len(vals), "vals": vals}
        return {"rows": len(dicts), "cols": cols}

    def _assemble_aggregate(self, aggs, table, stats) -> ResultSet:
        """stats -> the single CQL aggregate output row. stats["cols"]
        entries carry either a host value list ("vals") or the device
        partial scalars ("sum"/"min"/"max") — reductions are exact ints
        on the device path, so both shapes produce identical output."""
        known = {c.name: c.type for c in table.schema.columns}
        empty = {"nonnull": 0, "vals": []}
        out_row: List[object] = []
        out_cols: List[str] = []
        out_types: List[Optional[DataType]] = []
        for fname, col in aggs:
            label = f"{fname}({'*' if col is None else col})"
            out_cols.append(label)
            if fname == "count":
                out_row.append(stats["rows"] if col is None
                               else stats["cols"].get(col, empty)["nonnull"])
                out_types.append(DataType.INT64)
                continue
            st = stats["cols"].get(col, empty)
            nn = st["nonnull"]
            t = known.get(col)
            if fname in ("sum", "avg") and t not in (
                    DataType.INT32, DataType.INT64, DataType.FLOAT,
                    DataType.DOUBLE):
                raise StatusError(Status.InvalidArgument(
                    f"{fname}() requires a numeric column"))
            if fname == "sum":
                total = sum(st["vals"]) if "vals" in st else st["sum"]
                out_row.append(total if nn else 0)
                # a sum of int32s overflows int32: widen on the wire
                out_types.append(DataType.INT64
                                 if t == DataType.INT32 else t)
            elif fname == "avg":
                total = sum(st["vals"]) if "vals" in st else st["sum"]
                if not nn:
                    out_row.append(0)
                elif t in (DataType.INT32, DataType.INT64):
                    out_row.append(total // nn)
                else:
                    out_row.append(total / nn)
                out_types.append(t)
            else:  # min / max
                try:
                    if "vals" in st:
                        out_row.append(
                            (min if fname == "min" else max)(st["vals"])
                            if nn else None)
                    else:
                        out_row.append(st[fname])
                except TypeError:
                    raise StatusError(Status.InvalidArgument(
                        f"{fname}() requires a comparable column type"))
                out_types.append(t)
        return ResultSet(columns=out_cols, rows=[out_row],
                         types=out_types,
                         source=(table.namespace, table.name))

    def _try_pushdown_aggregate(self, stmt: P.Select, aggs, params,
                                cursor, table) -> Optional[dict]:
        """Attempt the fused-aggregate path. Returns the device-shaped
        stats dict, or None when the statement is outside the pushdown
        shape (the caller runs the unchanged host path; parameter
        binding happens on a TRIAL cursor so a refusal consumes
        nothing). Fallback-tablet rows are re-checked with the
        executor's own _match before folding, so the combined stats
        carry executor semantics exactly — including the
        NULL-fails-every-operator rule."""
        from yugabyte_tpu.docdb import scan_spec as SS
        schema = table.schema
        wire_aggs = []
        for fname, col in aggs:
            fn = "sum" if fname == "avg" else fname
            if SS.compile_aggregate(schema, fn, col) is None:
                return None
            wire_aggs.append([fn, col])
        trial = [cursor[0]]
        where = self._bind_where(stmt.where, params, trial)
        known = {c.name: c.type for c in schema.columns}
        where = self._canon_jsonb_where(where, known)
        for c, op, _v in where:
            if not isinstance(c, str) or op == "in":
                return None
        dk, residual = self._doc_key_from_where(table, where)
        if dk is not None and len(dk.range_components) \
                == schema.num_range_key_columns:
            return None   # full primary key: the point read is optimal
        key_names = {c.name for c in schema.hash_columns} | \
            {c.name for c in schema.range_columns}
        partition_key = None
        lo = b""
        hi = None
        if dk is not None:
            prefix = DocKey(hash_components=dk.hash_components,
                            range_components=dk.range_components).encode()
            prefix = prefix[:-1]
            lo, hi = self._range_scan_bounds(schema, dk, prefix, residual)
            partition_key = table.partition_key_for(dk)
            residual = [r for r in residual
                        if not self._bound_enforces(schema, dk, r)]
        preds = []
        for c, op, v in residual:
            if c in key_names:
                # a key-component predicate the byte bounds don't fully
                # enforce: outside the scalar-aggregate shape
                return None
            if SS.compile_predicate(schema, c, op, v) is None:
                return None
            preds.append([c, op, v])
        cursor[0] = trial[0]
        fb_dicts: List[dict] = []

        def on_row(row):
            d = self._row_dict(schema, row)
            if self._match(d, residual):
                fb_dicts.append(d)

        partial, _read_ht = self._client.scan_aggregate(
            table, wire_aggs, filters=preds,
            partition_key=partition_key, lower_doc_key=lo,
            upper_doc_key=hi, row_cb=on_row)
        cid_to_name = {schema.column_id(c.name): c.name
                       for c in schema.value_columns}
        stats = {"rows": 0, "cols": {}}
        if partial is not None:
            stats["rows"] = partial["rows"]
            for cid, st in partial["cols"].items():
                name = cid_to_name.get(int(cid))
                if name is not None:
                    stats["cols"][name] = dict(st)
        # fold the host-checked fallback rows (disjoint tablet sets, so
        # adding counts/sums and reducing extremes is exact) — once per
        # DISTINCT aggregated column, however many functions name it
        stats["rows"] += len(fb_dicts)
        for col in dict.fromkeys(c for _f, c in aggs if c is not None):
            st = stats["cols"].setdefault(
                col, {"nonnull": 0, "sum": 0, "min": None, "max": None})
            vals = [d.get(col) for d in fb_dicts
                    if d.get(col) is not None]
            st["nonnull"] += len(vals)
            if vals:
                st["sum"] = st.get("sum", 0) + sum(vals)
                st["min"] = min(vals) if st.get("min") is None \
                    else min(st["min"], *vals)
                st["max"] = max(vals) if st.get("max") is None \
                    else max(st["max"], *vals)
        return stats

    @staticmethod
    def _bound_enforces(schema, dk, pred) -> bool:
        """True when _range_scan_bounds absorbed this residual predicate
        into an EXACT byte bound: an inequality on the first unbound
        clustering column with a correctly-typed literal. (Component
        encoding is order-preserving and every longer key continues
        with a tag byte < 0xff, so the prefix+encode(v) bounds include/
        exclude exactly the predicate's rows — no edge slack.)"""
        c, op, v = pred
        bound_n = len(dk.range_components)
        if bound_n >= len(schema.range_columns):
            return False
        nxt_col = schema.range_columns[bound_n]
        if c != nxt_col.name or op not in ("<", "<=", ">", ">="):
            return False
        if not QLProcessor._bound_type_ok(nxt_col.type, v):
            return False
        from yugabyte_tpu.docdb.doc_key import PrimitiveValue
        try:
            PrimitiveValue.encode(v, bytearray())
        except TypeError:
            return False
        return True

    def _conditional_dml(self, stmt, params: List[object],
                         cursor: List[int]) -> ResultSet:
        """Lightweight transaction: INSERT ... IF NOT EXISTS, UPDATE/
        DELETE ... IF EXISTS / IF <conds>. Runs as a read-check-write
        distributed transaction with conflict retry, returning the CQL
        [applied] row — with the current row's values when not applied
        (ref: the conditional QLWriteRequest if_expr path; the analyzer's
        if-clause handling in ql/ptree/pt_dml.h)."""
        table, op = self._dml_to_op(stmt, params, cursor)
        # IF conditions bind AFTER the WHERE clause (statement-text order)
        conds = [(c, o, self._bind(v, params, cursor))
                 for c, o, v in getattr(stmt, "conditions", [])]
        ks = self._resolve_ks(getattr(stmt, "keyspace", None))
        schema = table.schema
        insert_mode = getattr(stmt, "if_not_exists", False)

        def body(txn):
            row = txn.read_row(table, op.doc_key)
            d = self._row_dict(schema, row) if row is not None else None
            if insert_mode:
                applied = row is None
            elif conds:
                applied = d is not None and self._match(d, conds)
            else:  # IF EXISTS
                applied = row is not None
            if applied:
                IM.txn_write_with_indexes(
                    txn, table, op,
                    lambda name, _ks=ks: self._table(_ks, name),
                    old_row_dict=d if d is not None else {})
            return applied, d

        applied, d = IM.run_in_implicit_txn(
            self._txn_manager, None, body, 30.0)
        rs = ResultSet(columns=["[applied]"], types=[DataType.BOOL])
        if applied or d is None:
            rs.rows.append([applied])
        else:
            # not applied: CQL returns the current values alongside
            # [applied] = false so clients can see why the CAS failed
            extra = sorted(d) if insert_mode else \
                list(dict.fromkeys(c for c, _o, _v in conds)) or sorted(d)
            rs.columns += extra
            rs.types += [schema.column(c).type if self._has_col(schema, c)
                         else None for c in extra]
            rs.rows.append([applied] + [d.get(c) for c in extra])
        return rs

    @staticmethod
    def _has_col(schema, name: str) -> bool:
        try:
            schema.column(name)
            return True
        except KeyError:
            return False

    def _truncate(self, stmt: P.Truncate) -> ResultSet:
        """Delete every row (and maintained index rows) from the table.
        Functional equivalent of the reference's whole-tablet truncate
        (tablet.cc Truncate), expressed through the row delete path so
        secondary indexes stay consistent."""
        ks = self._resolve_ks(stmt.keyspace)
        table = self._table(stmt.keyspace, stmt.table)

        def flush(ops: List[QLWriteOp]) -> None:
            if not table.indexes:
                self._client.write(table, ops)
                return
            # one implicit distributed txn per BATCH (not per row): the
            # batch's main-row + index-row deletes commit atomically
            IM.run_in_implicit_txn(
                self._txn_manager, None,
                lambda txn: [IM.txn_write_with_indexes(
                    txn, table, op,
                    lambda name, _ks=ks: self._table(_ks, name))
                    for op in ops],
                30.0)

        batch: List[QLWriteOp] = []
        for row in self._client.scan(table):
            batch.append(QLWriteOp(WriteOpKind.DELETE_ROW, row.doc_key))
            if len(batch) >= 512:
                flush(batch)
                batch = []
        if batch:
            flush(batch)
        return ResultSet()

    def _alter_table(self, stmt: P.AlterTable) -> ResultSet:
        """ALTER TABLE ADD/DROP column riding the master's versioned
        online schema change (ref ql/ptree/pt_alter_table.h)."""
        ks = self._resolve_ks(stmt.keyspace)
        add = []
        for col, cql_t in stmt.add_columns:
            t = cql_t.upper()
            if t not in _CQL_TYPES:
                raise StatusError(Status.NotSupported(f"type {t}"))
            add.append((col, _CQL_TYPES[t].value))
        self._client.alter_table(ks, stmt.name, add_columns=add,
                                 drop_columns=stmt.drop_columns)
        with self._lock:
            self._tables.pop((ks, stmt.name), None)
        return ResultSet()

    def _create_index(self, stmt: P.CreateIndex) -> ResultSet:
        ks = self._resolve_ks(stmt.keyspace)
        index_name = stmt.index_name \
            or f"{stmt.table}_{'_'.join(stmt.columns)}_idx"
        try:
            self._client.create_index(ks, stmt.table, index_name,
                                      list(stmt.columns))
        except StatusError as e:
            if not (stmt.if_not_exists
                    and e.status.code.name == "ALREADY_PRESENT"):
                raise
        with self._lock:
            self._tables.pop((ks, stmt.table), None)  # refresh index list
        return ResultSet()

    def _create_table(self, stmt: P.CreateTable) -> ResultSet:
        ks = self._resolve_ks(stmt.keyspace)
        key_order = stmt.hash_keys + stmt.range_keys
        cols_by_name = dict(stmt.columns)
        unknown = [k for k in key_order if k not in cols_by_name]
        if unknown:
            raise StatusError(Status.InvalidArgument(
                f"primary key columns not defined: {unknown}"))
        ordered = key_order + [n for n, _t in stmt.columns
                               if n not in key_order]
        columns = []
        for n in ordered:
            cql_t = cols_by_name[n].upper()
            coll = _parse_collection_type(cql_t)
            if coll is not None:
                if n in key_order:
                    # FROZEN keys would need a canonical bytes encoding of
                    # the collection as a DocKey component — unsupported
                    raise StatusError(Status.NotSupported(
                        f"collection column {n} cannot be a key"))
                columns.append(ColumnSchema(n, DataType.BINARY,
                                            collection=coll))
                continue
            cql_t, type_params = _split_type_params(cql_t)
            if cql_t not in _CQL_TYPES:
                raise StatusError(Status.NotSupported(f"type {cql_t}"))
            if _CQL_TYPES[cql_t] is DataType.JSONB and n in key_order:
                # jsonb has no order-preserving key encoding (the
                # reference likewise rejects jsonb primary keys)
                raise StatusError(Status.NotSupported(
                    f"jsonb column {n} cannot be a key"))
            dtype = _CQL_TYPES[cql_t]
            if dtype is DataType.DECIMAL:
                type_params = (tuple(type_params) + (0,))[:2] \
                    if type_params else (38, 0)
            elif dtype is DataType.CHAR:
                type_params = type_params or (1,)
            else:
                type_params = None
            columns.append(ColumnSchema(n, dtype, type_params=type_params))
        schema = Schema(columns=columns,
                        num_hash_key_columns=len(stmt.hash_keys),
                        num_range_key_columns=len(stmt.range_keys))
        try:
            self._client.create_table(ks, stmt.name, schema,
                                      num_tablets=stmt.num_tablets)
        except StatusError as e:
            if not (stmt.if_not_exists
                    and e.status.code.name == "ALREADY_PRESENT"):
                raise
        return ResultSet()

    def _dml_to_op(self, stmt, params: List[object],
                   cursor: List[int]) -> Tuple[YBTable, QLWriteOp]:
        if isinstance(stmt, P.Insert):
            table = self._table(stmt.keyspace, stmt.table)
            schema = table.schema
            bound = {c: self._bind(v, params, cursor)
                     for c, v in zip(stmt.columns, stmt.values)}
            bound = {c: _grouped.stored_value(schema, c, v)
                     for c, v in bound.items()}
            key_names = [c.name for c in schema.hash_columns] + \
                [c.name for c in schema.range_columns]
            missing = [k for k in key_names if k not in bound]
            if missing:
                raise StatusError(Status.InvalidArgument(
                    f"INSERT missing key columns {missing}"))
            dk = DocKey(
                hash_components=tuple(bound[c.name]
                                      for c in schema.hash_columns),
                range_components=tuple(bound[c.name]
                                       for c in schema.range_columns))
            values = {c: v for c, v in bound.items()
                      if c not in key_names}
            coll_ops = {}
            for c in list(values):
                coll = self._collection_of(schema, c)
                if coll is not None and values[c] is not None:
                    coll_ops[c] = [("replace",
                                    _collection_to_storage(coll,
                                                           values.pop(c)))]
                elif values[c] is not None and self._is_jsonb(schema, c):
                    values[c] = _jsonb_canonical(values[c])
            return table, QLWriteOp(
                WriteOpKind.INSERT, dk, values, collection_ops=coll_ops,
                ttl_ms=stmt.ttl_seconds * 1000 if stmt.ttl_seconds else None)
        if isinstance(stmt, P.Update):
            table = self._table(stmt.keyspace, stmt.table)
            schema = table.schema
            # Bind in statement-text order: SET comes before WHERE.
            assignments = [(c, self._bind(v, params, cursor))
                           for c, v in stmt.assignments]
            where = self._bind_where(stmt.where, params, cursor)
            dk, residual = self._doc_key_from_where(table, where)
            if dk is None or residual:
                raise StatusError(Status.InvalidArgument(
                    "UPDATE requires the full primary key"))
            values = {}
            # ORDERED op list per column: mixed element writes and deletes
            # in one UPDATE apply in statement order (later wins at the
            # same path via ascending intra-batch write ids)
            coll_ops: Dict[str, List[Tuple[str, object]]] = {}

            for c, v in assignments:
                if isinstance(c, tuple):        # m['k'] = v  /  l[i] = v
                    col, sub = c
                    coll = self._collection_of(schema, col)
                    if coll is None:
                        raise StatusError(Status.InvalidArgument(
                            f"{col} is not a collection"))
                    ops = coll_ops.setdefault(col, [])
                    if v is None:
                        ops.append(("del_keys", [sub]))
                    else:
                        ops.append(("merge", {sub: v}))
                    continue
                coll = self._collection_of(schema, c)
                if coll is None:
                    if isinstance(v, tuple) and len(v) == 2 \
                            and v[0] in ("__append__", "__remove__"):
                        raise StatusError(Status.InvalidArgument(
                            f"{c} is not a collection: col = col +/- X "
                            f"applies to collections only"))
                    if v is not None and self._is_jsonb(schema, c):
                        v = _jsonb_canonical(v)
                    values[c] = v
                    continue
                if isinstance(v, tuple) and len(v) == 2 \
                        and v[0] in ("__append__", "__remove__"):
                    lit = v[1]
                    if coll[0] == "list":
                        # lists store {index: elem}; value-based +/- would
                        # need read-modify-write — be explicit, not wrong
                        raise StatusError(Status.NotSupported(
                            "list +/-: assign the full list"))
                    if v[0] == "__append__":
                        coll_ops.setdefault(c, []).append(
                            ("merge", _collection_to_storage(coll, lit)))
                    else:
                        elems = list(lit.keys()) if isinstance(lit, dict) \
                            else list(lit)
                        coll_ops.setdefault(c, []).append(
                            ("del_keys", elems))
                elif v is None:
                    values[c] = None  # whole-collection delete (tombstone)
                else:
                    coll_ops.setdefault(c, []).append(
                        ("replace", _collection_to_storage(coll, v)))
            return table, QLWriteOp(
                WriteOpKind.UPDATE, dk, values, collection_ops=coll_ops,
                ttl_ms=stmt.ttl_seconds * 1000 if stmt.ttl_seconds else None)
        # Delete
        table = self._table(stmt.keyspace, stmt.table)
        where = self._bind_where(stmt.where, params, cursor)
        dk, residual = self._doc_key_from_where(table, where)
        if dk is None or residual:
            raise StatusError(Status.InvalidArgument(
                "DELETE requires the full primary key"))
        if stmt.columns:
            plain = [c for c in stmt.columns if not isinstance(c, tuple)]
            coll_ops: Dict[str, List[Tuple[str, object]]] = {}
            for c in stmt.columns:
                if isinstance(c, tuple):        # DELETE m['k'] FROM ...
                    col, sub = c
                    if self._collection_of(table.schema, col) is None:
                        raise StatusError(Status.InvalidArgument(
                            f"{col} is not a collection"))
                    coll_ops.setdefault(col, []).append(("del_keys",
                                                         [sub]))
            return table, QLWriteOp(WriteOpKind.DELETE_COLS, dk,
                                    columns_to_delete=tuple(plain),
                                    collection_ops=coll_ops)
        return table, QLWriteOp(WriteOpKind.DELETE_ROW, dk)

    @staticmethod
    def _collection_of(schema, name: str):
        try:
            return schema.column(name).collection
        except KeyError:
            return None

    @staticmethod
    def _canon_jsonb_where(where, known):
        """Jsonb predicates: reject -> on non-jsonb columns, and
        canonicalize comparison values where the lhs yields json text
        (whole-document equality, or a -> chain without ->>) so equal
        documents match regardless of literal spelling — the stored form
        is canonical (common/jsonb.py)."""
        out = []
        for c, op, v in where:
            canon = False
            if isinstance(c, P.JsonOp):
                if known.get(c.column) is not DataType.JSONB:
                    raise StatusError(Status.InvalidArgument(
                        f"{c.column} is not a jsonb column"))
                canon = not c.as_text
            elif isinstance(c, str) and known.get(c) is DataType.JSONB:
                canon = True
            if canon and v is not None:
                if op == "in":
                    v = [_jsonb_canonical(x) if x is not None else None
                         for x in v]
                else:
                    v = _jsonb_canonical(v)
            out.append((c, op, v))
        return out

    @staticmethod
    def _is_jsonb(schema, name: str) -> bool:
        try:
            return schema.column(name).type is DataType.JSONB
        except KeyError:
            return False

    def _row_dict(self, schema, row):
        """Row -> dict with collection columns converted from their
        subdocument storage form to CQL shapes (map/set/list)."""
        d = row.to_dict(schema)
        for c in schema.value_columns:
            if c.collection is not None and d.get(c.name) is not None:
                d[c.name] = _collection_from_storage(c.collection,
                                                     d[c.name])
        return d

    def _select(self, stmt: P.Select, params: List[object],
                cursor: List[int], page_size: Optional[int] = None,
                page_state: Optional[bytes] = None) -> ResultSet:
        table = self._table(stmt.keyspace, stmt.table)
        schema = table.schema

        def bind_item(it):
            """Bind '?' markers inside select-list builtin calls. Select
            items are bound BEFORE the WHERE clause: positional params
            arrive in statement-text order."""
            if isinstance(it, P.FuncCall):
                return P.FuncCall(it.name, [bind_item(a) for a in it.args])
            if it is P.MARKER:
                return self._bind(it, params, cursor)
            return it

        out_items = [bind_item(i)
                     for i in (stmt.columns
                               or [c.name for c in schema.columns
                                   if not c.dropped])]
        # token() must name the partition key columns in order — a hash
        # over anything else matches no partition layout (real CQL
        # rejects it the same way)
        hash_col_names = [c.name for c in schema.hash_columns]
        for it in list(out_items) + [f[0] for f in stmt.where]:
            if isinstance(it, P.TokenRef) \
                    and list(it.columns) != hash_col_names:
                raise StatusError(Status.InvalidArgument(
                    f"token() arguments must be the partition key "
                    f"columns {hash_col_names} in order"))
        if _grouped.wants_grouped(P, schema, stmt, out_items):
            # GROUP BY, product terms, DECIMAL / DATE / CHAR aggregates:
            # the typed, grouped pushdown (yql/cql/grouped.py)
            return _grouped.select_grouped(self, P, stmt, out_items,
                                           params, cursor)
        aggs = _extract_cql_aggregates(out_items)
        if aggs is not None:
            return self._select_aggregate(stmt, aggs, params, cursor)
        if stmt.distinct:
            return self._select_distinct(stmt, params, cursor,
                                         page_size, page_state)
        where = _grouped.typed_where(
            schema, self._bind_where(stmt.where, params, cursor))
        known = {c.name: c.type for c in schema.columns}
        where = self._canon_jsonb_where(where, known)

        # ---- discrete ScanChoices: col IN (...) on a KEY column runs one
        # sub-select per option (ref docdb/scan_choices.cc option seeks)
        key_names = {c.name for c in schema.hash_columns} | \
            {c.name for c in schema.range_columns}
        range_names = {c.name for c in schema.range_columns}
        hash_names = {c.name for c in schema.hash_columns}
        eq_cols = {c for c, op, _v in where if op == "="}
        range_order = [c.name for c in schema.range_columns]
        # ORDER BY validation happens BEFORE any execution-path branch so
        # rejection does not depend on the WHERE shape (CQL: partition
        # key restricted, single direction, clustering-order prefix)
        if stmt.order_by:
            if not hash_names <= eq_cols and not any(
                    op == "in" and c in hash_names for c, op, _v in where):
                raise StatusError(Status.InvalidArgument(
                    "ORDER BY is only supported when the partition key "
                    "is restricted"))
            dirs = {d for _c, d in stmt.order_by}
            if len(dirs) > 1:
                raise StatusError(Status.InvalidArgument(
                    "ORDER BY must use a single direction over the "
                    "clustering order"))
            want = [c for c, _d in stmt.order_by]
            if want != range_order[: len(want)]:
                raise StatusError(Status.InvalidArgument(
                    f"ORDER BY must follow the clustering key order "
                    f"{range_order}"))
        for i, (c, op, v) in enumerate(where):
            if op == "in" and c in key_names:
                if stmt.order_by:
                    # ordered results: take the scan path (IN becomes a
                    # residual filter) so the reversal logic applies once
                    continue
                # only worthwhile when every sub-select still reaches a
                # key prefix — with the hash key unbound, N sub-selects
                # would be N full scans where ONE scan with the IN as a
                # residual filter suffices
                if not hash_names <= (eq_cols | {c}):
                    continue
                # IN is a SET: duplicates must not duplicate rows
                options = list(dict.fromkeys(v))
                if c in range_names:
                    # rows come back in clustering order — option order
                    # must follow it or LIMIT keeps the wrong rows.  That
                    # only holds when every clustering column BEFORE the
                    # IN column is equality-bound: otherwise the per-
                    # option concatenation orders by (c, earlier cols)
                    # instead of clustering order (real CQL rejects such
                    # restrictions outright).  Unsortable option types
                    # fall back to a single residual-filter scan for the
                    # same reason (ADVICE r3).
                    if any(rc not in eq_cols
                           for rc in range_order[:range_order.index(c)]):
                        continue
                    try:
                        options = sorted(options)
                    except TypeError:
                        continue
                merged = ResultSet(columns=[], types=[], source=None)
                limit = stmt.limit
                for option in options:
                    # sub-select built from ALREADY-BOUND pieces (markers
                    # were consumed above; re-binding would misalign)
                    sub = P.Select(stmt.keyspace, stmt.table, out_items,
                                   where=[w for j, w in enumerate(where)
                                          if j != i] + [(c, "=", option)],
                                   limit=limit)
                    rs = self._select(sub, (), [0])
                    merged.columns, merged.types = rs.columns, rs.types
                    merged.source = rs.source
                    merged.rows.extend(rs.rows)
                    if limit is not None:
                        limit -= len(rs.rows)
                        if limit <= 0:
                            break
                return merged
        rs = ResultSet(columns=[self._item_label(i) for i in out_items],
                       types=[self._item_type(i, known) for i in out_items],
                       source=(table.namespace, table.name))
        item_fns = [self._compile_item(i, known) for i in out_items]
        dk, residual = self._doc_key_from_where(table, where)
        full_key = (dk is not None
                    and len(dk.range_components)
                    == schema.num_range_key_columns)
        if full_key:
            row = self._client.read_row(table, dk)
            if row is not None:
                d = self._row_dict(schema, row)
                if self._match(d, residual):
                    rs.rows.append([f(d, row) for f in item_fns])
            return rs
        ps = _decode_page_state(page_state) if page_state else None
        scan_state: dict = {}
        pageable = False
        if dk is not None:
            # Full hash key: single-partition prefix scan on the owning
            # tablet (ref ScanChoices hashed-key scan), not a table scan.
            prefix = DocKey(hash_components=dk.hash_components,
                            range_components=dk.range_components).encode()
            prefix = prefix[:-1]  # open the range group
            lo, hi = self._range_scan_bounds(schema, dk, prefix, residual)
            if ps:
                lo = max(lo, ps[0])
            rows = self._client.scan_key_range(
                table, table.partition_key_for(dk), lo, hi,
                read_ht=HybridTime(ps[2]) if ps else None,
                filters=self._wire_filters(schema, residual),
                scan_state=scan_state)
            pageable = True
        else:
            # No key prefix: try a readable secondary index on an equality
            # predicate before falling back to the full scan.  A resume
            # token forces the scan path: the first page came from a scan
            # (tokens are only issued on pageable paths), and switching to
            # an index that became readable between pages would restart
            # the result set (duplicates) and ignore the pinned snapshot.
            picked = None if ps else IM.choose_index(table, residual)
            if picked is not None:
                idx, value, residual = picked
                ks = self._resolve_ks(stmt.keyspace)
                idx_table = self._table(ks, idx.index_name)
                rows = IM.index_lookup(self._client, table, idx_table,
                                       idx, value)
            else:
                rows = self._client.scan(
                    table, read_ht=HybridTime(ps[2]) if ps else None,
                    filters=self._wire_filters(schema, residual),
                    start_cursor=ps[1] if ps else b"",
                    start_lower=ps[0] if ps else b"",
                    scan_state=scan_state)
                pageable = True
        # ---- ORDER BY clustering columns (CQL: only with the partition
        # key restricted; rows already stream in clustering ASC order, so
        # ASC is a no-op and DESC materializes the partition and
        # reverses — ref: sem analyzer order-by checks + reverse scans)
        if stmt.order_by:
            if {d for _c, d in stmt.order_by} == {True}:
                # DESC: collect the partition's matching rows, reverse;
                # no paging token (the resume cursor is ascending-only)
                collected = []
                for row in rows:
                    d = self._row_dict(schema, row)
                    if tuple(d[c.name] for c in schema.hash_columns) !=                             dk.hash_components:
                        continue
                    if not self._match(d, residual):
                        continue
                    collected.append((d, row))
                collected.reverse()
                budget = ps[3] if ps else stmt.limit
                for d, row in collected:
                    rs.rows.append([f(d, row) for f in item_fns])
                    if budget is not None and len(rs.rows) >= budget:
                        break
                return rs
        # LIMIT budget spans pages: the token carries what is still owed
        remaining = ps[3] if ps else stmt.limit
        count = 0
        rows_it = iter(rows)
        for row in rows_it:
            d = self._row_dict(schema, row)
            if dk is not None and tuple(
                    d[c.name] for c in schema.hash_columns) != \
                    dk.hash_components:
                continue
            if not self._match(d, residual):
                continue
            rs.rows.append([f(d, row) for f in item_fns])
            count += 1
            if remaining is not None and count >= remaining:
                break
            if pageable and page_size is not None and count >= page_size:
                # peek before issuing a token: an exactly-exhausted scan
                # must report "no more pages", not charge the client one
                # extra round trip for an empty final page
                if next(rows_it, None) is not None:
                    rs.paging_state = _encode_page_state(
                        row.doc_key.encode() + b"\xff",
                        table.partition_key_for(row.doc_key),
                        scan_state.get("read_ht", 0),
                        None if remaining is None else remaining - count)
                break
        return rs

    # predicate value classes whose doc-key encoding shares the column's
    # type tag — cross-tag bounds would compare different tag bytes and
    # silently exclude every row (e.g. a float literal on a bigint column)
    _BOUND_TYPES = {
        DataType.INT32: int, DataType.INT64: int,
        DataType.FLOAT: float, DataType.DOUBLE: float,
        DataType.STRING: str, DataType.BINARY: bytes,
        DataType.TIMESTAMP: int,
    }

    @classmethod
    def _bound_type_ok(cls, col_type, v) -> bool:
        want = cls._BOUND_TYPES.get(col_type)
        return want is not None and isinstance(v, want) \
            and not isinstance(v, bool)

    @staticmethod
    def _range_scan_bounds(schema, dk, prefix: bytes, residual) -> tuple:
        """Hybrid ScanChoices: inequality predicates on the first UNBOUND
        clustering column tighten the partition scan's byte range instead
        of filtering after a full-partition read (ref
        docdb/scan_choices.cc range bounds). Component encoding is
        order-preserving, so prefix+encode(v) bounds are exact; the
        predicates stay in the residual (bounds prune, the filter
        decides), so edge inclusivity cannot produce wrong rows."""
        from yugabyte_tpu.docdb.doc_key import PrimitiveValue
        lo, hi = prefix, prefix + b"\xff"
        bound_n = len(dk.range_components)
        if bound_n >= len(schema.range_columns):
            return lo, hi
        nxt_col = schema.range_columns[bound_n]
        nxt = nxt_col.name
        for c, op, v in residual:
            if c != nxt or op not in ("<", "<=", ">", ">="):
                continue
            if not QLProcessor._bound_type_ok(nxt_col.type, v):
                continue  # cross-type predicate: residual filter decides
            buf = bytearray()
            try:
                PrimitiveValue.encode(v, buf)
            except TypeError:
                continue
            enc = prefix + bytes(buf)
            if op in (">", ">="):
                cand = enc + (b"\xff" if op == ">" else b"")
                if cand > lo:
                    lo = cand
            else:
                cand = enc + (b"\xff" if op == "<=" else b"")
                if cand < hi:
                    hi = cand
        return lo, hi

    # -------------------------------------------------------- system vtables
    # Canonical column orders — the metadata contract is FIXED, not
    # derived from whichever rows happen to match (a zero-row
    # "SELECT * FROM system.peers" must still describe its columns).
    SYSTEM_VTABLES: Dict[Tuple[str, str], List[str]] = {
        ("system", "local"): ["key", "rpc_address", "rpc_port",
                              "data_center", "rack", "cluster_name",
                              "partitioner", "release_version",
                              "cql_version", "tokens"],
        ("system", "peers"): ["peer", "rpc_address", "data_center",
                              "rack", "tokens"],
        ("system_schema", "keyspaces"): ["keyspace_name", "durable_writes"],
        ("system_schema", "tables"): ["keyspace_name", "table_name", "id"],
        ("system_schema", "columns"): ["keyspace_name", "table_name",
                                       "column_name", "kind", "position",
                                       "type"],
    }

    def _system_rows(self, ks: str, table: str,
                     eq: Dict[str, object]) -> List[dict]:
        """Synthesized rows of the system/system_schema virtual tables —
        what every Cassandra driver queries on connect (ref: the master's
        YQLVirtualTable family, master/yql_local_vtable.cc,
        yql_peers_vtable.cc, yql_keyspaces_vtable.cc ...).

        eq: equality predicates pushed into generation — metadata
        refreshes filter by keyspace_name/table_name, and opening every
        table in the cluster to answer them would cost O(tables) master
        round-trips per query.

        This processor IS the CQL endpoint (the reference runs one per
        tserver; this architecture runs one standalone server embedding
        the client), so system.local describes THIS server and
        system.peers is empty — there are no other CQL endpoints."""
        if (ks, table) == ("system", "local"):
            host, port = (self.local_addr if self.local_addr
                          else ("127.0.0.1", 0))
            return [{"key": "local", "rpc_address": host,
                     "rpc_port": int(port),
                     "data_center": "datacenter1", "rack": "rack1",
                     "cluster_name": "ybtpu", "partitioner": "multi-hash",
                     "release_version": "3.9-SNAPSHOT",
                     "cql_version": "3.4.4", "tokens": ["0"]}]
        if (ks, table) == ("system", "peers"):
            return []
        want_ks = eq.get("keyspace_name")
        want_table = eq.get("table_name")
        namespaces = ([want_ks] if want_ks is not None
                      else self._client.list_namespaces())
        if (ks, table) == ("system_schema", "keyspaces"):
            return [{"keyspace_name": n, "durable_writes": True}
                    for n in namespaces]
        if (ks, table) == ("system_schema", "tables"):
            rows = []
            for n in namespaces:
                for t in self._client.list_tables(n):
                    if want_table is not None and t["name"] != want_table:
                        continue
                    rows.append({"keyspace_name": n,
                                 "table_name": t["name"],
                                 "id": t.get("table_id", "")})
            return rows
        if (ks, table) == ("system_schema", "columns"):
            rows = []
            for n in namespaces:
                for t in self._client.list_tables(n):
                    if want_table is not None and t["name"] != want_table:
                        continue
                    try:
                        schema = self._table(n, t["name"]).schema
                    except StatusError:
                        continue
                    hash_names = [c.name for c in schema.hash_columns]
                    range_names = [c.name for c in schema.range_columns]
                    for c in schema.columns:
                        kind = ("partition_key" if c.name in hash_names
                                else "clustering" if c.name in range_names
                                else "regular")
                        rows.append({"keyspace_name": n,
                                     "table_name": t["name"],
                                     "column_name": c.name,
                                     "kind": kind,
                                     "position": (
                                         hash_names.index(c.name)
                                         if kind == "partition_key"
                                         else range_names.index(c.name)
                                         if kind == "clustering" else -1),
                                     "type": c.type.value})
            return rows
        raise StatusError(Status.NotFound(f"table {ks}.{table}"))

    def _select_system(self, ks: str, stmt: P.Select, params: List[object],
                       cursor: List[int]) -> ResultSet:
        if (ks, stmt.table) not in self.SYSTEM_VTABLES:
            raise StatusError(Status.NotFound(f"table {ks}.{stmt.table}"))
        where = self._bind_where(stmt.where, params, cursor)
        eq = {c: v for c, op, v in where if op == "="}
        rows = [r for r in self._system_rows(ks, stmt.table, eq)
                if self._match(r, where)]
        items = stmt.columns or self.SYSTEM_VTABLES[(ks, stmt.table)]
        out_cols = [c if isinstance(c, str) else self._item_label(c)
                    for c in items]
        rs = ResultSet(columns=out_cols, types=[None] * len(out_cols),
                       source=(ks, stmt.table))
        limit = stmt.limit
        for r in rows:
            rs.rows.append([r.get(c) if isinstance(c, str) else None
                            for c in items])
            if limit is not None and len(rs.rows) >= limit:
                break
        return rs

    def _run_transaction(self, stmt: P.Transaction,
                         params: List[object]) -> ResultSet:
        """ref executor.cc transactional block execution + retry."""
        cursor = [0]
        for s in stmt.statements:
            if getattr(s, "if_not_exists", False) \
                    or getattr(s, "if_exists", False) \
                    or getattr(s, "conditions", None):
                # conditional DML inside a transaction block would need
                # per-statement [applied] results and condition reads at
                # the block's snapshot — reject loudly rather than apply
                # unconditionally (the reference likewise restricts LWT
                # in batches)
                raise StatusError(Status.NotSupported(
                    "conditional DML (IF ...) inside BEGIN TRANSACTION"))
        decoded = [self._dml_to_op(s, params, cursor)
                   for s in stmt.statements]
        deadline = time.monotonic() + 30
        while True:
            txn = self._txn_manager.begin()
            try:
                for table, op in decoded:
                    IM.txn_write_with_indexes(
                        txn, table, op,
                        lambda name, _t=table: self._table(
                            _t.namespace, name))
                txn.commit()
                return ResultSet()
            except TransactionError:
                txn.abort()
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.05)
            except BaseException:
                # Non-conflict failure: abort, or the still-heartbeating
                # txn would pin its intents indefinitely.
                txn.abort()
                raise
