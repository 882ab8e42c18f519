"""The typed, grouped SELECT: `SELECT g1, g2, fn(term) [AS a], ... FROM t
WHERE <conjunction> GROUP BY g1, g2 [ORDER BY ...]`, planned onto
`YBClient.scan_aggregate`'s grouped form (TPC-H Q1 and Q6 are statements of
this shape). An extension over YCQL, which has neither GROUP BY nor
arithmetic in an aggregate: the CQL front end carries it because its
aggregates already plan onto `scan_aggregate` with the per-tablet rows
fallback, while the pgsql front end maps DECIMAL to DOUBLE and DATE to
text and folds rows.

Every tablet answers at one pinned hybrid time, from its leader, with one
device partial (ops/scan_group.py); a tablet that cannot (the spec refused
there: `PushdownUnsupported`, counted by reason) answers in rows, which are
re-checked with the executor's own `_match` and folded by
`scan_spec.group_partial_from_dicts` into the same partial form. Partials
combine group by group in exact integers; AVG is divided once, here.

Stored forms (common/schema.py): DECIMAL(p, s) is the unscaled integer,
DATE is days since 1970-01-01, CHAR(n) its text. `to_stored` /
`from_stored` are the only conversions, used for literals, bind values,
INSERT values and result cells alike.
"""

from __future__ import annotations

import datetime
import decimal
from typing import List, Optional

from yugabyte_tpu.common.schema import ColumnSchema, DataType
from yugabyte_tpu.utils.status import Status, StatusError

_EPOCH = datetime.date(1970, 1, 1)
TYPED = (DataType.DECIMAL, DataType.DATE, DataType.CHAR)


def to_stored(col: ColumnSchema, v):
    """A literal or bind value -> what the column stores."""
    if v is None:
        return None
    if col.type is DataType.DECIMAL:
        if isinstance(v, bool):
            raise StatusError(Status.InvalidArgument(
                f"{col.name}: a boolean is no decimal"))
        text = getattr(v, "text", None)
        d = decimal.Decimal(text if text is not None
                            else (repr(v) if isinstance(v, float) else v))
        scaled = d.scaleb(col.scale)
        if scaled != scaled.to_integral_value():
            raise StatusError(Status.InvalidArgument(
                f"{col.name}: {d} has more than {col.scale} decimals"))
        return int(scaled)
    if col.type is DataType.DATE:
        if isinstance(v, datetime.date):
            return (v - _EPOCH).days
        if isinstance(v, str):
            return (datetime.date.fromisoformat(v) - _EPOCH).days
        return int(v)
    if col.type is DataType.CHAR and not isinstance(v, str):
        raise StatusError(Status.InvalidArgument(
            f"{col.name}: CHAR takes text"))
    return v


def from_stored(col: ColumnSchema, v):
    if v is None:
        return None
    if col.type is DataType.DECIMAL:
        return decimal.Decimal(v).scaleb(-col.scale)
    if col.type is DataType.DATE:
        return _EPOCH + datetime.timedelta(days=v)
    return v


def typed_where(schema, where):
    """WHERE literals of DECIMAL / DATE / CHAR columns in stored form."""
    out = []
    for c, op, v in where:
        col = _typed_column(schema, c)
        if col is not None:
            v = [to_stored(col, x) for x in v] if op == "in" \
                else to_stored(col, v)
        out.append((c, op, v))
    return out


def stored_value(schema, name, v):
    """`v` as column `name` stores it (an INSERT's value)."""
    col = _typed_column(schema, name)
    return v if col is None else to_stored(col, v)


def _typed_column(schema, name) -> Optional[ColumnSchema]:
    if not isinstance(name, str):
        return None
    try:
        col = schema.column(name)
    except KeyError:
        return None
    return col if col.type in TYPED else None


def typed_result(schema, rs) -> None:
    """A plain SELECT's cells of typed columns, out of stored form."""
    typed = [(j, col) for j, col in
             ((j, _typed_column(schema, name))
              for j, name in enumerate(rs.columns)) if col is not None]
    if not typed:
        return
    rows = [list(row) for row in rs.rows]
    for row in rows:
        for j, col in typed:
            row[j] = from_stored(col, row[j])
    rs.rows = rows


def wants_grouped(P, schema, stmt, items) -> bool:
    """A GROUP BY, a product term, or an aggregate over a typed column."""
    if stmt.group_by:
        return True
    for it in items:
        if isinstance(it, P.FuncCall) and len(it.args) == 1:
            a = it.args[0]
            if isinstance(a, P.Product):
                return True
            if isinstance(a, P.ColumnRef) \
                    and _typed_column(schema, a.name) is not None \
                    and it.name.lower() in ("count", "sum", "avg", "min",
                                            "max"):
                return True
    return False


def select_grouped(proc, P, stmt, items, params, cursor):
    """Plan and run one grouped SELECT; returns the ResultSet, whose
    `pushdown` attribute says how the tablets answered."""
    from yugabyte_tpu.docdb import scan_spec as SS
    from yugabyte_tpu.yql.cql.executor import ResultSet
    table = proc._table(stmt.keyspace, stmt.table)
    schema = table.schema
    out = []                # ("group", name) | ("agg", index into aggs)
    wire_aggs: List[list] = []
    labels: List[str] = []
    for i, it in enumerate(items):
        if isinstance(it, str):
            if it not in stmt.group_by:
                raise StatusError(Status.InvalidArgument(
                    f"column {it} must appear in GROUP BY or in an "
                    f"aggregate"))
            out.append(("group", it))
            labels.append(stmt.aliases.get(i, it))
            continue
        if not (isinstance(it, P.FuncCall) and len(it.args) == 1
                and it.name.lower() in SS.AGG_FNS):
            raise StatusError(Status.InvalidArgument(
                "a grouped SELECT lists group columns and aggregates"))
        arg = it.args[0]
        if arg == "*":
            term, text = None, "*"
        elif isinstance(arg, P.ColumnRef):
            term, text = [["col", arg.name]], arg.name
        elif isinstance(arg, P.Product):
            term = [list(f) for f in arg.factors]
            text = "*".join(c if k == "col" else f"({k[0]}{k[1]}{c})"
                            for k, c in arg.factors)
        else:
            raise StatusError(Status.InvalidArgument(
                f"{it.name}: unsupported aggregate argument"))
        out.append(("agg", len(wire_aggs)))
        wire_aggs.append([it.name.lower(), term])
        labels.append(stmt.aliases.get(i, f"{it.name.lower()}({text})"))
    where = typed_where(schema, proc._bind_where(stmt.where, params,
                                                 cursor))
    filters = [[c, op, v] for c, op, v in where]
    spec, reason = SS.compile_group_aggregate(schema, filters, wire_aggs,
                                              stmt.group_by)
    if spec is None:
        raise StatusError(Status.NotSupported(
            f"grouped aggregate outside the supported subset ({reason})"))
    fb_dicts: List[dict] = []

    def on_row(row):
        d = proc._row_dict(schema, row)
        if proc._match(d, where):
            fb_dicts.append(d)

    walk: dict = {}
    partial, _ht = proc._client.scan_aggregate(
        table, wire_aggs, filters=filters, row_cb=on_row,
        group_by=stmt.group_by, walk_stats=walk)
    parts = [partial] if partial is not None else []
    if fb_dicts:
        parts.append(SS.group_partial_from_dicts(spec, fb_dicts))
    groups = SS.combine_group_partials(parts)["groups"]
    if not groups and not stmt.group_by:
        # an aggregate over no rows is still one row: counts 0, the rest NULL
        groups = [{"key": [], "rows": 0,
                   "terms": [SS.empty_term_stats() for _ in spec.terms]}]
    gcols = [schema.column(n) for n in stmt.group_by]
    rows = []
    for g in groups:
        row = []
        for kind, ref in out:
            if kind == "group":
                j = stmt.group_by.index(ref)
                row.append(from_stored(gcols[j], g["key"][j]))
            else:
                row.append(_agg_cell(schema, spec.aggregates[ref], g))
        rows.append(row)
    _order(rows, labels, items, stmt)
    if stmt.limit is not None:
        rows = rows[:stmt.limit]
    rs = ResultSet(columns=labels, rows=rows,
                   types=[None] * len(labels),
                   source=(table.namespace, table.name))
    rs.pushdown = {"tablets": walk.get("tablets", 0),
                   "from_rows": walk.get("from_rows", 0)}
    return rs


def _agg_cell(schema, agg, group):
    """One aggregate's output cell from its group's exact statistics."""
    if agg.fn == "count":
        return group["rows"] if agg.term < 0 \
            else group["terms"][agg.term]["nonnull"]
    st = group["terms"][agg.term]
    if not st["nonnull"]:
        return None
    cols = [schema.column(f.col) for f in agg.factors]
    is_decimal = any(c.type is DataType.DECIMAL for c in cols)
    if agg.fn == "avg":
        # (sum, count), divided once, at the client
        if not is_decimal:
            return st["sum"] // st["nonnull"]
        return (decimal.Decimal(st["sum"])
                / decimal.Decimal(st["nonnull"])).scaleb(-agg.scale)
    v = st[agg.fn]
    if is_decimal:
        return decimal.Decimal(v).scaleb(-agg.scale)
    if len(cols) == 1 and cols[0].type is DataType.DATE \
            and agg.fn in ("min", "max"):
        return from_stored(cols[0], v)
    return v


def _order(rows, labels, items, stmt) -> None:
    """ORDER BY over the groups is the client's: by output label or group
    column, NULLs last; without one, by the group key."""
    keys = stmt.order_by or [(g, False) for g in stmt.group_by]
    for name, desc in reversed(keys):
        if name in labels:
            j = labels.index(name)
        else:
            j = next((i for i, it in enumerate(items) if it == name), None)
            if j is None:
                raise StatusError(Status.InvalidArgument(
                    f"ORDER BY {name}: not in the select list"))
        rows.sort(key=lambda r, j=j: (1,) if r[j] is None else (0, r[j]),
                  reverse=desc)
