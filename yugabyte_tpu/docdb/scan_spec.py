"""ScanSpec: the pushed-down query fragment a scan carries to storage.

ROADMAP item 5 (query pushdown): the YCQL executor classifies a SELECT's
WHERE conjunction + aggregate list into the device-compilable subset and
threads the result — this ScanSpec — through the scan RPC down to
`ops/scan.py`'s fused filtered/aggregating kernels, so predicate checks
and COUNT/SUM/MIN/MAX reductions happen where the data sits instead of
surfacing every row to host Python (the LSM-OPD compute-where-the-data-
sits argument applied to the query layer).

The compilable subset is deliberately EXACT, never approximate: a
predicate compiles only when the device's encoded-byte comparison is
provably identical to the host path's decoded-Python comparison —
  - integer-family columns (INT32/INT64/TIMESTAMP): every int encodes as
    kInt64 + big-endian offset binary (docdb/doc_key.py), so memcmp
    order == numeric order and byte equality == value equality;
  - BOOL columns: the value IS the tag byte (kFalse=70 < kTrue=84,
    matching Python False < True).
Floats are excluded (the -0.0/NaN corners of IEEE comparison diverge
from the order-preserving byte transform), strings are excluded
(variable width exceeds the fixed value-word stride), collections/jsonb
are excluded (their "value" is a subdocument). Anything outside the
subset falls back to the host path per query, byte/result-identically,
counted by reason (`scan_pushdown_fallback_*_total`).

NULL semantics are mode-exact: the AGGREGATE path implements the CQL
executor's `_match` (a NULL/absent column fails the row for EVERY
operator, `!=` included — there is no per-row re-check downstream of a
scalar), while the ROW-SCAN path implements the wire filter contract
(`common/wire.FILTER_OPS`, what the tserver's host fallback and the
pgsql pushdown evaluate): NULL fails everything EXCEPT `!=`, which it
passes — packed on device as NOT(exists an equal entry). On device the
NULL exclusion is the payload-tag check — a kNullLow payload never
matches a kInt64/kTrue/kFalse tag pair.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from yugabyte_tpu.common.schema import DataType, Schema
from yugabyte_tpu.docdb.doc_key import PrimitiveValue
from yugabyte_tpu.docdb.value_type import ValueType

class PushdownUnsupported(Exception):
    """A compiled ScanSpec hit a storage-side blocker (deep documents,
    missing device, oversized batch, ...): the caller must serve the
    query through the host path. `reason` keys the fallback counter."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


# operators the fused kernel evaluates (op codes are kernel operand data)
PUSHDOWN_OPS = ("=", "!=", "<", "<=", ">", ">=")
OP_CODES = {op: i + 1 for i, op in enumerate(PUSHDOWN_OPS)}  # 0 = inactive

# integer-family column types: stored payloads are kInt64 + 8B biased BE.
# DECIMAL (the unscaled integer; literals arrive unscaled too — the query
# layer owns the scale) and DATE (days) are integers like the rest.
_INT_TYPES = (DataType.INT32, DataType.INT64, DataType.TIMESTAMP,
              DataType.DECIMAL, DataType.DATE)
# the typed columns that route an aggregate to the grouped kernel
_TYPED = (DataType.DECIMAL, DataType.DATE, DataType.CHAR)

AGG_FNS = ("count", "sum", "avg", "min", "max")

# value words per entry staged for pushdown: 3 words = 12 bytes covers
# the widest compilable payload (kInt64 tag + 8 bytes = 9)
VAL_WORDS = 3


@dataclass(frozen=True)
class ColPredicate:
    """One compiled column comparison: `col op literal`."""
    col: str
    cid: int
    op: str
    value: object
    enc: bytes           # encoded payload bytes of the literal
    tag_a: int           # acceptable payload tag byte(s): a stored value
    tag_b: int           # outside {tag_a, tag_b} fails the row (NULLs)


@dataclass(frozen=True)
class AggSpec:
    """One aggregate over the filtered row set. col/cid are None for
    COUNT(*)."""
    fn: str
    col: Optional[str] = None
    cid: Optional[int] = None
    tag_a: int = 0
    tag_b: int = 0


@dataclass(frozen=True)
class ScanSpec:
    """Predicate conjunction + aggregate list the kernels evaluate."""
    predicates: Tuple[ColPredicate, ...] = ()
    aggregates: Tuple[AggSpec, ...] = ()

    @property
    def needs_vals(self) -> bool:
        """True when the dispatch needs the staged value words: any
        column predicate, or any aggregate naming a column (COUNT(col)
        checks the payload tag to exclude NULLs)."""
        return bool(self.predicates) or any(a.cid is not None
                                            for a in self.aggregates)

    @property
    def agg_cids(self) -> Tuple[int, ...]:
        """Distinct aggregated column ids, in first-appearance order."""
        seen: List[int] = []
        for a in self.aggregates:
            if a.cid is not None and a.cid not in seen:
                seen.append(a.cid)
        return tuple(seen)


def _column(schema: Schema, name):
    if not isinstance(name, str):
        return None
    try:
        return schema.column(name)
    except KeyError:
        return None


def _value_tags(col_type: DataType, value) -> Optional[Tuple[int, int]]:
    """(tag_a, tag_b) acceptable payload tags for a literal on a column,
    or None when the (type, literal) pair is outside the subset."""
    if col_type in _INT_TYPES:
        if isinstance(value, bool) or not isinstance(value, int):
            return None
        return (int(ValueType.kInt64), int(ValueType.kInt64))
    if col_type is DataType.BOOL:
        if not isinstance(value, bool):
            return None
        return (int(ValueType.kFalse), int(ValueType.kTrue))
    if col_type is DataType.CHAR:
        # short fixed-length strings: the whole encoded payload must sit
        # inside the staged value words, or byte order would be compared
        # on a truncated prefix
        if not isinstance(value, str) \
                or len(encode_literal(value)) > VAL_WORDS * 4:
            return None
        return (int(ValueType.kString), int(ValueType.kString))
    return None


def encode_literal(value) -> bytes:
    """Encoded DocValue payload bytes of a predicate literal — exactly
    what a stored (non-NULL, non-TTL'd) cell of that value holds."""
    buf = bytearray()
    PrimitiveValue.encode(value, buf)
    return bytes(buf)


def compile_predicate(schema: Schema, col, op: str,
                      value) -> Optional[ColPredicate]:
    """Compile one WHERE triple, or None when outside the subset (wrong
    op, key column, collection/jsonb/float/string column, mistyped or
    NULL literal)."""
    if op not in PUSHDOWN_OPS or value is None:
        return None
    c = _column(schema, col)
    if c is None or c.collection is not None:
        return None
    key_names = {k.name for k in schema.hash_columns} | \
        {k.name for k in schema.range_columns}
    if col in key_names:
        # key components are pushed as encoded byte BOUNDS by the scan
        # planner, not as value predicates (they have no column entry)
        return None
    tags = _value_tags(c.type, value)
    if tags is None:
        return None
    return ColPredicate(col=col, cid=schema.column_id(col), op=op,
                        value=value, enc=encode_literal(value),
                        tag_a=tags[0], tag_b=tags[1])


def compile_aggregate(schema: Schema, fn: str,
                      col: Optional[str]) -> Optional[AggSpec]:
    """Compile one aggregate, or None when outside the subset. SUM/AVG/
    MIN/MAX compile only over integer-family columns (exact byte-column
    sums + biased-limb min/max); COUNT(col) additionally over BOOL."""
    fn = fn.lower()
    if fn not in AGG_FNS:
        return None
    if col is None:
        return AggSpec(fn="count") if fn == "count" else None
    c = _column(schema, col)
    if c is None or c.collection is not None:
        return None
    key_names = {k.name for k in schema.hash_columns} | \
        {k.name for k in schema.range_columns}
    if col in key_names:
        # key components have no column entries to reduce over (and a
        # key is never NULL — the host path answers COUNT(key) exactly)
        return None
    if c.type in _INT_TYPES:
        tags = (int(ValueType.kInt64), int(ValueType.kInt64))
    elif c.type is DataType.BOOL and fn == "count":
        tags = (int(ValueType.kFalse), int(ValueType.kTrue))
    else:
        return None
    return AggSpec(fn=fn, col=col, cid=schema.column_id(col),
                   tag_a=tags[0], tag_b=tags[1])


def compile_filters(schema: Schema, filters: Optional[Sequence[Sequence]],
                    aggregates: Optional[Sequence[Sequence]] = None
                    ) -> Tuple[Optional[ScanSpec], List[List], str]:
    """Classify a wire filter conjunction (+ optional aggregate list)
    into (spec, leftover_filters, reason).

    spec is None — with `reason` naming the first blocker — when nothing
    is pushable, or when aggregates were requested but ANY aggregate or
    ANY filter is outside the subset (an aggregating scan cannot half-
    push: the scalar must be computed over exactly the filtered row
    set). For row scans partial pushdown is fine: leftover_filters are
    evaluated host-side after the fused filter."""
    filters = filters or ()
    preds: List[ColPredicate] = []
    leftover: List[List] = []
    reason = ""
    for f in filters:
        col, op, value = f[0], f[1], f[2]
        p = compile_predicate(schema, col, op, value)
        if p is None:
            leftover.append(list(f))
            reason = reason or ("op" if op not in PUSHDOWN_OPS else "type")
        else:
            preds.append(p)
    if aggregates:
        aggs: List[AggSpec] = []
        for a in aggregates:
            spec = compile_aggregate(schema, a[0], a[1])
            if spec is None:
                return None, [list(f) for f in filters], "agg_type"
            aggs.append(spec)
        if leftover:
            return None, [list(f) for f in filters], reason or "type"
        return ScanSpec(tuple(preds), tuple(aggs)), [], ""
    if not preds:
        return None, leftover, reason or "no_predicates"
    return ScanSpec(tuple(preds)), leftover, ""


def combine_agg_partials(partials: Sequence[dict]) -> dict:
    """Merge per-tablet aggregate partials (disjoint row sets): counts
    and sums add, mins/maxes reduce, None means "no qualifying rows".
    Grouped partials (the typed kernel's) combine group by group."""
    if any("groups" in p for p in partials):
        return combine_group_partials(partials)
    out = {"rows": 0, "cols": {}}
    for p in partials:
        out["rows"] += int(p.get("rows", 0))
        for cid, st in (p.get("cols") or {}).items():
            cid = int(cid)
            dst = out["cols"].setdefault(
                cid, {"nonnull": 0, "sum": 0, "min": None, "max": None})
            dst["nonnull"] += int(st.get("nonnull", 0))
            dst["sum"] += int(st.get("sum", 0))
            for k, pick in (("min", min), ("max", max)):
                v = st.get(k)
                if v is None:
                    continue
                dst[k] = v if dst[k] is None else pick(dst[k], v)
    return out


# ---------------------------------------------------------------------------
# The typed, grouped aggregate (TPC-H Q1 / Q6 shape): product terms over
# exact fixed-point columns, a short group list, predicates over the typed
# columns. One dispatch a tablet in ops/scan_group.py; everything is integer
# arithmetic, and a DECIMAL answer is exact or refused.

FACTOR_KINDS = ("col", "1-", "1+")      # col, (1 - col), (1 + col)
MAX_FACTORS = 3
MAX_GROUP_COLS = 2
_GROUP_TYPES = _INT_TYPES + (DataType.BOOL, DataType.CHAR)


@dataclass(frozen=True)
class Factor:
    """One factor of a product term. `one` is 1 at the column's scale:
    `(1 - col)` over a DECIMAL(15,2) is `100 - stored`."""
    kind: str
    col: str
    cid: int
    scale: int

    @property
    def one(self) -> int:
        return 10 ** self.scale


@dataclass(frozen=True)
class TermAgg:
    """fn over a product of up to MAX_FACTORS factors; `factors` is ()
    for COUNT(*). `term` indexes GroupAggSpec.terms (the distinct factor
    tuples: SUM and AVG of one term share its sum), -1 for COUNT(*).
    `scale` is the sum of the factors' scales: Q1's charge is scale 6."""
    fn: str
    factors: Tuple[Factor, ...] = ()
    term: int = -1
    scale: int = 0


@dataclass(frozen=True)
class GroupCol:
    col: str
    cid: int


@dataclass(frozen=True)
class GroupAggSpec:
    """What the grouped kernel evaluates: the predicate conjunction, the
    distinct product terms, the aggregates over them, the group list."""
    predicates: Tuple[ColPredicate, ...] = ()
    aggregates: Tuple[TermAgg, ...] = ()
    terms: Tuple[Tuple[Factor, ...], ...] = ()
    group_by: Tuple[GroupCol, ...] = ()
    needs_vals = True

    @property
    def cids(self) -> Tuple[int, ...]:
        """Distinct value columns the kernel lifts to row level, in
        first-use order (predicates, groups, factors)."""
        seen: List[int] = []
        for cid in ([p.cid for p in self.predicates]
                    + [g.cid for g in self.group_by]
                    + [f.cid for t in self.terms for f in t]):
            if cid not in seen:
                seen.append(cid)
        return tuple(seen)

    @property
    def wants_minmax(self) -> bool:
        return any(a.fn in ("min", "max") for a in self.aggregates)


def _value_column(schema: Schema, name):
    """A non-key, non-collection column, or None."""
    c = _column(schema, name)
    if c is None or c.collection is not None:
        return None
    if name in {k.name for k in schema.hash_columns} | \
            {k.name for k in schema.range_columns}:
        return None
    return c


def _term_of(agg) -> Optional[list]:
    """The wire aggregate's term as [[kind, col], ...]; [] for COUNT(*);
    None when malformed."""
    t = agg[1] if len(agg) > 1 else None
    if t is None:
        return []
    if isinstance(t, str):
        return [["col", t]]
    if isinstance(t, (list, tuple)) and t and all(
            isinstance(f, (list, tuple)) and len(f) == 2 for f in t):
        return [list(f) for f in t]
    return None


def wants_group_kernel(schema: Schema, filters, aggregates,
                       group_by) -> bool:
    """True when the (filters, aggregates, group list) triple is the
    typed kernel's to answer: a group list, a product term, or any
    DECIMAL / DATE / CHAR column. Everything else stays with the scalar
    kernel it had."""
    if group_by:
        return True
    names = [f[0] for f in filters or () if isinstance(f[0], str)]
    for a in aggregates or ():
        t = _term_of(a)
        if t is None or len(t) > 1 or any(k != "col" for k, _c in t):
            return True
        names += [c for _k, c in t]
    for n in names:
        c = _column(schema, n)
        if c is not None and c.type in _TYPED:
            return True
    return False


def compile_group_aggregate(schema: Schema, filters, aggregates, group_by
                            ) -> Tuple[Optional[GroupAggSpec], str]:
    """(spec, "") or (None, reason). Nothing half-pushes: one aggregate,
    predicate or group column outside the subset refuses the whole."""
    preds: List[ColPredicate] = []
    for f in filters or ():
        p = compile_predicate(schema, f[0], f[1], f[2])
        if p is None:
            return None, "op" if f[1] not in PUSHDOWN_OPS else "type"
        preds.append(p)
    groups: List[GroupCol] = []
    for name in group_by or ():
        c = _value_column(schema, name)
        if c is None or c.type not in _GROUP_TYPES:
            return None, "group_type"
        groups.append(GroupCol(name, schema.column_id(name)))
    if len(groups) > MAX_GROUP_COLS:
        return None, "group_width"
    terms: List[Tuple[Factor, ...]] = []
    aggs: List[TermAgg] = []
    for a in aggregates or ():
        fn = str(a[0]).lower()
        raw = _term_of(a)
        if fn not in AGG_FNS or raw is None:
            return None, "agg_type"
        if not raw:
            if fn != "count":
                return None, "agg_type"
            aggs.append(TermAgg("count"))
            continue
        if len(raw) > MAX_FACTORS:
            return None, "agg_width"
        factors = []
        for kind, name in raw:
            c = _value_column(schema, name)
            if kind not in FACTOR_KINDS or c is None \
                    or c.type not in _INT_TYPES:
                return None, "agg_type"
            factors.append(Factor(kind, name, schema.column_id(name),
                                  c.scale))
        key = tuple(factors)
        if key not in terms:
            terms.append(key)
        scale = sum(f.scale for f in factors)
        aggs.append(TermAgg(fn, key, terms.index(key), scale))
    if not aggs:
        return None, "agg_type"
    return GroupAggSpec(tuple(preds), tuple(aggs), tuple(terms),
                        tuple(groups)), ""


def empty_term_stats() -> dict:
    return {"nonnull": 0, "sum": 0, "min": None, "max": None}


def combine_group_partials(partials: Sequence[dict]) -> dict:
    """Grouped partials ({"groups": [{"key", "rows", "terms"}]}) from
    disjoint row sets, merged group by group: exact Python integers."""
    merged: dict = {}
    for p in partials:
        for g in p.get("groups") or ():
            key = tuple(g["key"])
            dst = merged.get(key)
            if dst is None:
                dst = merged[key] = {
                    "key": list(key), "rows": 0,
                    "terms": [empty_term_stats() for _ in g["terms"]]}
            dst["rows"] += int(g["rows"])
            for d, st in zip(dst["terms"], g["terms"]):
                d["nonnull"] += int(st["nonnull"])
                d["sum"] += int(st["sum"])
                for k, pick in (("min", min), ("max", max)):
                    v = st.get(k)
                    if v is not None:
                        d[k] = v if d[k] is None else pick(d[k], v)
    return {"groups": list(merged.values())}


def group_partial_from_dicts(spec: GroupAggSpec, dicts) -> dict:
    """The rows path's twin of the kernel's partial: the same groups and
    the same statistics from decoded row dicts (rows that already passed
    the predicates), in Python integers. The grouped kernel is held to
    this, and a refused spec is answered by it."""
    merged: dict = {}
    for d in dicts:
        key = tuple(d.get(g.col) for g in spec.group_by)
        dst = merged.get(key)
        if dst is None:
            dst = merged[key] = {
                "key": list(key), "rows": 0,
                "terms": [empty_term_stats() for _ in spec.terms]}
        dst["rows"] += 1
        for st, factors in zip(dst["terms"], spec.terms):
            v = 1
            for f in factors:
                x = d.get(f.col)
                if x is None:
                    v = None
                    break
                v *= x if f.kind == "col" else \
                    (f.one - x if f.kind == "1-" else f.one + x)
            if v is None:
                continue
            st["nonnull"] += 1
            st["sum"] += v
            st["min"] = v if st["min"] is None else min(st["min"], v)
            st["max"] = v if st["max"] is None else max(st["max"], v)
    return {"groups": list(merged.values())}
