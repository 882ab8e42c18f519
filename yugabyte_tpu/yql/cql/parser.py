"""YCQL-subset parser: hand-written tokenizer + recursive descent.

Capability parity with the reference's CQL frontend (ref: src/yb/yql/cql/ql/
parser/ — a bison grammar over the full CQL dialect; ptree/ analyzer). This
covers the core DML/DDL surface (the YCSB / kv-workload subset plus
multi-statement transactions): CREATE KEYSPACE / CREATE TABLE with
hash+range primary keys / DROP TABLE / INSERT (USING TTL) / SELECT with
WHERE + LIMIT / UPDATE / DELETE / BEGIN TRANSACTION ... END TRANSACTION.
Bind markers (?) fill from an ordered params list, like the reference's
prepared statements.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

from yugabyte_tpu.utils.status import Status, StatusError


class ParseError(StatusError):
    def __init__(self, msg: str):
        super().__init__(Status.InvalidArgument(f"syntax error: {msg}"))


_TOKEN_RE = re.compile(r"""
    \s*(?:
      (?P<string>'(?:[^']|'')*')
    | (?P<blob>0[xX][0-9a-fA-F]+)
    | (?P<number>-?\d+\.\d+|-?\d+)
    | (?P<name>[A-Za-z_][A-Za-z0-9_]*)
    | (?P<param>\$\d+)
    | (?P<op>->>|->|<=|>=|!=|[=<>(),;*?.+%/\[\]{}:-])
    )""", re.VERBOSE)


def tokenize(text: str) -> List[Tuple[str, str]]:
    out = []
    pos = 0
    while pos < len(text):
        m = _TOKEN_RE.match(text, pos)
        if m is None:
            if text[pos:].strip() == "":
                break
            raise ParseError(f"unexpected character {text[pos]!r}")
        pos = m.end()
        kind = m.lastgroup
        tok = m.group(kind)
        # `price-2` / `3-1`: a '-' directly after a value-like token is
        # the subtraction operator, not a negative-literal sign (PG lexes
        # '-' as an operator always; we keep the sign only where a value
        # cannot precede it, e.g. VALUES (-5))
        if kind == "number" and tok.startswith("-") and out and (
                out[-1][0] in ("name", "number", "blob", "param")
                or out[-1] == ("op", ")")):
            out.append(("op", "-"))
            out.append(("number", tok[1:]))
        else:
            out.append((kind, tok))
    return out


# --------------------------------------------------------------- statements
@dataclass
class CreateKeyspace:
    name: str
    if_not_exists: bool = False


@dataclass
class CreateTable:
    keyspace: Optional[str]
    name: str
    columns: List[Tuple[str, str]]            # (name, cql type)
    hash_keys: List[str]
    range_keys: List[str]
    num_tablets: int = 4
    if_not_exists: bool = False


@dataclass
class DropTable:
    keyspace: Optional[str]
    name: str
    if_exists: bool = False


@dataclass
class AlterTable:
    keyspace: Optional[str]
    name: str
    add_columns: List[Tuple[str, str]]   # (name, cql type)
    drop_columns: List[str]


@dataclass
class CreateIndex:
    index_name: Optional[str]
    keyspace: Optional[str]
    table: str
    columns: List[str]
    if_not_exists: bool = False


class DecimalText(float):
    """A numeric literal with a point: a float to everything that took
    one before, with the literal's own text beside it, so that a DECIMAL
    column reads the digits as written (no float on that path)."""

    def __new__(cls, text: str):
        obj = super().__new__(cls, text)
        obj.text = text
        return obj


@dataclass(frozen=True)
class Product:
    """An aggregate's argument that is a product of up to three factors
    `col`, `(1 - col)`, `(1 + col)`: ((kind, column), ...), kind one of
    col / 1- / 1+ (TPC-H Q1's `l_extendedprice * (1 - l_discount)`)."""
    factors: Tuple[Tuple[str, str], ...]


@dataclass
class FuncCall:
    """Builtin invocation in a select list or value expression (ref: the
    grammar's function_call; resolved against yql/bfunc.py's registry,
    the bfql/directory.cc equivalent)."""
    name: str
    args: List[object]                        # ColumnRef | FuncCall | literal


@dataclass(frozen=True)
class ColumnRef:
    name: str


@dataclass(frozen=True)
class TokenRef:
    """token(pk_cols): the row's 16-bit partition hash — the CQL token
    function used for partition-range scans by bulk readers (ref: the
    grammar's token function; our partition hash is
    common/partition.hash_column_compound_value)."""
    columns: Tuple[str, ...]


@dataclass(frozen=True)
class JsonOp:
    """JSONB path navigation: col->'key'->2->>'leaf' (ref: the reference's
    jsonb operators in ql — common/jsonb.cc ApplyJsonbOperators; PG's
    jsonb -> / ->> semantics). path holds object keys (str) and array
    indexes (int); as_text marks a trailing ->> (text extraction)."""
    column: str
    path: Tuple[object, ...]
    as_text: bool = False


@dataclass
class Insert:
    keyspace: Optional[str]
    table: str
    columns: List[str]
    values: List[object]                      # literal | FuncCall
    ttl_seconds: Optional[int] = None
    # lightweight transaction: INSERT ... IF NOT EXISTS (ref: the CQL
    # conditional DML surface; executed as a read-check-write txn like
    # the reference's conditional QLWriteRequest with if_expr)
    if_not_exists: bool = False


@dataclass
class Select:
    keyspace: Optional[str]
    table: str
    columns: Optional[List[str]]              # None = *
    where: List[Tuple[str, str, object]] = field(default_factory=list)
    limit: Optional[int] = None
    # SELECT DISTINCT <partition key cols> (CQL restricts DISTINCT to
    # the partition key; ref the grammar's distinct handling)
    distinct: bool = False
    # ORDER BY clustering_col [ASC|DESC] — valid only with the partition
    # key restricted (CQL semantics; ref: sem/analyzer order-by checks)
    order_by: List[Tuple[str, bool]] = field(default_factory=list)
    # GROUP BY value columns: the typed, grouped aggregate (an extension
    # over YCQL, which has none; yql/cql/grouped.py)
    group_by: List[str] = field(default_factory=list)
    # select item position -> its AS alias
    aliases: Dict[int, str] = field(default_factory=dict)


@dataclass
class Update:
    keyspace: Optional[str]
    table: str
    assignments: List[Tuple[str, object]]
    where: List[Tuple[str, str, object]]
    ttl_seconds: Optional[int] = None
    # IF EXISTS / IF col op val [AND ...] conditions (LWT)
    if_exists: bool = False
    conditions: List[Tuple[str, str, object]] = field(default_factory=list)


@dataclass
class Delete:
    keyspace: Optional[str]
    table: str
    where: List[Tuple[str, str, object]]
    columns: Optional[List[str]] = None       # DELETE col FROM ...
    if_exists: bool = False
    conditions: List[Tuple[str, str, object]] = field(default_factory=list)


@dataclass
class Truncate:
    """TRUNCATE [TABLE] ks.t (ref: the CQL truncate statement, executed
    by the reference as a whole-tablet truncation)."""
    keyspace: Optional[str]
    table: str


@dataclass
class Transaction:
    statements: List[Union[Insert, Update, Delete]]


@dataclass
class UseKeyspace:
    name: str


Statement = Union[CreateKeyspace, CreateTable, DropTable, Insert, Select,
                  Update, Delete, Transaction, UseKeyspace]


class _Marker:
    """A `?` bind marker awaiting a parameter."""


MARKER = _Marker()


class Parser:
    def __init__(self, text: str):
        self.toks = tokenize(text)
        self.pos = 0

    # ------------------------------------------------------------- helpers
    def peek(self) -> Optional[Tuple[str, str]]:
        return self.toks[self.pos] if self.pos < len(self.toks) else None

    def next(self) -> Tuple[str, str]:
        tok = self.peek()
        if tok is None:
            raise ParseError("unexpected end of statement")
        self.pos += 1
        return tok

    def accept_kw(self, *words: str) -> bool:
        tok = self.peek()
        if tok and tok[0] == "name" and tok[1].upper() == words[0]:
            save = self.pos
            for i, w in enumerate(words):
                tok = self.peek()
                if not (tok and tok[0] == "name" and tok[1].upper() == w):
                    self.pos = save
                    return False
                self.pos += 1
            return True
        return False

    def expect_kw(self, *words: str) -> None:
        if not self.accept_kw(*words):
            raise ParseError(f"expected {' '.join(words)}, got {self.peek()}")

    def accept_op(self, op: str) -> bool:
        tok = self.peek()
        if tok and tok[0] == "op" and tok[1] == op:
            self.pos += 1
            return True
        return False

    def expect_op(self, op: str) -> None:
        if not self.accept_op(op):
            raise ParseError(f"expected {op!r}, got {self.peek()}")

    def name(self) -> str:
        tok = self.next()
        if tok[0] != "name":
            raise ParseError(f"expected identifier, got {tok[1]!r}")
        return tok[1]

    def qualified_name(self) -> Tuple[Optional[str], str]:
        first = self.name()
        if self.accept_op("."):
            return first, self.name()
        return None, first

    def _column_type(self) -> str:
        """Type name, including collections: LIST<T>, SET<T>, MAP<K,V>,
        FROZEN<...> (ref: common/ql_type.h). Returned as the canonical
        text form, e.g. 'MAP<TEXT,INT>'."""
        t = self.name().upper()
        if t == "FROZEN" and self.accept_op("<"):
            inner = self._column_type()
            self.expect_op(">")
            return f"FROZEN<{inner}>"
        if t in ("LIST", "SET", "MAP") and self.accept_op("<"):
            inner = [self._column_type()]
            while self.accept_op(","):
                inner.append(self._column_type())
            self.expect_op(">")
            return f"{t}<{','.join(inner)}>"
        if t in ("DECIMAL", "NUMERIC", "CHAR") and self.accept_op("("):
            params = [str(int(self.literal()))]
            while self.accept_op(","):
                params.append(str(int(self.literal())))
            self.expect_op(")")
            return f"{t}({','.join(params)})"
        return t

    def _date_literal(self):
        """`date 'YYYY-MM-DD' [(+|-) interval 'n' (day|month|year)]`, as
        TPC-H's query text has it: a datetime.date."""
        import datetime
        try:
            d = datetime.date.fromisoformat(self.next()[1][1:-1])
        except ValueError as e:
            raise ParseError(f"bad date literal: {e}")
        while self.peek() in (("op", "+"), ("op", "-")):
            sign = 1 if self.next()[1] == "+" else -1
            self.expect_kw("INTERVAL")
            tok = self.next()
            if tok[0] not in ("string", "number"):
                raise ParseError("interval needs a count")
            n = sign * int(tok[1].strip("'"))
            unit = self.name().upper().rstrip("S")
            if unit == "DAY":
                d += datetime.timedelta(days=n)
            elif unit in ("MONTH", "YEAR"):
                months = d.year * 12 + d.month - 1 + (
                    n if unit == "MONTH" else 12 * n)
                d = d.replace(year=months // 12, month=months % 12 + 1)
            else:
                raise ParseError(f"unknown interval unit {unit!r}")
            if self.accept_op("("):         # `day (3)`: a precision
                self.literal()
                self.expect_op(")")
        return d

    def literal(self):
        # collection literals: [e, ...] list, {e, ...} set, {k: v, ...} map
        nxt = self.peek()
        if nxt == ("op", "["):
            self.next()
            out = []
            if not self.accept_op("]"):
                out.append(self.literal())
                while self.accept_op(","):
                    out.append(self.literal())
                self.expect_op("]")
            return out
        if nxt == ("op", "{"):
            self.next()
            if self.accept_op("}"):
                return {}
            try:
                first = self.literal()
                if self.accept_op(":"):        # map
                    m = {first: self.literal()}
                    while self.accept_op(","):
                        k = self.literal()
                        self.expect_op(":")
                        m[k] = self.literal()
                    self.expect_op("}")
                    return m
                s = {first}                    # set
                while self.accept_op(","):
                    s.add(self.literal())
                self.expect_op("}")
                return s
            except TypeError:
                raise ParseError(
                    "set/map literal elements must be hashable scalars")
        tok = self.next()
        kind, text = tok
        if kind == "string":
            return text[1:-1].replace("''", "'")
        if kind == "number":
            return DecimalText(text) if "." in text else int(text)
        if kind == "name" and text.upper() == "DATE" \
                and self.peek() and self.peek()[0] == "string":
            return self._date_literal()
        if kind == "blob":
            return bytes.fromhex(text[2:])
        if kind == "op" and text == "?":
            return MARKER
        if kind == "name":
            u = text.upper()
            if u == "TRUE":
                return True
            if u == "FALSE":
                return False
            if u == "NULL":
                return None
        raise ParseError(f"expected literal, got {text!r}")

    # ----------------------------------------------------------- statements
    def parse(self) -> Statement:
        if self.accept_kw("CREATE", "KEYSPACE"):
            ine = self.accept_kw("IF", "NOT", "EXISTS")
            return CreateKeyspace(self.name(), ine)
        if self.accept_kw("CREATE", "TABLE"):
            return self._create_table()
        if self.accept_kw("CREATE", "INDEX"):
            return self._create_index()
        if self.accept_kw("DROP", "TABLE"):
            ife = self.accept_kw("IF", "EXISTS")
            ks, name = self.qualified_name()
            return DropTable(ks, name, ife)
        if self.accept_kw("ALTER", "TABLE"):
            ks, name = self.qualified_name()
            add, drop = [], []
            while True:
                if self.accept_kw("ADD"):
                    col = self.name()
                    add.append((col, self.name()))
                elif self.accept_kw("DROP"):
                    drop.append(self.name())
                else:
                    raise ParseError(
                        f"expected ADD or DROP, got {self.peek()}")
                if not self.accept_op(","):
                    break
            return AlterTable(ks, name, add, drop)
        if self.accept_kw("USE"):
            return UseKeyspace(self.name())
        if self.accept_kw("INSERT", "INTO"):
            return self._insert()
        if self.accept_kw("SELECT"):
            return self._select()
        if self.accept_kw("UPDATE"):
            return self._update()
        if self.accept_kw("DELETE"):
            return self._delete()
        if self.accept_kw("BEGIN", "TRANSACTION"):
            return self._transaction()
        if self.accept_kw("TRUNCATE"):
            self.accept_kw("TABLE")
            ks, name = self.qualified_name()
            return Truncate(ks, name)
        raise ParseError(f"unrecognized statement start: {self.peek()}")

    def _create_index(self) -> CreateIndex:
        """CREATE INDEX [IF NOT EXISTS] [name] ON [ks.]table (column)
        (ref: the YCQL grammar's index_stmt, ql/ptree/pt_create_index.h)."""
        ine = self.accept_kw("IF", "NOT", "EXISTS")
        index_name = None
        if not self.accept_kw("ON"):
            index_name = self.name()
            self.expect_kw("ON")
        ks, table = self.qualified_name()
        self.expect_op("(")
        columns = [self.name()]
        while self.accept_op(","):
            columns.append(self.name())
        self.expect_op(")")
        return CreateIndex(index_name, ks, table, columns, ine)

    def _create_table(self) -> CreateTable:
        ine = self.accept_kw("IF", "NOT", "EXISTS")
        ks, name = self.qualified_name()
        self.expect_op("(")
        columns: List[Tuple[str, str]] = []
        hash_keys: List[str] = []
        range_keys: List[str] = []
        while True:
            if self.accept_kw("PRIMARY", "KEY"):
                self.expect_op("(")
                if self.accept_op("("):   # ((h1, h2), r1, ...)
                    hash_keys.append(self.name())
                    while self.accept_op(","):
                        hash_keys.append(self.name())
                    self.expect_op(")")
                else:
                    hash_keys.append(self.name())
                while self.accept_op(","):
                    range_keys.append(self.name())
                self.expect_op(")")
            else:
                cname = self.name()
                ctype = self._column_type()
                columns.append((cname, ctype))
                if self.accept_kw("PRIMARY", "KEY"):
                    hash_keys.append(cname)
            if not self.accept_op(","):
                break
        self.expect_op(")")
        num_tablets = 4
        if self.accept_kw("WITH"):
            while True:
                prop = self.name().lower()
                self.expect_op("=")
                val = self.literal()
                if prop == "tablets":
                    num_tablets = int(val)
                if not self.accept_kw("AND"):
                    break
        if not hash_keys:
            raise ParseError("no PRIMARY KEY defined")
        return CreateTable(ks, name, columns, hash_keys, range_keys,
                           num_tablets, ine)

    def _peek2(self):
        return self.toks[self.pos + 1] if self.pos + 1 < len(self.toks) \
            else None

    def _func_call(self) -> FuncCall:
        fname = self.name()
        self.expect_op("(")
        if fname.upper() == "COUNT" and self.accept_op("*"):
            # COUNT(*) — the star is an aggregate-only argument form
            self.expect_op(")")
            return FuncCall(fname, ["*"])
        args: List[object] = []
        if not self.accept_op(")"):
            args.append(self._func_arg())
            while self.accept_op(","):
                args.append(self._func_arg())
            self.expect_op(")")
        return FuncCall(fname, args)

    def _func_arg(self):
        tok = self.peek()
        if tok == ("op", "("):
            return self._product(self._paren_factor())
        if tok and tok[0] == "name" and \
                tok[1].upper() not in ("TRUE", "FALSE", "NULL"):
            if self._peek2() == ("op", "("):
                return self._func_call()
            ref = ColumnRef(self.name())
            if self.peek() == ("op", "*"):
                return self._product(("col", ref.name))
            return ref
        return self.literal()

    def _paren_factor(self) -> Tuple[str, str]:
        """`(1 - col)` or `(1 + col)`."""
        self.expect_op("(")
        if self.next() != ("number", "1"):
            raise ParseError("a product factor is col, (1 - col) or "
                             "(1 + col)")
        tok = self.next()
        if tok not in (("op", "-"), ("op", "+")):
            raise ParseError(f"expected + or - in a factor, got {tok[1]!r}")
        col = self.name()
        self.expect_op(")")
        return ("1" + tok[1], col)

    def _product(self, first: Tuple[str, str]) -> Product:
        factors = [first]
        while self.accept_op("*"):
            factors.append(self._paren_factor()
                           if self.peek() == ("op", "(")
                           else ("col", self.name()))
        return Product(tuple(factors))

    def _value_expr(self):
        """literal, or a builtin call over literals — INSERT ... VALUES
        (now(), uuid(), intasblob(7), ...)."""
        tok = self.peek()
        if tok and tok[0] == "name" and self._peek2() == ("op", "(") \
                and tok[1].upper() not in ("TRUE", "FALSE", "NULL"):
            return self._func_call()
        return self.literal()

    def _insert(self) -> Insert:
        ks, table = self.qualified_name()
        self.expect_op("(")
        cols = [self.name()]
        while self.accept_op(","):
            cols.append(self.name())
        self.expect_op(")")
        self.expect_kw("VALUES")
        self.expect_op("(")
        vals = [self._value_expr()]
        while self.accept_op(","):
            vals.append(self._value_expr())
        self.expect_op(")")
        ine = self.accept_kw("IF", "NOT", "EXISTS")
        ttl = None
        if self.accept_kw("USING", "TTL"):
            ttl = int(self.literal())
        if not ine:
            ine = self.accept_kw("IF", "NOT", "EXISTS")
        if len(cols) != len(vals):
            raise ParseError(f"{len(cols)} columns but {len(vals)} values")
        return Insert(ks, table, cols, vals, ttl, bool(ine))

    def _if_conditions(self):
        """Trailing IF EXISTS / IF col op literal [AND ...] of UPDATE and
        DELETE -> (if_exists, conditions)."""
        if not self.accept_kw("IF"):
            return False, []
        if self.accept_kw("EXISTS"):
            return True, []
        conds = []
        while True:
            col = self.name()
            tok = self.next()
            if tok[0] != "op" or tok[1] not in ("=", "<", ">", "<=",
                                                ">=", "!="):
                raise ParseError(
                    f"expected comparison in IF, got {tok[1]!r}")
            conds.append((col, tok[1], self.literal()))
            if not self.accept_kw("AND"):
                return False, conds

    def _json_path(self, col: str) -> JsonOp:
        """col ->'k' ->0 ... [->>'leaf'] — ->> is terminal (it yields
        text, which has no further json structure to navigate)."""
        path: List[object] = []
        as_text = False
        while True:
            if self.accept_op("->"):
                terminal = False
            elif self.accept_op("->>"):
                terminal = True
            else:
                break
            tok = self.next()
            if tok[0] == "string":
                path.append(tok[1][1:-1].replace("''", "'"))
            elif tok[0] == "number" and "." not in tok[1]:
                path.append(int(tok[1]))
            else:
                raise ParseError(
                    f"json path operand must be a text key or an array "
                    f"index, got {tok[1]!r}")
            if terminal:
                as_text = True
                if self.peek() in (("op", "->"), ("op", "->>")):
                    raise ParseError("->> returns text: no further json "
                                     "navigation is possible")
                break
        return JsonOp(col, tuple(path), as_text)

    def _token_args(self) -> TokenRef:
        """name [, name]* ')' of a token(...) call (opening paren already
        consumed) — shared by the select-list and WHERE grammars."""
        cols = [self.name()]
        while self.accept_op(","):
            cols.append(self.name())
        self.expect_op(")")
        return TokenRef(tuple(cols))

    def _select_item(self):
        tok = self.peek()
        if tok and tok[0] == "name" and tok[1].upper() == "TOKEN" \
                and self._peek2() == ("op", "("):
            self.name()
            self.expect_op("(")
            return self._token_args()
        if tok and tok[0] == "name" and self._peek2() == ("op", "("):
            return self._func_call()
        col = self.name()
        if self.peek() in (("op", "->"), ("op", "->>")):
            return self._json_path(col)
        return col

    def _select(self) -> Select:
        distinct = bool(self.accept_kw("DISTINCT"))
        aliases: Dict[int, str] = {}
        if self.accept_op("*"):
            cols = None
        else:
            cols = []
            while True:
                cols.append(self._select_item())
                if self.accept_kw("AS"):
                    aliases[len(cols) - 1] = self.name()
                if not self.accept_op(","):
                    break
        self.expect_kw("FROM")
        ks, table = self.qualified_name()
        where = self._where() if self.accept_kw("WHERE") else []
        group_by: List[str] = []
        if self.accept_kw("GROUP", "BY"):
            group_by.append(self.name())
            while self.accept_op(","):
                group_by.append(self.name())
        order_by: List[Tuple[str, bool]] = []
        if self.accept_kw("ORDER", "BY"):
            while True:
                col = self.name()
                desc = bool(self.accept_kw("DESC"))
                if not desc:
                    self.accept_kw("ASC")
                order_by.append((col, desc))
                if not self.accept_op(","):
                    break
        limit = None
        if self.accept_kw("LIMIT"):
            limit = int(self.literal())
        self.accept_kw("ALLOW", "FILTERING")
        return Select(ks, table, cols, where, limit, order_by=order_by,
                      distinct=distinct, group_by=group_by,
                      aliases=aliases)

    def _where(self) -> List[Tuple[str, str, object]]:
        conds = []
        while True:
            col = self.name()
            if col.upper() == "TOKEN" and self.accept_op("("):
                col = self._token_args()
            elif self.peek() in (("op", "->"), ("op", "->>")):
                col = self._json_path(col)
            if self.accept_kw("IN"):
                # col IN (v1, v2, ...) — drives the discrete ScanChoices
                # strategy (ref docdb/scan_choices.cc option iteration)
                self.expect_op("(")
                vals = [self.literal()]
                while self.accept_op(","):
                    vals.append(self.literal())
                self.expect_op(")")
                conds.append((col, "in", vals))
            elif self.accept_kw("BETWEEN"):
                lo = self.literal()
                self.expect_kw("AND")
                conds.append((col, ">=", lo))
                conds.append((col, "<=", self.literal()))
            else:
                tok = self.next()
                if tok[0] != "op" or tok[1] not in ("=", "<", ">", "<=",
                                                    ">=", "!="):
                    raise ParseError(f"expected comparison, got {tok[1]!r}")
                conds.append((col, tok[1], self.literal()))
            if not self.accept_kw("AND"):
                return conds

    def _update(self) -> Update:
        ks, table = self.qualified_name()
        ttl = None
        if self.accept_kw("USING", "TTL"):
            ttl = int(self.literal())
        self.expect_kw("SET")
        assignments = []
        while True:
            col = self.name()
            if self.accept_op("["):
                # element assignment: m['k'] = v / l[i] = v
                sub = self.literal()
                self.expect_op("]")
                self.expect_op("=")
                assignments.append(((col, sub), self.literal()))
            else:
                self.expect_op("=")
                nxt = self.peek()
                if nxt == ("name", col):
                    # col = col + X (append/merge) | col = col - X (remove)
                    self.next()
                    tok = self.next()
                    if tok[0] != "op" or tok[1] not in ("+", "-"):
                        raise ParseError(
                            f"expected + or - after '{col} = {col}'")
                    tag = "__append__" if tok[1] == "+" else "__remove__"
                    assignments.append((col, (tag, self.literal())))
                else:
                    assignments.append((col, self.literal()))
            if not self.accept_op(","):
                break
        self.expect_kw("WHERE")
        where = self._where()
        ife, conds = self._if_conditions()
        return Update(ks, table, assignments, where, ttl,
                      if_exists=ife, conditions=conds)

    def _delete_target(self):
        col = self.name()
        if self.accept_op("["):
            sub = self.literal()
            self.expect_op("]")
            return (col, sub)
        return col

    def _delete(self) -> Delete:
        cols = None
        if not (self.peek() and self.peek()[0] == "name"
                and self.peek()[1].upper() == "FROM"):
            cols = [self._delete_target()]
            while self.accept_op(","):
                cols.append(self._delete_target())
        self.expect_kw("FROM")
        ks, table = self.qualified_name()
        self.expect_kw("WHERE")
        where = self._where()
        ife, conds = self._if_conditions()
        return Delete(ks, table, where, cols,
                      if_exists=ife, conditions=conds)

    def _transaction(self) -> Transaction:
        stmts: List[Union[Insert, Update, Delete]] = []
        while True:
            if self.accept_kw("END", "TRANSACTION"):
                break
            if self.accept_op(";"):
                continue
            if self.accept_kw("INSERT", "INTO"):
                stmts.append(self._insert())
            elif self.accept_kw("UPDATE"):
                stmts.append(self._update())
            elif self.accept_kw("DELETE"):
                stmts.append(self._delete())
            else:
                raise ParseError(
                    f"only DML allowed in transactions, got {self.peek()}")
        return Transaction(stmts)


def parse(text: str) -> Statement:
    p = Parser(text)
    stmt = p.parse()
    p.accept_op(";")
    if p.peek() is not None:
        raise ParseError(f"trailing tokens: {p.peek()}")
    return stmt
