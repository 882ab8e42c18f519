"""Seeded TPC-H `lineitem` (TPC Benchmark H rev 3.0, clauses 1.4.1 and
4.2.3), its refresh sets (clauses 2.5-2.7: RF1 inserts SF x 1,500 new
orders, RF2 deletes as many old ones) and the substitution parameters of Q1
and Q6 (clauses 2.4.1.3, 2.4.6.3), in numpy. No dbgen, nothing downloaded.

Every column has the specification's type and domain. Stored forms are the
program's (DECIMAL(15,2) as the unscaled integer, DATE as days since
1970-01-01, CHAR / VARCHAR as text), so the same arrays feed the bulk
import, the client-path refresh and `reference_tpch.py`.

What is not the specification's, to the letter (the configuration file
lists these under `assumed`): `l_comment` is random lower-case words at the
specification's lengths (10..43 characters), not the output of dbgen's text
grammar; RF2 deletes the orders with the lowest keys (dbgen reads them from
its delete files, which hold the lowest keys too); the random streams are
numpy's, not dbgen's, so no row equals dbgen's row.

Nothing here reads the clock: the same seed gives the same rows.
"""

import datetime

import numpy as np

from benchmarks.datagen import rng_for

_EPOCH = datetime.date(1970, 1, 1)


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - _EPOCH).days


STARTDATE = days(1992, 1, 1)
ENDDATE = days(1998, 12, 31)
CURRENTDATE = days(1995, 6, 17)
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
_WORDS = ("furiously", "carefully", "quickly", "slyly", "blithely", "final",
          "ironic", "regular", "express", "special", "pending", "bold",
          "deposits", "requests", "accounts", "packages", "theodolites",
          "instructions", "foxes", "pinto", "beans", "dependencies",
          "sleep", "nag", "haggle", "cajole", "boost", "wake", "above",
          "across", "after", "the", "along")

# (name, CQL type) in the specification's column order; the primary key is
# (l_orderkey hash, l_linenumber range)
COLUMNS = (
    ("l_orderkey", "bigint"), ("l_partkey", "bigint"),
    ("l_suppkey", "bigint"), ("l_linenumber", "int"),
    ("l_quantity", "decimal(15,2)"), ("l_extendedprice", "decimal(15,2)"),
    ("l_discount", "decimal(15,2)"), ("l_tax", "decimal(15,2)"),
    ("l_returnflag", "char(1)"), ("l_linestatus", "char(1)"),
    ("l_shipdate", "date"), ("l_commitdate", "date"),
    ("l_receiptdate", "date"), ("l_shipinstruct", "char(25)"),
    ("l_shipmode", "char(10)"), ("l_comment", "varchar"))
TEXT_COLUMNS = ("l_returnflag", "l_linestatus", "l_shipinstruct",
                "l_shipmode", "l_comment")


def create_table_cql(keyspace: str, table: str, tablets: int) -> str:
    cols = ", ".join(f"{n} {t}" for n, t in COLUMNS)
    return (f"CREATE TABLE {keyspace}.{table} ({cols}, PRIMARY KEY "
            f"((l_orderkey), l_linenumber)) WITH tablets = {tablets}")


def sparse_orderkeys(index: np.ndarray, second_eight: bool = False):
    """Clause 4.2.3: only the first 8 of every 32 keys are populated; the
    refresh inserts take the next 8, between the existing ones."""
    index = np.asarray(index, dtype=np.int64)
    return (index // 8) * 32 + index % 8 + 1 + (8 if second_eight else 0)


class Lineitem:
    def __init__(self, seed: int, scale_factor: float):
        self.seed = seed
        self.sf = float(scale_factor)
        self.n_orders = int(round(1_500_000 * self.sf))
        self.n_parts = max(1, int(round(200_000 * self.sf)))
        self.n_supps = max(1, int(round(10_000 * self.sf)))
        self.n_refresh = max(1, int(round(1_500 * self.sf)))

    def _lines(self, rng, orderkeys: np.ndarray) -> dict:
        n_orders = len(orderkeys)
        per_order = rng.integers(1, 8, size=n_orders)
        n = int(per_order.sum())
        order_of = np.repeat(np.arange(n_orders), per_order)
        first = np.cumsum(per_order) - per_order
        linenumber = np.arange(n) - first[order_of] + 1
        orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1,
                                 size=n_orders)[order_of]
        partkey = rng.integers(1, self.n_parts + 1, size=n)
        s = self.n_supps
        suppkey = (partkey + rng.integers(0, 4, size=n)
                   * (s // 4 + (partkey - 1) // s)) % s + 1
        quantity = rng.integers(1, 51, size=n)
        retail_cents = (90000 + (partkey // 10) % 20001
                        + 100 * (partkey % 1000))
        shipdate = orderdate + rng.integers(1, 122, size=n)
        commitdate = orderdate + rng.integers(30, 91, size=n)
        receiptdate = shipdate + rng.integers(1, 31, size=n)
        returned = np.where(rng.random(n) < 0.5, "R", "A")
        n_words = rng.integers(2, 7, size=n)
        words = np.asarray(_WORDS, dtype=object)[
            rng.integers(0, len(_WORDS), size=int(n_words.sum()))]
        ends = np.cumsum(n_words)
        comment_len = rng.integers(10, 44, size=n)
        comments = []
        for a, b, ln in zip((ends - n_words).tolist(), ends.tolist(),
                            comment_len.tolist()):
            text = " ".join(words[a:b])
            comments.append((text + " " + text)[:ln].rstrip() or "final")
        return {
            "l_orderkey": orderkeys[order_of].astype(np.int64),
            "l_partkey": partkey.astype(np.int64),
            "l_suppkey": suppkey.astype(np.int64),
            "l_linenumber": linenumber.astype(np.int64),
            "l_quantity": (quantity * 100).astype(np.int64),
            "l_extendedprice": (quantity * retail_cents).astype(np.int64),
            "l_discount": rng.integers(0, 11, size=n).astype(np.int64),
            "l_tax": rng.integers(0, 9, size=n).astype(np.int64),
            "l_returnflag": np.where(receiptdate <= CURRENTDATE, returned,
                                     "N").tolist(),
            "l_linestatus": np.where(shipdate > CURRENTDATE, "O",
                                     "F").tolist(),
            "l_shipdate": shipdate.astype(np.int64),
            "l_commitdate": commitdate.astype(np.int64),
            "l_receiptdate": receiptdate.astype(np.int64),
            "l_shipinstruct": np.asarray(INSTRUCTIONS, dtype=object)[
                rng.integers(0, 4, size=n)].tolist(),
            "l_shipmode": np.asarray(MODES, dtype=object)[
                rng.integers(0, 7, size=n)].tolist(),
            "l_comment": comments,
        }

    def initial(self) -> dict:
        """The population: name -> int64 array or list of str."""
        return self._lines(rng_for(self.seed, 21),
                           sparse_orderkeys(np.arange(self.n_orders)))

    def rf1(self) -> dict:
        """The new orders' lines (keys between the existing ones)."""
        return self._lines(
            rng_for(self.seed, 22),
            sparse_orderkeys(np.arange(self.n_refresh), second_eight=True))

    def rf2_orderkeys(self) -> np.ndarray:
        """The orders RF2 deletes: the lowest keys of the population."""
        return sparse_orderkeys(np.arange(self.n_refresh))


def rows_of(columns: dict, keep: np.ndarray) -> dict:
    keep = np.asarray(keep)
    idx = np.flatnonzero(keep) if keep.dtype == bool else keep
    return {name: (col[idx] if isinstance(col, np.ndarray)
                   else [col[i] for i in idx.tolist()])
            for name, col in columns.items()}


def concat(a: dict, b: dict) -> dict:
    return {name: (np.concatenate([a[name], b[name]])
                   if isinstance(a[name], np.ndarray)
                   else list(a[name]) + list(b[name])) for name in a}


# -------------------------------------------------- substitution parameters

def q1_params(rng) -> dict:
    """Clause 2.4.1.3: DELTA uniform in [60, 120]."""
    return {"delta": int(rng.integers(60, 121))}


def q6_params(rng) -> dict:
    """Clause 2.4.6.3: DATE the first of January of a year in [1993,
    1997], DISCOUNT in [0.02, 0.09], QUANTITY 24 or 25."""
    return {"year": int(rng.integers(1993, 1998)),
            "discount": int(rng.integers(2, 10)),
            "quantity": int(rng.integers(24, 26))}


Q1_TEXT = """select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
 sum(l_extendedprice) as sum_base_price,
 sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
 sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
 avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
 avg(l_discount) as avg_disc, count(*) as count_order
 from {table} where l_shipdate <= date '1998-12-01' - interval '{delta}' day (3)
 group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""

Q6_TEXT = """select sum(l_extendedprice * l_discount) as revenue from {table}
 where l_shipdate >= date '{year}-01-01'
 and l_shipdate < date '{year}-01-01' + interval '1' year
 and l_discount between {lo} and {hi} and l_quantity < {quantity}"""


def q1_statement(table: str, p: dict) -> str:
    return Q1_TEXT.format(table=table, delta=p["delta"])


def q6_statement(table: str, p: dict) -> str:
    d = p["discount"]
    return Q6_TEXT.format(table=table, year=p["year"],
                          lo="0.%02d" % (d - 1), hi="0.%02d" % (d + 1),
                          quantity=p["quantity"])
