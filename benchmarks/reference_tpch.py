"""Plain reference for TPC-H Q1 and Q6 over `lineitem` rows held as arrays:
numpy and Python INTEGERS only, importing nothing of the program (and
nothing of the generator but the rows it is handed). A DECIMAL(15,2) is its
unscaled integer, a DATE its days since 1970-01-01; sums are Python ints,
AVG is (sum, count) divided once with `decimal` at the default context,
which is what the system's client does with its exact (sum, count).

The rows are those a query of the window sees: the population, less RF2's
orders, plus RF1's, as the acknowledged history has them.
"""

import datetime
import decimal

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - _EPOCH).days


def apply_refresh(population: dict, inserted: dict,
                  deleted_orderkeys) -> dict:
    """The table after RF1 and RF2: name -> array (text columns as object
    arrays)."""
    gone = np.isin(population["l_orderkey"],
                   np.asarray(list(deleted_orderkeys), dtype=np.int64))
    out = {}
    for name, col in population.items():
        a = np.asarray(col, dtype=object if isinstance(col, list) else None)
        b = np.asarray(inserted[name],
                       dtype=object if isinstance(col, list) else None)
        out[name] = np.concatenate([a[~gone], b])
    return out


def _isum(a) -> int:
    """Exact: int64 partial sums cannot wrap below 2^62, checked."""
    a = np.asarray(a, dtype=np.int64)
    if len(a) and int(np.abs(a).max()) * len(a) >= 1 << 62:
        return sum(int(x) for x in a)
    return int(a.sum())


def q1_raw(rows: dict, delta: int) -> dict:
    """(returnflag, linestatus) -> {"rows", "sums": [sum_qty, sum_price,
    sum_disc_price (scale 4), sum_charge (scale 6), sum_discount]}."""
    sel = rows["l_shipdate"] <= _days(1998, 12, 1) - int(delta)
    qty = rows["l_quantity"][sel].astype(np.int64)
    price = rows["l_extendedprice"][sel].astype(np.int64)
    disc = rows["l_discount"][sel].astype(np.int64)
    tax = rows["l_tax"][sel].astype(np.int64)
    rf = rows["l_returnflag"][sel]
    ls = rows["l_linestatus"][sel]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    out = {}
    for key in sorted(set(zip(rf.tolist(), ls.tolist()))):
        m = (rf == key[0]) & (ls == key[1])
        out[key] = {"rows": int(m.sum()),
                    "sums": [_isum(qty[m]), _isum(price[m]),
                             _isum(disc_price[m]), _isum(charge[m]),
                             _isum(disc[m])]}
    return out


def q6_raw(rows: dict, year: int, discount: int, quantity: int) -> dict:
    """{"rows", "sum"}: sum of price x discount (scale 4) over the rows of
    the year with discount within 0.01 of DISCOUNT and quantity under
    QUANTITY."""
    ship = rows["l_shipdate"]
    disc = rows["l_discount"].astype(np.int64)
    sel = ((ship >= _days(year, 1, 1)) & (ship < _days(year + 1, 1, 1))
           & (disc >= discount - 1) & (disc <= discount + 1)
           & (rows["l_quantity"] < quantity * 100))
    return {"rows": int(sel.sum()),
            "sum": _isum(rows["l_extendedprice"][sel].astype(np.int64)
                         * disc[sel])}


def _dec(v: int, scale: int) -> decimal.Decimal:
    return decimal.Decimal(v).scaleb(-scale)


def _avg(total: int, count: int, scale: int) -> decimal.Decimal:
    return (decimal.Decimal(total) / decimal.Decimal(count)).scaleb(-scale)


def q1_rows(raw: dict) -> list:
    """Q1's result rows as the statement lists them, ordered by the group
    columns."""
    out = []
    for (rf, ls), g in sorted(raw.items()):
        qty, price, disc_price, charge, disc = g["sums"]
        n = g["rows"]
        out.append([rf, ls, _dec(qty, 2), _dec(price, 2),
                    _dec(disc_price, 4), _dec(charge, 6),
                    _avg(qty, n, 2), _avg(price, n, 2), _avg(disc, n, 2),
                    n])
    return out


def q6_rows(raw: dict) -> list:
    return [[_dec(raw["sum"], 4) if raw["rows"] else None]]
