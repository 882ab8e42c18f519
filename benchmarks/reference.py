"""The plain references that decide `correct`. They import nothing of the
program and take nothing it has made: each is computed from the seeded data
alone, in plain numpy / Python.

Compaction: what a major compaction of DocDB runs must leave when the history
cutoff lies above every write (docdb's MVCC GC, DocDB compaction filter):
  * of the writes of one (row, column), only the newest survives;
  * a row tombstone hides every column write of its row that is older than it;
  * the tombstones themselves are dropped (major compaction, nothing below).
Survivors are ordered by key bytes.

YCSB: a per-(record, field) history of acknowledged writes. A value a read
returns is admissible if no other write to that field had completed before
the read began and started after the returned write was acknowledged (the
weakest rule a linearizable store can never break, so concurrent writers to
one hot key cannot fail a sound run).
"""

import numpy as np

from benchmarks.datagen import KIND_TOMB


# ------------------------------------------------------------ compaction

def major_compaction_survivors(gen, runs: list) -> dict:
    """Expected output of a major compaction over `runs` (dicts of
    datagen.Kv64Runs.run). Returns zero-padded key matrix, key lengths,
    hybrid times and the value bytes, in output order."""
    ids = np.concatenate([r["ids"] for r in runs])
    kind = np.concatenate([r["kind"] for r in runs])
    ht = np.concatenate([r["ht"] for r in runs])
    vals = np.concatenate([r["vals"] for r in runs])
    val_len = np.concatenate([r["val_len"] for r in runs])
    if len(np.unique(ht)) != len(ht):
        raise RuntimeError("reference: hybrid times are not unique")
    is_tomb = kind == KIND_TOMB
    tomb_ht = np.zeros(int(ids.max()) + 1, dtype=np.uint64)
    np.maximum.at(tomb_ht, ids[is_tomb], ht[is_tomb])
    w = np.flatnonzero(~is_tomb)
    group = ids[w] * 2 + kind[w]
    order = np.lexsort((ht[w], group))            # by group, then time
    last = np.ones(len(order), dtype=bool)
    last[:-1] = group[order][1:] != group[order][:-1]
    newest = w[order[last]]                       # sorted by (id, kind)
    alive = newest[ht[newest] > tomb_ht[ids[newest]]]
    keys, key_len = gen.full_keys(ids[alive], kind[alive])
    by_key = np.lexsort(keys.T[::-1])             # byte-wise key order
    alive = alive[by_key]
    return {"keys": keys[by_key], "key_len": key_len[by_key],
            "ht": ht[alive], "vals": vals[alive], "val_len": val_len[alive],
            "rows_in": int(len(ids)), "rows_out": int(len(alive))}


def count_row_mismatches(expect: dict, got: dict) -> int:
    """Rows of the output that differ from the reference in key, hybrid time
    or value; a differing row count counts every row."""
    n = expect["rows_out"]
    if got["n"] != n:
        return max(n, got["n"])
    w = min(expect["keys"].shape[1], got["keys"].shape[1])
    bad = (expect["key_len"] != got["key_len"]) | (expect["ht"] != got["ht"])
    bad |= (expect["keys"][:, :w] != got["keys"][:, :w]).any(axis=1)
    bad |= expect["keys"][:, w:].any(axis=1) | got["keys"][:, w:].any(axis=1)
    bad |= expect["val_len"] != got["val_len"]
    vw = expect["vals"].shape[1]
    mask = np.arange(vw)[None, :] < expect["val_len"][:, None]
    gv = np.zeros_like(expect["vals"])
    ok_len = ~bad
    # scatter got's ragged values into the padded layout, rows whose length
    # already differs stay bad
    lens = np.where(ok_len, got["val_len"], 0)
    rows = np.repeat(np.arange(n), lens)
    cols = np.arange(int(lens.sum())) - np.repeat(
        np.cumsum(lens) - lens, lens)
    src = np.repeat(got["val_offs"][:-1], lens) + cols
    gv[rows, cols] = got["val_data"][src]
    bad |= ((expect["vals"] != gv) & mask).any(axis=1)
    return int(bad.sum())


# ------------------------------------------------------------------ YCSB

class FieldHistory:
    """Writes to every (record, field), with the initial load as write 0.

    A write is acknowledged (it landed between its start and its end) or
    given up by the client with its outcome unknown: such a write may have
    landed, may land later (a raft entry can commit after its sender timed
    out) or never. Its value is admissible from its start on, it replaces
    nothing for certain, and it may be what the field holds at the end."""

    def __init__(self, n_fields: int):
        self.n_fields = n_fields
        self.initial = {}          # record -> list of field values
        self.writes = {}           # (record, field) -> {value: (start, end)}
        self.unknown = {}          # (record, field) -> {value: start}

    def load(self, record: int, values: list) -> None:
        self.initial[record] = values

    def wrote(self, record: int, field: int, value: str,
              start: float, end: float) -> None:
        self.writes.setdefault((record, field), {})[value] = (start, end)

    def gave_up(self, record: int, field: int, value: str,
                start: float) -> None:
        self.unknown.setdefault((record, field), {})[value] = start

    def admissible(self, record: int, field: int, value, read_start: float,
                   read_end: float) -> bool:
        ws = self.writes.get((record, field), {})
        maybe = self.unknown.get((record, field), {})
        if value in maybe:
            return maybe[value] <= read_end        # else: from the future
        if value == self.initial[record][field]:
            mine_end = float("-inf")
        elif value in ws:
            start, mine_end = ws[value]
            if start > read_end:
                return False                       # read from the future
        else:
            return False                           # nobody wrote this
        for other, (s, e) in ws.items():
            if other != value and s > mine_end and e < read_start:
                return False                       # a later write had landed
        return True

    def final_values(self, record: int, field: int) -> set:
        """Values the field may hold once every write has settled."""
        ws = self.writes.get((record, field))
        maybe = set(self.unknown.get((record, field), ()))
        if not ws:
            return maybe | {self.initial[record][field]}
        latest_start = max(s for s, _e in ws.values())
        return maybe | {v for v, (_s, e) in ws.items() if e >= latest_start}
