"""The plain references on hand-made cases."""

import numpy as np

from benchmarks import reference
from benchmarks.datagen import KIND_F, KIND_N, KIND_TOMB


class Gen:
    """Stands in for Kv64Runs.full_keys: key = [id, kind] as two bytes."""

    @staticmethod
    def full_keys(ids, kind):
        keys = np.stack([ids, np.where(kind == KIND_TOMB, 0, kind + 1)],
                        axis=1).astype(np.uint8)
        return keys, np.where(kind == KIND_TOMB, 1, 2)


def run(rows):
    ids, kind, ht, val = (np.asarray(c) for c in zip(*rows))
    vals = np.zeros((len(rows), 2), dtype=np.uint8)
    vals[:, 0] = val
    return {"ids": ids.astype(np.int64), "kind": kind.astype(np.int8),
            "ht": ht.astype(np.uint64), "vals": vals,
            "val_len": np.full(len(rows), 1)}


def test_major_compaction_semantics():
    runs = [run([(5, KIND_F, 10, 1), (5, KIND_N, 11, 2), (7, KIND_F, 12, 3),
                 (9, KIND_F, 13, 4)]),
            run([(5, KIND_F, 20, 5),          # overwrites ht 10
                 (7, KIND_TOMB, 21, 0),       # hides 7/f at 12
                 (9, KIND_TOMB, 5, 0),        # older than 9/f at 13
                 (3, KIND_TOMB, 22, 0)])]     # tombstone alone: dropped
    out = reference.major_compaction_survivors(Gen, runs)
    assert out["rows_in"] == 8 and out["rows_out"] == 3
    assert out["keys"].tolist() == [[5, 1], [5, 2], [9, 1]]
    assert out["ht"].tolist() == [20, 11, 13]
    assert out["vals"][:, 0].tolist() == [5, 2, 4]


def as_got(out, **change):
    got = {"n": out["rows_out"], "keys": out["keys"],
           "key_len": out["key_len"], "ht": out["ht"],
           "val_len": out["val_len"],
           "val_data": out["vals"][:, 0].copy(),
           "val_offs": np.arange(out["rows_out"] + 1)}
    got.update(change)
    return got


def test_row_mismatches_are_counted():
    out = reference.major_compaction_survivors(Gen, [run(
        [(1, KIND_F, 10, 1), (2, KIND_F, 11, 2), (3, KIND_N, 12, 3)])])
    assert reference.count_row_mismatches(out, as_got(out)) == 0
    bad = out["vals"][:, 0].copy()
    bad[1] ^= 1
    assert reference.count_row_mismatches(out, as_got(out, val_data=bad)) == 1
    ht = out["ht"].copy()
    ht[0] += 1
    assert reference.count_row_mismatches(out, as_got(out, ht=ht)) == 1
    assert reference.count_row_mismatches(out, as_got(out, n=2)) == 3


def test_field_history_admissibility():
    h = reference.FieldHistory(1)
    h.load(0, ["init"])
    h.wrote(0, 0, "a", 1.0, 2.0)
    h.wrote(0, 0, "b", 3.0, 4.0)
    h.wrote(0, 0, "c", 3.5, 4.5)        # concurrent with b
    assert h.admissible(0, 0, "init", 0.0, 0.5)
    assert h.admissible(0, 0, "init", 1.5, 1.8)      # a not yet acked
    assert not h.admissible(0, 0, "init", 2.5, 2.6)  # a had landed
    assert h.admissible(0, 0, "a", 2.5, 2.6)
    assert not h.admissible(0, 0, "b", 2.5, 2.6)     # from the future
    assert not h.admissible(0, 0, "a", 5.0, 5.1)     # b, c had landed
    assert h.admissible(0, 0, "b", 5.0, 5.1)         # b || c: either may win
    assert h.admissible(0, 0, "c", 5.0, 5.1)
    assert not h.admissible(0, 0, "zzz", 5.0, 5.1)
    assert not h.admissible(0, 0, None, 5.0, 5.1)
    assert h.final_values(0, 0) == {"b", "c"}
    h.wrote(0, 0, "d", 6.0, 7.0)
    assert h.final_values(0, 0) == {"d"}


def test_field_history_write_given_up_by_the_client():
    """A write whose outcome the client does not know replaces nothing for
    certain, is admissible from its start on, and may be the final value;
    what was acknowledged is held as strictly as before."""
    h = reference.FieldHistory(1)
    h.load(0, ["init"])
    h.wrote(0, 0, "a", 1.0, 2.0)
    h.gave_up(0, 0, "u", 3.0)
    assert h.admissible(0, 0, "a", 5.0, 5.1)         # u may never have landed
    assert h.admissible(0, 0, "u", 5.0, 5.1)
    assert not h.admissible(0, 0, "u", 2.0, 2.5)     # from the future
    assert not h.admissible(0, 0, "init", 5.0, 5.1)  # a had landed
    assert h.final_values(0, 0) == {"a", "u"}
    h.wrote(0, 0, "b", 6.0, 7.0)
    assert not h.admissible(0, 0, "a", 8.0, 8.1)     # b had landed
    assert h.admissible(0, 0, "u", 8.0, 8.1)         # u may land after b
    assert h.final_values(0, 0) == {"b", "u"}
    h.gave_up(1, 0, "v", 1.0)
    h.load(1, ["init1"])
    assert h.final_values(1, 0) == {"init1", "v"}
