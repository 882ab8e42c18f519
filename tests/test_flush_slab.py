"""The slab built from the native encoder's columns
(native_engine.slab_from_packed -> NativeCompactionJob.export_slab) against
`pack_kvs`, the per-entry Python packer that is its oracle: every column,
the width and the values, for every key and value kind the store writes.
A flush stages this slab in the device cache and a scan reads a native
memtable through it, so one disagreement is a wrong answer on the device."""

import random
import struct

import numpy as np
import pytest

from yugabyte_tpu.common.hybrid_time import DocHybridTime, HybridTime
from yugabyte_tpu.docdb.doc_key import DocKey, SubDocKey
from yugabyte_tpu.docdb.doc_operations import column_key_suffix
from yugabyte_tpu.docdb.intents import (IntentType, encode_intent_key,
                                        reverse_index_key)
from yugabyte_tpu.docdb.value import Value
from yugabyte_tpu.ops.slabs import (FLAG_DEEP, FLAG_HAS_TTL,
                                    FLAG_OBJECT_INIT, FLAG_TOMBSTONE,
                                    pack_kvs)
from yugabyte_tpu.storage import native_engine
from yugabyte_tpu.storage.memtable import (MemTable, NativeMemTable,
                                           packed_triples)

COLUMNS = ("key_words", "key_len", "doc_key_len", "ht_hi", "ht_lo",
           "write_id", "flags", "ttl_ms", "value_idx")


def assert_slabs_equal(got, want):
    assert got.n == want.n and got.width_words == want.width_words
    for name in COLUMNS:
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
        assert a.shape == b.shape, (name, a.shape, b.shape)
        assert np.array_equal(a, b), name
    assert got.values == want.values
    assert list(got.values) == list(want.values)


def _kv64_key(uid, column=None):
    root = DocKey(range_components=("user%08d" % uid,)).encode()
    return root if column is None else root + column_key_suffix(column)


def _entries(keys_values, ht0=1000):
    """(key, ht, wid, value) with distinct hybrid times, in the order
    given."""
    return [(k, (ht0 + i) << 12, i % 3, v)
            for i, (k, v) in enumerate(keys_values)]


# no key here is another's prefix followed by a byte under '#': there the
# memtable's order (whole internal keys) and the encoder's (key, then
# length) part ways, and no DocDB key is so (every component tag is above
# kHybridTime; reverse-index keys have one length)
_TXN = bytes(range(0x40, 0x50))
_F = Value(primitive="x" * 41).encode()
_DEEP = SubDocKey(DocKey(hash_components=("h",), range_components=(3,)),
                  (("col", 2), "map_key", 7)).encode(include_ht=False)

CASES = {
    "kv64_row_tombstone": _entries(
        [(_kv64_key(u), Value.tombstone().encode()) for u in (5, 1, 9)]),
    "kv64_int64_column": _entries(
        [(_kv64_key(u, 2), Value(primitive=u * 31337).encode())
         for u in (5, 1, 9)]),
    "kv64_string_column": _entries(
        [(_kv64_key(u, 1), _F) for u in (5, 1, 9)]),
    "hashed_doc_key": _entries(
        [(DocKey(hash_components=("a%d" % i, i),
                 range_components=("r", i)).encode() + column_key_suffix(1),
          Value(primitive=i).encode()) for i in range(4)]),
    "range_only_doc_key": _entries(
        [(DocKey(range_components=(i, "a\x00b!", 2.5)).encode()
          + column_key_suffix(3), Value(primitive="v").encode())
         for i in range(4)]),
    "deep_document": _entries(
        [(_DEEP, Value(primitive=1).encode()),
         (_DEEP[:-9], Value(is_object=True).encode())]),
    "value_with_ttl": _entries(
        [(_kv64_key(1, 1), Value(primitive="t", ttl_ms=86_400_000).encode()),
         (_kv64_key(2, 1), Value(is_tombstone=True, ttl_ms=5).encode())]),
    "value_with_merge_flags": _entries(
        [(_kv64_key(1, 1), Value(primitive=1, merge_flags=1).encode()),
         (_kv64_key(2, 1),
          Value(primitive=2, merge_flags=1, ttl_ms=7000).encode())]),
    "object_marker": _entries(
        [(_kv64_key(1, 1), Value(is_object=True).encode())]),
    "intents_reverse_index_key": _entries(
        [(reverse_index_key(_TXN, s),
          encode_intent_key(_kv64_key(s, 1), IntentType.kStrongWrite))
         for s in range(3)]),
    "intents_primary_key": _entries(
        [(encode_intent_key(_kv64_key(1, 1), IntentType.kStrongWrite),
          b"x" + _TXN + struct.pack(">H", 2) + b"st" + b"w"
          + struct.pack(">I", 0) + _F)]),
    "key_of_1_byte": _entries([(b"!", b"$"), (b"x", b"$")]),
    "key_of_4_bytes": _entries([(b"$FT!", b"$"), (b"x123", b"$")]),
    "key_of_5_bytes": _entries([(b"$$FT!", b"$"), (b"x1234", b"$")]),
    "empty_run": [],
    "single_entry": _entries([(_kv64_key(7, 2), Value(primitive=7).encode())]),
}
CASES["versions_of_one_key"] = [
    (_kv64_key(1, 1), ht << 12, wid, Value(primitive=ht * 10 + wid).encode())
    for ht, wid in ((5, 0), (9, 2), (9, 0), (9, 1), (7, 0))]
_everything = [e for name in sorted(CASES) for e in CASES[name]]
random.Random(3).shuffle(_everything)
# distinct hybrid times across the cases' entries (each case numbers its own)
CASES["unsorted_run_of_every_kind"] = [
    (k, (2000 + i) << 12, w, v) for i, (k, _ht, w, v) in enumerate(_everything)]


def _packed(entries):
    keys = [e[0] for e in entries]
    vals = [e[3] for e in entries]
    offs = lambda parts: np.concatenate(  # noqa: E731
        [[0], np.cumsum([len(p) for p in parts])]).astype(np.int64)
    return (b"".join(keys), offs(keys),
            np.asarray([e[1] for e in entries], dtype=np.uint64),
            np.asarray([e[2] for e in entries], dtype=np.uint32),
            b"".join(vals), offs(vals))


def _oracle(entries):
    """pack_kvs over the entries in internal-key order (key ascending,
    hybrid time then write id descending)."""
    ordered = sorted(entries, key=lambda e: (e[0], -e[1], -e[2]))
    return pack_kvs([(k, (ht << 32) | wid, v) for k, ht, wid, v in ordered])


@pytest.mark.requires_native("compaction_engine")
@pytest.mark.parametrize("case", sorted(CASES))
def test_native_columns_equal_pack_kvs(case):
    entries = CASES[case]
    got = native_engine.slab_from_packed(*_packed(entries))
    assert_slabs_equal(got, _oracle(entries))


@pytest.mark.requires_native("compaction_engine")
def test_the_cases_carry_the_flags_they_are_named_for():
    """The equality above would hold for two parsers that both saw
    nothing: the oracle's flags are what the cases say they are."""
    def flags(case):
        return _oracle(CASES[case]).flags
    assert (flags("kv64_row_tombstone") == FLAG_TOMBSTONE).all()
    assert (flags("object_marker") == FLAG_OBJECT_INIT).all()
    assert (flags("value_with_ttl") & FLAG_HAS_TTL).all()
    assert flags("value_with_merge_flags").tolist() == [0, FLAG_HAS_TTL]
    assert flags("deep_document").tolist() == [FLAG_OBJECT_INIT | FLAG_DEEP,
                                               FLAG_DEEP]
    assert not (flags("kv64_string_column") & FLAG_DEEP).any()
    rev = _oracle(CASES["intents_reverse_index_key"])
    assert (rev.doc_key_len == rev.key_len).all()
    widths = [_oracle(CASES["key_of_%d_byte%s" % (n, "s" * (n > 1))]
                      ).width_words for n in (1, 4, 5)]
    assert widths == [1, 1, 2]
    hashed = _oracle(CASES["hashed_doc_key"])
    assert (hashed.doc_key_len < hashed.key_len).all()


@pytest.mark.requires_native("compaction_engine")
def test_an_unsorted_run_comes_back_in_key_order_with_its_values():
    entries = CASES["unsorted_run_of_every_kind"]
    got = native_engine.slab_from_packed(*_packed(entries))
    ordered = sorted(entries, key=lambda e: (e[0], -e[1], -e[2]))
    assert ordered != entries
    assert [got.key_bytes(i) for i in range(got.n)] == \
        [e[0] for e in ordered]
    assert list(got.values) == [e[3] for e in ordered]


@pytest.mark.requires_native("compaction_engine")
def test_the_job_that_writes_the_file_hands_out_its_slab(tmp_path):
    """write_sst_from_packed's on_job: the open job, once, after the file
    is written; its slab is the written file's, and what comes back is the
    props, with or without the hook."""
    from yugabyte_tpu.storage.sst import SSTReader, write_sst_from_packed
    entries = CASES["unsorted_run_of_every_kind"]
    slabs = []
    props = write_sst_from_packed(
        str(tmp_path / "a.sst"), *_packed(entries),
        on_job=lambda job: slabs.append(job.export_slab()))
    plain = write_sst_from_packed(str(tmp_path / "b.sst"), *_packed(entries))
    assert props.n_entries == plain.n_entries == len(entries)
    (slab,) = slabs
    rdr = SSTReader(str(tmp_path / "a.sst"))
    try:
        assert_slabs_equal(slab, rdr.read_all())
    finally:
        rdr.close()


@pytest.mark.requires_native("memtable_arena")
@pytest.mark.requires_native("compaction_engine")
@pytest.mark.parametrize("case", sorted(CASES))
def test_native_memtable_slab_equals_python_memtable_slab(case):
    py, nat = MemTable(), NativeMemTable()
    items = [(k, DocHybridTime(HybridTime(ht), wid), v)
             for k, ht, wid, v in CASES[case]]
    if items:
        py.add_batch(items)
        nat.add_batch(items)
    assert_slabs_equal(nat.to_slab(), py.to_slab())


@pytest.mark.requires_native("memtable_arena")
def test_native_memtable_without_the_engine_packs_entry_by_entry(monkeypatch):
    """Where the arena built and the compaction engine did not, to_slab is
    `pack_kvs` over the arena's export: the same slab."""
    nat = NativeMemTable()
    nat.add_batch([(k, DocHybridTime(HybridTime(ht), wid), v)
                   for k, ht, wid, v in CASES["unsorted_run_of_every_kind"]])
    want = pack_kvs(packed_triples(*nat.to_packed()))
    monkeypatch.setattr(native_engine, "available", lambda: False)
    monkeypatch.setattr(native_engine, "slab_from_packed", None)
    assert_slabs_equal(nat.to_slab(), want)
