"""The least bytes a pushed-down aggregate query must move, computed from
shapes, in `benchmarks/roofline.py`'s style (a BYTES bound: the kernel
compares, selects and adds; it multiplies no matrix that matters).

A query reads every staged entry of the table's leaders once: the columns
matrix (the eight fixed rows of ops/merge_gc.py's layout and the key words)
and the value-word matrix (the payload length and three payload words,
ops/scan.py). What it writes back is a few hundred bytes a tablet and is
left out. The share is defined on the data, not on a kernel's name.
"""

COLS_FIXED_ROWS = 8         # key_len, doc_key_len, ht x2, write id, flags, ttl x2
VALS_ROWS = 4               # payload length + three payload words


def entry_bytes(key_bytes: int) -> int:
    """One staged entry: its column of the cols matrix (keys padded to
    whole 4-byte words) and of the vals matrix."""
    return (COLS_FIXED_ROWS + -(-key_bytes // 4) + VALS_ROWS) * 4


def scan_query_bytes(entries: int, key_bytes: int) -> int:
    return entries * entry_bytes(key_bytes)
