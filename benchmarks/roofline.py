"""The least work a cell's device path must do, computed from shapes. Kept
with the benchmark so that no PR that claims a gain can change it.

Every bound here is a BYTES bound (HBM traffic over the chip's peak bytes/s),
not an operations bound: the merge, the GC scan and the block codec compare,
select and copy; none multiplies matrices. The share is defined on the work,
not on a kernel's name, so it reads the same whatever implements the merge.
"""

import json
import os

# KVSlab columns besides the key words (ops/slabs.py): key_len, doc_key_len,
# ht_hi, ht_lo, write_id, flags, value_idx at 4 bytes and ttl_ms at 8
SLAB_FIXED_BYTES = 7 * 4 + 8
DECISION_BYTES = 4          # one keep/perm word per input row


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json")
    return table[device_kind]


def slab_row_bytes(key_bytes: int) -> int:
    """One row of a slab whose keys are padded to whole 4-byte words."""
    return -(-key_bytes // 4) * 4 + SLAB_FIXED_BYTES


def compaction_job_bytes(rows_in: int, rows_out: int, key_bytes: int,
                         value_bytes_in: int, value_bytes_out: int) -> int:
    """Input slabs and values read once, survivors' slabs and values written
    once, one decision word per input row written once."""
    row = slab_row_bytes(key_bytes)
    return (rows_in * row + value_bytes_in
            + rows_out * row + value_bytes_out
            + rows_in * DECISION_BYTES)


def bytes_bound_s(n_bytes: int, device_kind: str) -> float:
    return n_bytes / peaks_for(device_kind)["hbm_bytes_per_s"]
