"""Seeded data for the benchmark's cells, and the YCSB key chooser.

`Kv64Runs` is a copy of `chip_smoke.YcsbRuns` (proven on the chip in PR 22):
vectorised ~64-byte key-value L0 runs in the repo's DocDB encoding, with a
self-check against the repo's own encoder. The copy lives here because later
PRs may change `chip_smoke.py` and may not change the yardstick.

`ScrambledZipfian` is YCSB's request distribution (Cooper et al., SoCC 2010):
ranks drawn Zipfian with constant 0.99 over the records, scattered over the
key space by FNV-1a 64 as YCSB's ScrambledZipfianGenerator does. The ranks
are drawn by exact inverse CDF, not by Gray's approximation YCSB uses.

Nothing here reads the clock: the same seed gives the same bytes.
"""

import numpy as np

F_PAYLOAD = 41      # 'S' + 41 + 00 00 = 44 value bytes; 19-byte key: ~64 B KV
_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", dtype=np.uint8)
KIND_F, KIND_N, KIND_TOMB = 0, 1, 2


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole-number seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def kv64_schema():
    """k (range key), f (~41-byte string), n (INT64)."""
    from yugabyte_tpu.common.schema import ColumnSchema, DataType, Schema
    return Schema(columns=[ColumnSchema("k", DataType.STRING),
                           ColumnSchema("f", DataType.STRING),
                           ColumnSchema("n", DataType.INT64)],
                  num_hash_key_columns=0, num_range_key_columns=1)


class Kv64Runs:
    """Vectorised YCSB-shaped L0 runs: key 'S' 'user%08d' 00 00 '!' (+ 'K'
    col id), one column write or one row tombstone per entry."""

    def __init__(self, seed: int, shares: dict):
        from yugabyte_tpu.docdb.doc_key import DocKey
        from yugabyte_tpu.docdb.doc_operations import column_key_suffix
        from yugabyte_tpu.docdb.value import Value
        self.schema = kv64_schema()
        self.rng = rng_for(seed, 1)
        self.tomb_share = float(shares["row_tombstone"])
        self.n_share = float(shares["int64_column"])
        root = DocKey(range_components=("user00000000",)).encode()
        self.root = np.frombuffer(root, dtype=np.uint8)
        self.digit0 = root.index(b"00000000")
        self.suffix = {KIND_F: np.frombuffer(column_key_suffix(
            self.schema.column_id("f")), dtype=np.uint8),
            KIND_N: np.frombuffer(column_key_suffix(
                self.schema.column_id("n")), dtype=np.uint8)}
        self.tomb = Value.tombstone().encode()
        f_enc = Value(primitive="x" * F_PAYLOAD).encode()
        self.f_head, self.f_tail = f_enc[:1], f_enc[1 + F_PAYLOAD:]
        self.f_len = 1 + F_PAYLOAD + len(self.f_tail)
        self.n_tag = Value(primitive=0).encode()[:1]
        self._self_check()

    def root_keys(self, ids: np.ndarray) -> np.ndarray:
        keys = np.tile(self.root, (len(ids), 1))
        digits = ids[:, None] // (10 ** np.arange(7, -1, -1)[None, :]) % 10
        keys[:, self.digit0:self.digit0 + 8] = digits + ord("0")
        return keys

    def full_keys(self, ids: np.ndarray, kind: np.ndarray):
        """(zero-padded key matrix, key lengths) for entries of `kind`."""
        sfx = len(self.suffix[KIND_F])
        keys = np.zeros((len(ids), len(self.root) + sfx), dtype=np.uint8)
        keys[:, :len(self.root)] = self.root_keys(ids)
        keys[:, len(self.root):] = np.where(
            (kind == KIND_N)[:, None], self.suffix[KIND_N][None, :],
            self.suffix[KIND_F][None, :])
        is_tomb = kind == KIND_TOMB
        keys[is_tomb, len(self.root):] = 0
        key_len = np.where(is_tomb, len(self.root), keys.shape[1])
        return keys, key_len

    def encode_n(self, vals: np.ndarray) -> np.ndarray:
        biased = (vals.astype(np.int64).view(np.uint64)
                  ^ np.uint64(1 << 63)).astype(">u8")
        out = np.empty((len(vals), 9), dtype=np.uint8)
        out[:, 0] = self.n_tag[0]
        out[:, 1:] = biased.view(np.uint8).reshape(-1, 8)
        return out

    def run(self, n: int, key_space: int, ht_base_us: int) -> dict:
        """One run of n entries; hybrid times unique within and across runs
        as long as n < the distance between two runs' bases."""
        rng = self.rng
        ids = rng.integers(0, key_space, size=n)
        u = rng.random(n)
        kind = np.where(u < self.tomb_share, KIND_TOMB, np.where(
            u < self.tomb_share + self.n_share, KIND_N, KIND_F)).astype(
                np.int8)
        is_tomb, is_n = kind == KIND_TOMB, kind == KIND_N
        keys, key_len = self.full_keys(ids, kind)
        vals = np.zeros((n, self.f_len), dtype=np.uint8)
        vals[:, 0] = self.f_head[0]
        vals[:, 1:1 + F_PAYLOAD] = _LETTERS[
            rng.integers(0, 26, size=(n, F_PAYLOAD))]
        vals[:, 1 + F_PAYLOAD:] = np.frombuffer(self.f_tail, dtype=np.uint8)
        n_vals = rng.integers(0, 1_000_000, size=n)
        vals[is_n, :9] = self.encode_n(n_vals[is_n])
        vals[is_n, 9:] = 0
        vals[is_tomb, 0] = self.tomb[0]
        vals[is_tomb, 1:] = 0
        val_len = np.where(is_tomb, 1, np.where(is_n, 9, self.f_len))
        ht = ((np.uint64(ht_base_us) + rng.permutation(n).astype(np.uint64))
              << np.uint64(12))
        return {
            "ids": ids, "kind": kind, "vals": vals, "val_len": val_len,
            "keys_blob": keys[np.arange(keys.shape[1])[None, :]
                              < key_len[:, None]].tobytes(),
            "key_offs": np.concatenate([[0], np.cumsum(key_len)]).astype(
                np.int64),
            "vals_blob": vals[np.arange(self.f_len)[None, :]
                              < val_len[:, None]].tobytes(),
            "val_offs": np.concatenate([[0], np.cumsum(val_len)]).astype(
                np.int64),
            "ht": ht, "wid": np.zeros(n, dtype=np.uint32),
        }

    def _self_check(self) -> None:
        """The vectorised bytes are what the repo's encoder writes."""
        from yugabyte_tpu.docdb.doc_key import DocKey
        from yugabyte_tpu.docdb.doc_operations import QLWriteOp, WriteOpKind
        for uid, nv in ((7, 0), (12345678, 999_999), (99999999, 31337)):
            op = QLWriteOp(WriteOpKind.UPDATE,
                           DocKey(range_components=("user%08d" % uid,)),
                           {"n": nv})
            (key, val), = op.to_kv_pairs(self.schema)
            keys, key_len = self.full_keys(np.asarray([uid]),
                                           np.asarray([KIND_N]))
            if key != keys[0, :key_len[0]].tobytes():
                raise RuntimeError("datagen: key encoding drifted")
            if val != self.encode_n(np.asarray([nv]))[0].tobytes():
                raise RuntimeError("datagen: INT64 value encoding drifted")


# ------------------------------------------------------------------ YCSB

FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def fnv1a64(vals: np.ndarray) -> np.ndarray:
    """YCSB's Utils.fnvhash64 over the eight octets of each value, low octet
    first; YCSB takes the absolute value of the signed result."""
    v = np.asarray(vals, dtype=np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = h ^ (v & np.uint64(0xFF))
            h = h * FNV_PRIME
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64)).astype(np.uint64)


class ScrambledZipfian:
    """Record numbers in [0, n): rank r drawn with P(r) ~ 1/(r+1)^theta,
    then scattered by fnv1a64(r) % n."""

    def __init__(self, n: int, theta: float):
        self.n = int(n)
        w = 1.0 / np.arange(1, self.n + 1, dtype=np.float64) ** float(theta)
        self.cdf = np.cumsum(w)
        self.zeta = float(self.cdf[-1])
        self.cdf /= self.zeta
        self.scatter = (fnv1a64(np.arange(self.n)) % np.uint64(self.n)
                        ).astype(np.int64)

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return np.minimum(np.searchsorted(self.cdf, rng.random(size)),
                          self.n - 1)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return self.scatter[self.ranks(rng, size)]


def key_chooser(spec: dict, n: int):
    """`request_distribution` of a traffic file -> a chooser over n records."""
    kind = spec["kind"]
    if kind == "scrambled_zipfian":
        return ScrambledZipfian(n, spec["constant"])
    raise ValueError(f"unknown request distribution {kind!r}")


def ycsb_key_names(n: int) -> list:
    """YCSB's buildKeyName with hashed insert order: 'user' + fnvhash64(i)."""
    return ["user%d" % h for h in fnv1a64(np.arange(n)).tolist()]


def letter_strings(rng: np.random.Generator, count: int, length: int) -> list:
    """`count` strings of `length` random lower-case letters."""
    blob = _LETTERS[rng.integers(0, 26, size=count * length)].tobytes(
        ).decode("ascii")
    return [blob[i * length:(i + 1) * length] for i in range(count)]
