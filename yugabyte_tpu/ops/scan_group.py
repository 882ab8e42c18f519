"""The typed, grouped aggregate: TPC-H Q1 / Q6 in one dispatch a tablet.

Beside `ops/scan.py::_scan_agg_fused`, which reduces whole columns, this
kernel answers `SELECT <group cols>, fn(term), ... WHERE <conjunction>
GROUP BY <group cols>` where a term is a product of up to three factors
`col`, `(1 - col)`, `(1 + col)` over exact integer columns (INT, DATE,
DECIMAL as its unscaled integer). One program resolves MVCC visibility,
lifts every referenced column to ROW level, evaluates the predicates, finds
the groups and reduces:

  1. `_pushdown_base` (shared with the scalar kernels): snapshot GC at the
     read time, key-range mask, doc segments over the sorted entries.
  2. Row assembly: DocDB stores one ENTRY a (row, column). A segmented
     inclusive sum over the doc segments (`_segmented_sum`: static lane
     shifts, no strided scan) carries each referenced column's
     staged value words (and a 4-bit payload length a column, and the
     liveness count) to the row's LAST entry; after snapshot GC a row has at
     most one visible entry a column, so the "sum" is that entry's words.
  3. Predicates, group key and product terms are evaluated at the rows'
     last entries only, lane-wise, in uint32 limbs.
  4. Groups: the distinct group keys are found by repeated first-occurrence
     (at most GROUP_SLOTS; more refuses the dispatch, `groups`).
  5. Reduce: every term value is a two's-complement 64-bit integer held as
     two uint32 limbs, cut into 16 nibbles; ONE int8 x int8 -> int32 matrix
     product (one-hot group rows against nibble columns) gives every
     (group, term) nibble-column sum, nonnull count and row count.

**The accumulator has 64 bits (two's complement), and no sum may wrap.**
All term arithmetic on the device is modulo 2^64; it is exact because the
host refuses (`PushdownUnsupported("overflow")`) any dispatch in which
`rows selected x the product of the largest factor magnitudes seen` reaches
2^63: below that bound no row's product and no partial sum leaves the
signed 64-bit range, so arithmetic modulo 2^64 never wrapped a true value.
The largest magnitudes are reduced in the same dispatch. Nibble-column sums
are int32: exact while 15 x n_pad < 2^31, which PUSHDOWN_MAX_NPAD (2^24)
holds. There is no float anywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_tpu.ops import scan as _scan
from yugabyte_tpu.ops.scan import VAL_WORDS, _cmp_words, _pushdown_base

GROUP_SLOTS = 16                    # distinct groups a dispatch may hold
PRED_PAD = 8                        # predicate slots (Q6 has five)
# (column slots, term slots): Q6's class and Q1's
SHAPE_CLASSES = ((4, 2), (8, 8))
MAX_FACTORS = 3
KEY_WORDS = 2 * (VAL_WORDS + 1)     # two group columns x (3 words + length)
ACC_BITS = 64                       # two's-complement accumulator width
_WIDE = 13                          # length nibble of a payload over 12 B
_TAG_INT64 = 0x49                   # ValueType.kInt64


def shape_class(n_cols: int, n_terms: int):
    """Smallest (c_pad, t_pad) holding the spec, or None."""
    for c_pad, t_pad in SHAPE_CLASSES:
        if n_cols <= c_pad and n_terms <= t_pad:
            return c_pad, t_pad
    return None


def group_metrics():
    """The grouped kernel's own counters (the /compactionz "scans"
    block and the benchmark's notes read these)."""
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "scan_pushdown")
    return {
        "dispatches": e.counter(
            "scan_group_agg_dispatches_total",
            "grouped typed aggregates answered by one fused dispatch"),
        "entries": e.counter(
            "scan_group_agg_entries_total",
            "DocDB entries resolved by the grouped aggregate kernel"),
        "groups": e.counter(
            "scan_group_agg_groups_total",
            "group partials the grouped aggregate kernel returned"),
        "stage_miss": e.counter(
            "scan_group_agg_stage_miss_total",
            "slabs or value words staged inside a grouped-aggregate "
            "request (write-through should have left them resident)"),
    }


# ------------------------------------------------------------ limb helpers

def _u32(x):
    return jnp.uint32(x)


def _add64(ah, al, bh, bl):
    lo = al + bl
    return ah + bh + (lo < al).astype(jnp.uint32), lo


def _neg64(h, l):
    return _add64(~h, ~l, _u32(0), _u32(1))


def _mul64(ah, al, bh, bl):
    """(a * b) mod 2^64 over uint32 limbs: the low limbs' full 64-bit
    product by 16-bit halves (every partial under 2^32), the cross terms
    by wrapping multiplies."""
    m16 = _u32(0xFFFF)
    a0, a1 = al & m16, al >> 16
    b0, b1 = bl & m16, bl >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (p01 & m16) + (p10 & m16) + (p00 >> 16)
    lo = (p00 & m16) | ((mid & m16) << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi + ah * bl + al * bh, lo


_SHORT_DOC = 16     # entries: a row of a wide table with no old versions


def _segmented_sum(stack, new_doc):
    """Inclusive sum of every row of `stack` [K, n] within the doc
    segments `new_doc` opens: steps of one static lane shift each
    (Hillis-Steele), an element taking its neighbour 2^k back only while
    that neighbour is in its own segment. log2(n) steps cover any doc;
    where no doc is longer than _SHORT_DOC entries (lineitem's rows have
    15) four steps do, chosen on the device by ONE conditional (a
    conditional a step cost a copy of the operand each, taken or not: 19 x
    0.35 ms of a 15-ms dispatch on the v5e). Static shifts compile in about
    a minute at n = 2^19 where `lax.associative_scan`'s strided halving
    over the same operand took the TPU compiler longer than 19 minutes
    (PERF.md, PR 32)."""
    n = stack.shape[1]
    lane = jnp.arange(n, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(new_doc, lane, 0), axis=0)
    in_seg = lane - start                    # entries before me in my doc

    def steps(limit):
        def run(st):
            step = 1
            while step < limit:
                shifted = jnp.pad(st[:, :-step], ((0, 0), (step, 0)))
                st = st + jnp.where((in_seg >= step)[None, :], shifted,
                                    jnp.uint32(0))
                step *= 2
            return st
        return run

    if n <= _SHORT_DOC:
        return steps(n)(stack)
    return jax.lax.cond(jnp.max(in_seg) < _SHORT_DOC, steps(_SHORT_DOC),
                        steps(n), stack)


# ------------------------------------------------------------------ kernel

@functools.partial(jax.jit, static_argnames=(
    "w", "c_pad", "t_pad", "minmax", "presorted"))
def _scan_group_agg_fused(cols, vals, sort_rows, n_sort,
                          cutoff_hi, cutoff_lo, cph, cpl,
                          lo_words, lo_len, hi_words, hi_len, up_inf,
                          up_trunc,
                          c_sub, p_col, p_op, p_tag_a, p_tag_b, p_words,
                          p_len, g_col, t_col, t_kind, t_one_hi, t_one_lo,
                          w: int, c_pad: int, t_pad: int, minmax: bool,
                          presorted: bool = False):
    """See the module docstring. Operands that name columns are DATA
    (`c_sub`: the column-id key suffix a slot; `p_col` / `g_col` /
    `t_col`: slot indices, -1 = unused), so one executable a (w, n_pad,
    shape class) answers every query of the class."""
    n = cols.shape[1]
    (perm, _s, base, new_doc, end_doc, sub3, is_len3, is_bare,
     is_colkey) = _pushdown_base(
        cols, sort_rows, n_sort, cutoff_hi, cutoff_lo, cph, cpl,
        lo_words, lo_len, hi_words, hi_len, up_inf, up_trunc, w, presorted)
    sv = vals if presorted else vals[:, perm]
    v_len = jnp.minimum(sv[0], _u32(_WIDE))
    zero = _u32(0)

    # ---- row assembly: every referenced column's words to the row's end
    lifted = []
    lens = jnp.zeros(n, jnp.uint32)
    ce = base & is_len3
    for c in range(c_pad):
        m = ce & (sub3 == c_sub[c]) & (c_sub[c] != zero)
        for j in range(VAL_WORDS):
            lifted.append(jnp.where(m, sv[1 + j], zero))
        lens = lens | (jnp.where(m, v_len, zero) << _u32(4 * c))
    live_e = (base & (is_bare | is_colkey)).astype(jnp.uint32)
    stack = jnp.stack(lifted + [lens, live_e])
    rowv = _segmented_sum(stack, new_doc)
    r_words = rowv[:VAL_WORDS * c_pad].reshape(c_pad, VAL_WORDS, n)
    r_lens = rowv[VAL_WORDS * c_pad]
    row_ok = end_doc & (rowv[VAL_WORDS * c_pad + 1] > zero)

    def col_at(idx):
        """(words[3], length) of column slot `idx` (a traced scalar)."""
        i = jnp.clip(idx, 0, c_pad - 1)
        words = jax.lax.dynamic_index_in_dim(r_words, i, 0, keepdims=False)
        ln = (r_lens >> (i.astype(jnp.uint32) * _u32(4))) & _u32(15)
        return words, ln

    # ---- predicates, at row level (NULL and absent fail every operator)
    rowpass = jnp.ones(n, bool)
    wide = jnp.zeros((), bool)
    for i in range(PRED_PAD):
        code = p_op[i]
        words, ln = col_at(p_col[i])
        lt, eq = _cmp_words([words[j] for j in range(VAL_WORDS)],
                            ln.astype(jnp.int32), p_words[i], p_len[i],
                            VAL_WORDS)
        m = jnp.where(
            code == 1, eq,
            jnp.where(code == 2, ~eq,
                      jnp.where(code == 3, lt,
                                jnp.where(code == 4, lt | eq,
                                          jnp.where(code == 5, ~(lt | eq),
                                                    ~lt)))))
        tag = words[0] >> _u32(24)
        ok = (ln > zero) & ((tag == p_tag_a[i]) | (tag == p_tag_b[i])) & m
        rowpass = rowpass & ((code == 0) | ok)
        wide = wide | jnp.any(row_ok & (code != 0) & (ln == _u32(_WIDE)))
    sel = row_ok & rowpass

    # ---- group key: two columns x (3 words + length); unused -> zeros
    key_rows = []
    for g in range(2):
        words, ln = col_at(g_col[g])
        on = g_col[g] >= 0
        for j in range(VAL_WORDS):
            key_rows.append(jnp.where(on, words[j], zero))
        key_rows.append(jnp.where(on, ln, zero))
        wide = wide | jnp.any(sel & on & (ln == _u32(_WIDE)))
    key = jnp.stack(key_rows)                               # [KEY_WORDS, n]
    lane = jnp.arange(n, dtype=jnp.int32)

    def more(carry):
        k, gid, _keys = carry
        return (k < GROUP_SLOTS) & jnp.any(sel & (gid < 0))

    def assign(carry):
        k, gid, keys = carry
        first = jnp.min(jnp.where(sel & (gid < 0), lane, n - 1))
        key_k = jax.lax.dynamic_slice_in_dim(key, first, 1, axis=1)
        same = sel & (gid < 0) & jnp.all(key == key_k, axis=0)
        return (k + 1, jnp.where(same, k, gid),
                jax.lax.dynamic_update_slice_in_dim(
                    keys, key_k.T, k, axis=0))

    n_groups, gid, keys = jax.lax.while_loop(
        more, assign,
        (jnp.int32(0), jnp.full(n, -1, jnp.int32),
         jnp.zeros((GROUP_SLOTS, KEY_WORDS), jnp.uint32)))
    too_many = jnp.any(sel & (gid < 0))

    # ---- product terms: two's complement, modulo 2^64 (module docstring)
    sign = _u32(0x80000000)
    nib_rows = []                   # int8 rows of the reduce's right side
    abs_hi, abs_lo, quals = [], [], []
    t_hi, t_lo = [], []
    for t in range(t_pad):
        ph, pl = jnp.zeros(n, jnp.uint32), jnp.ones(n, jnp.uint32)
        nonnull = t_col[t, 0] >= 0          # an unused term counts nothing
        f_abs = []
        for j in range(MAX_FACTORS):
            on = t_col[t, j] >= 0
            words, ln = col_at(t_col[t, j])
            is_int = (ln == _u32(9)) & ((words[0] >> _u32(24))
                                        == _u32(_TAG_INT64))
            vh = (((words[0] & _u32(0xFFFFFF)) << _u32(8))
                  | (words[1] >> _u32(24))) ^ sign
            vl = (words[1] << _u32(8)) | (words[2] >> _u32(24))
            nh, nl = _neg64(vh, vl)
            one_h, one_l = t_one_hi[t, j], t_one_lo[t, j]
            kind = t_kind[t, j]
            sh, sl = _add64(one_h, one_l, nh, nl)          # one - v
            ah, al = _add64(one_h, one_l, vh, vl)          # one + v
            fh = jnp.where(kind == 1, sh, jnp.where(kind == 2, ah, vh))
            fl = jnp.where(kind == 1, sl, jnp.where(kind == 2, al, vl))
            fh = jnp.where(on, fh, zero)
            fl = jnp.where(on, fl, _u32(1))
            ph, pl = _mul64(ph, pl, fh, fl)
            nonnull = nonnull & (~on | is_int)
            neg = (fh >> _u32(31)) == _u32(1)
            mh, ml = _neg64(fh, fl)
            f_abs.append((jnp.where(neg, mh, fh), jnp.where(neg, ml, fl)))
        q = sel & nonnull
        quals.append(q)
        for fa_h, fa_l in f_abs:
            abs_hi.append(jnp.where(q, fa_h, zero))
            abs_lo.append(jnp.where(q, fa_l, zero))
        for limb in (ph, pl):
            for k in range(8):
                nib_rows.append(jnp.where(
                    q, (limb >> _u32(28 - 4 * k)) & _u32(15), zero))
        nib_rows.append(q.astype(jnp.uint32))
        t_hi.append(ph ^ sign)              # order-preserving for min/max
        t_lo.append(pl)
    nib_rows.append(sel.astype(jnp.uint32))
    rhs = jnp.stack(nib_rows).astype(jnp.int8)      # [17 * t_pad + 1, n]
    onehot = (gid[None, :] == jnp.arange(GROUP_SLOTS, dtype=jnp.int32)
              [:, None]).astype(jnp.int8)
    sums = jax.lax.dot_general(
        onehot, rhs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)           # [GROUP_SLOTS, 17t+1]

    # largest factor magnitudes over the rows that count (the host's
    # overflow bound), as (hi, lo) two-step maxima
    a_hi, a_lo = jnp.stack(abs_hi), jnp.stack(abs_lo)
    max_hi = jnp.max(a_hi, axis=1)
    max_lo = jnp.max(jnp.where(a_hi == max_hi[:, None], a_lo, zero), axis=1)

    out = (n_groups, keys, too_many, wide, sums, max_hi, max_lo)
    if minmax:
        th, tl = jnp.stack(t_hi), jnp.stack(t_lo)           # [t_pad, n]
        in_g = (gid[None, None, :]
                == jnp.arange(GROUP_SLOTS, dtype=jnp.int32)[:, None, None]) \
            & jnp.stack(quals)[None, :, :]                  # [G, t_pad, n]
        u32max = _u32(0xFFFFFFFF)
        mn_hi = jnp.min(jnp.where(in_g, th[None], u32max), axis=2)
        mn_lo = jnp.min(jnp.where(in_g & (th[None] == mn_hi[:, :, None]),
                                  tl[None], u32max), axis=2)
        mx_hi = jnp.max(jnp.where(in_g, th[None], zero), axis=2)
        mx_lo = jnp.max(jnp.where(in_g & (th[None] == mx_hi[:, :, None]),
                                  tl[None], zero), axis=2)
        out = out + (mn_hi, mn_lo, mx_hi, mx_lo)
    return out


# ----------------------------------------------------- host-side driver

def _slot_suffix(cid: int) -> int:
    from yugabyte_tpu.docdb.doc_operations import column_key_suffix
    suf = column_key_suffix(cid)
    assert len(suf) == 3
    return (suf[0] << 16) | (suf[1] << 8) | suf[2]


@functools.lru_cache(maxsize=256)
def pack_group_operands(spec, c_pad: int, t_pad: int):
    """The spec as kernel operands (numpy, read-only; handed to the program
    as is). Cached by the spec's value: a stream of queries repeats a few."""
    slot = {cid: i for i, cid in enumerate(spec.cids)}
    c_sub = np.zeros(c_pad, np.uint32)
    for cid, i in slot.items():
        c_sub[i] = _slot_suffix(cid)
    (_p_sub, p_op, _p_neg, p_ta, p_tb, p_words,
     p_len) = _scan._pack_predicate_operands(spec, PRED_PAD)
    p_col = np.full(PRED_PAD, -1, np.int32)
    for i, p in enumerate(spec.predicates):
        p_col[i] = slot[p.cid]
    g_col = np.full(2, -1, np.int32)
    for i, g in enumerate(spec.group_by):
        g_col[i] = slot[g.cid]
    t_col = np.full((t_pad, MAX_FACTORS), -1, np.int32)
    t_kind = np.zeros((t_pad, MAX_FACTORS), np.int32)
    t_one_hi = np.zeros((t_pad, MAX_FACTORS), np.uint32)
    t_one_lo = np.zeros((t_pad, MAX_FACTORS), np.uint32)
    kinds = {"col": 0, "1-": 1, "1+": 2}
    for t, factors in enumerate(spec.terms):
        for j, f in enumerate(factors):
            t_col[t, j] = slot[f.cid]
            t_kind[t, j] = kinds[f.kind]
            t_one_hi[t, j] = f.one >> 32
            t_one_lo[t, j] = f.one & 0xFFFFFFFF
    return (c_sub, p_col, p_op, p_ta, p_tb, p_words, p_len, g_col, t_col,
            t_kind, t_one_hi, t_one_lo)


def _bound_operands(w: int, lower_key, upper_key):
    """The key-range operands as numpy (the dispatch uploads them with the
    rest; a jnp scalar each is an upload each). Bounds fit the key stride:
    the caller refused longer ones."""
    lo_w, lo_l = _scan._pack_bound(lower_key, w)
    hi_w, hi_l = _scan._pack_bound(upper_key, w)
    return (lo_w, np.int32(lo_l), hi_w, np.int32(hi_l),
            np.bool_(upper_key is None), np.bool_(False))


def _cutoff_operands(read_ht_value: int):
    phys = read_ht_value >> 12
    return (np.uint32(read_ht_value >> 32),
            np.uint32(read_ht_value & 0xFFFFFFFF),
            np.uint32(phys >> 20), np.uint32(phys & 0xFFFFF))


def _signed64(u: int) -> int:
    u &= (1 << 64) - 1
    return u - (1 << 64) if u >> 63 else u


def _decode_group_key(words) -> list:
    """Two (3 words + length) groups -> the stored primitives; an absent
    or NULL column is None."""
    from yugabyte_tpu.docdb.doc_key import PrimitiveValue
    out = []
    for g in range(2):
        chunk = words[g * (VAL_WORDS + 1):(g + 1) * (VAL_WORDS + 1)]
        ln = int(chunk[VAL_WORDS])
        if ln == 0:
            out.append(None)
            continue
        raw = np.asarray(chunk[:VAL_WORDS], dtype=">u4").tobytes()[:ln]
        out.append(PrimitiveValue.decode(raw, 0)[0])
    return out


def group_aggregate_sources(sources, read_ht_value: int, spec,
                            lower_key=None, upper_key=None,
                            device=None) -> dict:
    """One fused dispatch -> this source set's grouped partial:
    {"groups": [{"key": [...], "rows": n, "terms": [{"nonnull", "sum",
    "min", "max"}, ...]}]} with `terms` in `spec.terms` order. Raises
    PushdownUnsupported (the rows path answers, counted by reason) for a
    spec outside the shape classes, more than GROUP_SLOTS groups, a payload
    wider than the staged words, or a sum the accumulator cannot hold."""
    from yugabyte_tpu.docdb.scan_spec import (PushdownUnsupported,
                                              empty_term_stats)
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.utils import latency
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch

    with latency.sub_span("query_pack"):
        cls = shape_class(len(spec.cids), len(spec.terms))
        if cls is None or len(spec.predicates) > PRED_PAD:
            raise PushdownUnsupported("agg_width")
        c_pad, t_pad = cls
        staged, vals, _live, presorted = _scan._stage_pushdown(
            sources, spec, device)
        if staged is None:
            return {"groups": []}
        stride = staged.w * 4
        if (lower_key and len(lower_key) > stride) or \
                (upper_key and len(upper_key) > stride):
            raise PushdownUnsupported("bound_width")
        ops = pack_group_operands(spec, c_pad, t_pad)
        bounds = _bound_operands(staged.w, lower_key, upper_key)
        cutoffs = _cutoff_operands(read_ht_value)
        minmax = spec.wants_minmax
    bkey = _scan._check_pushdown_bucket(staged.n_pad, "scan_group_agg")
    try:
        with latency.sub_span("device_enqueue"):
            device_faults.maybe_fault("dispatch")
            out = _scan_group_agg_fused(
                staged.cols_dev, vals, staged.sort_rows,
                np.int32(staged.n_sort), *cutoffs, *bounds, *ops,
                w=staged.w, c_pad=c_pad, t_pad=t_pad, minmax=minmax,
                presorted=presorted)
        with latency.sub_span(latency.SUB_DEVICE_WAIT):
            device_faults.maybe_fault("result")
            out = [np.asarray(x) for x in out]
    except Exception as e:  # noqa: BLE001 — classified below
        _scan._contain_pushdown_fault(e, bkey, "scan_group_agg")
        raise
    with latency.sub_span("partial_build"):
        n_groups, keys, too_many, wide, sums, max_hi, max_lo = out[:7]
        record_kernel_dispatch("kernel_scan_group_agg", staged.n,
                               staged.n_pad)
        _scan._record_bucket_dispatch("group_agg", staged.n_pad)
        if bool(wide):
            raise PushdownUnsupported("value_width")
        if bool(too_many):
            raise PushdownUnsupported("groups")
        n_groups = int(n_groups)
        sums = sums.astype(np.int64)
        rows_sel = int(sums[:n_groups, -1].sum())
        limit = 1 << (ACC_BITS - 1)
        for t, factors in enumerate(spec.terms):
            bound = rows_sel
            for j in range(len(factors)):
                k = t * MAX_FACTORS + j
                bound *= (int(max_hi[k]) << 32) | int(max_lo[k])
            if bound >= limit:
                # the largest possible sum passes the accumulator: refused,
                # never wrapped
                raise PushdownUnsupported("overflow")
        groups = []
        bias = 1 << 63
        for g in range(n_groups):
            terms = []
            for t in range(len(spec.terms)):
                col0 = 17 * t
                nn = int(sums[g, col0 + 16])
                st = empty_term_stats()
                if nn:
                    total = 0
                    for k in range(16):
                        total += int(sums[g, col0 + k]) << (60 - 4 * k)
                    st["nonnull"] = nn
                    st["sum"] = _signed64(total)
                    if minmax:
                        mn_hi, mn_lo, mx_hi, mx_lo = out[7:11]
                        st["min"] = ((int(mn_hi[g, t]) << 32)
                                     | int(mn_lo[g, t])) - bias
                        st["max"] = ((int(mx_hi[g, t]) << 32)
                                     | int(mx_lo[g, t])) - bias
                terms.append(st)
            key = _decode_group_key(keys[g])[:len(spec.group_by)]
            groups.append({"key": key, "rows": int(sums[g, -1]),
                           "terms": terms})
        m = group_metrics()
        m["dispatches"].increment()
        m["entries"].increment(staged.n)
        m["groups"].increment(len(groups))
        pm = _scan.pushdown_metrics()
        pm["agg"].increment()
        pm["rows"].increment(staged.n)
        pm["batch"].increment(staged.n)
        return {"groups": groups}
