"""Device SST block codec: decode/encode block bytes on the accelerator.

Closes the byte shell around the compaction kernel (ROADMAP item 2): the
merge+GC kernel runs at 3.5M rows/s but end-to-end compaction ran at
~0.53x native because every job still paid the HOST byte codec — threaded
`decode_block` + `pack_cols` on ingest (stage A) and per-row block encode
on output (stage C).  This module moves the column transforms themselves
into two manifest-disciplined kernel families (the LUDA staging shape:
decode -> device compute -> encode as one offloaded chain):

  - `_block_decode_fused`: raw (CRC-checked, uncompressed) block bodies
    upload as ONE padded uint32 word matrix plus per-entry offset
    vectors; the kernel gathers key words (big-endian swap), widens the
    u16/u8 metadata arrays and splits TTL into the 20/32-bit microsecond
    limbs — producing the staged cols matrix `pack_cols` would have
    built, bit for bit, without materializing a decoded row on the host.
    Values never upload: they are zero-copy slices of the same raw body
    (block_format.raw_block_values) — the LSM-OPD direction of operating
    on block bytes directly.

  - `_block_encode_fused`: a gathered survivor-span cols matrix (already
    on device from the write-through gather) transforms into the exact
    on-disk column encodings — entry-major byteswapped key slab, packed
    u16 length pairs, packed u8 flags, raw TTL limbs — so the host
    writer only splices value bytes, stamps headers + CRC and writes the
    file (`encode_span`), killing the per-row encode work.

CRC stays host-side by design: zlib.crc32 is memory-bandwidth C over
bytes the host touches anyway (corrupt blocks surface typed
Status.Corruption BEFORE any upload, never wrong bytes), while the
per-entry transform work — the measured wall — runs on device.
`YBTPU_DEVICE_CODEC=0` disables both families (the compaction job then
takes the native byte shell exactly as before); device faults at the
dispatch/result sites quarantine the job's shape bucket and complete
byte-identically via the native merge, like every other kernel family.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_tpu.ops.merge_gc import (
    _ROW_WORDS, PAD_SENTINEL, StagedCols, bucket_size, build_sort_schedule)
from yugabyte_tpu.storage import block_format
from yugabyte_tpu.utils.jax_setup import Prewarm  # also: compilation cache


class BlockCodecUnsupported(Exception):
    """The device codec cannot run this job (host byte shell takes it)."""


def codec_enabled() -> bool:
    """YBTPU_DEVICE_CODEC=0 disables both codec families (the documented
    fallback knob, next to YBTPU_PIPELINE)."""
    return os.environ.get("YBTPU_DEVICE_CODEC", "1").lower() \
        not in ("0", "false", "off")


def codec_metrics():
    from yugabyte_tpu.utils.metrics import ROOT_REGISTRY
    e = ROOT_REGISTRY.entity("server", "storage")
    return {
        "decode_blocks": e.counter(
            "compaction_block_decode_device_total",
            "SST blocks decoded into staged cols by the device codec "
            "(the host decode_block loop these replace counts in "
            "sst_block_decode_total)"),
        "encode_blocks": e.counter(
            "compaction_block_encode_device_total",
            "output SST blocks whose column bytes were assembled by the "
            "device codec"),
        "encode_fallbacks": e.counter(
            "compaction_block_encode_fallback_total",
            "device-native compactions that wrote outputs through the "
            "native shell encode instead of the device codec (codec "
            "disabled, all inputs run-cached, or mid-job fault)"),
    }


def _bswap32(x):
    """Big-endian key bytes <-> the uint32 key-word convention of
    ops/slabs.py (a little-endian u32 view of the raw bytes needs one
    byte swap each way)."""
    return (((x & jnp.uint32(0xFF)) << jnp.uint32(24))
            | ((x & jnp.uint32(0xFF00)) << jnp.uint32(8))
            | ((x >> jnp.uint32(8)) & jnp.uint32(0xFF00))
            | (x >> jnp.uint32(24)))


def _block_decode_impl(cols_in, n):
    """Raw block columns -> the staged cols matrix, on device.

    The host splits each CRC-checked body into its CONTIGUOUS column
    regions, laid straight into the cols layout (pure memcpy-class
    slicing + u16/u8 widening, no per-entry work — see
    decode_file_to_staged); the kernel does the per-entry transforms:
    big-endian key byteswap, the TTL ms -> 20/32-bit-microsecond limb
    split, and the column stats.  Deliberately gather- and
    transpose-free: every op is elementwise, so the program is fast on
    both the CPU fallback and the TPU (1-D lane gathers run ~180MB/s
    there; this layout avoids them entirely) and the donated twin can
    reuse the input HBM in place.  No static args — the compile key is
    the (n_pad, w_pad) shape bucket.

      cols_in: u32 [8+w_pad, n_pad] — the pack_cols row layout, except
            rows 6..7 carry the RAW (lo, hi) limbs of the i64
            millisecond TTL and rows 8+ carry the little-endian raw key
            words (zero beyond each entry's real stride; the host
            pre-fills the pad template beyond n: sentinel lens, 0xFF
            keys — 0xFF is bswap-invariant and sorts last)

    Returns (cols [8+w_pad, n_pad], is_const [R], first [R]) — cols plus
    the column stats stage_slab computes, so the host never downloads
    the matrix."""
    n_pad = cols_in.shape[1]
    lane = jnp.arange(n_pad, dtype=jnp.int32)
    valid = lane < n

    t_lo = cols_in[6]
    t_hi = cols_in[7]
    # ttl_us = ttl_ms * 1000 in two u32 limbs, then the 20/32 split
    # pack_cols writes (int64-free: 16-bit partial products + carry)
    k1000 = jnp.uint32(1000)
    a0 = t_lo & jnp.uint32(0xFFFF)
    a1 = t_lo >> jnp.uint32(16)
    p0 = a0 * k1000
    p1 = a1 * k1000
    add = (p1 & jnp.uint32(0xFFFF)) << jnp.uint32(16)
    us_lo = p0 + add
    carry = (us_lo < add).astype(jnp.uint32)
    us_hi = (p1 >> jnp.uint32(16)) + t_hi * k1000 + carry
    ttl_hi_col = (us_lo >> jnp.uint32(20)) | (us_hi << jnp.uint32(12))
    ttl_lo_col = us_lo & jnp.uint32(0xFFFFF)

    cols = jnp.concatenate(
        [cols_in[:6], ttl_hi_col[None], ttl_lo_col[None],
         _bswap32(cols_in[_ROW_WORDS:])], axis=0)
    first = cols[:, 0]
    is_const = jnp.all((cols == first[:, None]) | (~valid)[None, :],
                       axis=1)
    return cols, is_const, first


_block_decode_fused = jax.jit(_block_decode_impl)

# Donated variant: the uploaded raw column buffers are TRANSIENT
# (nothing reads them after the decode — values were sliced host-side),
# so on backends that honor donation XLA reuses the key matrix's HBM for
# the cols output instead of holding both live together.
_block_decode_fused_donated = functools.partial(
    jax.jit, donate_argnums=(0,))(_block_decode_impl)


def _block_encode_impl(cols):
    """Gathered survivor-span cols -> the on-disk column encodings.

    Input is the write-through span gather (ops/run_merge.
    gather_staged_output_span — tombstone flags already OR'd on device);
    NEVER donated: the same buffer installs into the slab cache after
    the span's SST hits disk.  Outputs (all u32, sliced/viewed by the
    host assembler `encode_span`):
      keys  [n_pad, w_pad]  entry-major byteswapped key words
      kl2 / dkl2 [n_pad/2]  packed u16 pairs (little-endian)
      ht_hi / ht_lo / wid [n_pad]
      fl4   [n_pad/4]       packed u8 quads
      ttl   [2, n_pad]      the 20/32 microsecond limbs (host divides
                            back to i64 milliseconds — exact, the limbs
                            were ms*1000)"""
    from yugabyte_tpu.ops.point_read import (_FNV_OFFSET_HI,
                                             _FNV_OFFSET_LO,
                                             _mul64_by_prime)
    kl = cols[0]
    dkl = cols[1]
    w_pad = cols.shape[0] - _ROW_WORDS
    keys = _bswap32(cols[_ROW_WORDS:]).T
    kl2 = (kl[0::2] & jnp.uint32(0xFFFF)) | (kl[1::2] << jnp.uint32(16))
    dkl2 = (dkl[0::2] & jnp.uint32(0xFFFF)) | (dkl[1::2] << jnp.uint32(16))
    fl = cols[5] & jnp.uint32(0xFF)
    fl4 = (fl[0::4] | (fl[1::4] << jnp.uint32(8))
           | (fl[2::4] << jnp.uint32(16)) | (fl[3::4] << jnp.uint32(24)))
    ttl = jnp.stack([cols[6], cols[7]], axis=0)
    # doc-key bloom hashes ride the same dispatch: FNV-1a over the first
    # doc_key_len bytes of each key (storage/bloom.fnv64_masked's exact
    # limb arithmetic via the point-read device twin) — the base-file
    # bloom build needs them anyway and the host pass was the single
    # most expensive piece of the host encode
    n_pad = cols.shape[1]
    h_hi = jnp.full((n_pad,), jnp.uint32(_FNV_OFFSET_HI))
    h_lo = jnp.full((n_pad,), jnp.uint32(_FNV_OFFSET_LO))
    dkl_i = dkl.astype(jnp.int32)
    for j in range(w_pad * 4):
        word = cols[_ROW_WORDS + j // 4]
        byte = (word >> jnp.uint32(8 * (3 - (j % 4)))) & jnp.uint32(0xFF)
        active = dkl_i > j
        nhi, nlo = _mul64_by_prime(h_hi, h_lo ^ byte)
        h_hi = jnp.where(active, nhi, h_hi)
        h_lo = jnp.where(active, nlo, h_lo)
    return (keys, kl2, dkl2, cols[2], cols[3], cols[4], fl4, ttl,
            h_hi, h_lo)


_block_encode_fused = jax.jit(_block_encode_impl)


# ---------------------------------------------------------------------------
# Host side: raw-file parsing (CRC + zero-copy values), upload staging,
# and the output-block assembler.
# ---------------------------------------------------------------------------


@dataclass
class RawFileBlocks:
    """One SST data file parsed at the raw-block level: CRC-checked
    bodies ready for upload, values as zero-copy slices — no column
    decode happened and none of the sst_block_decode_total /
    compaction_ingest_decode_total counters moved."""
    n: int                       # total entries
    w: int                       # real key words (max stride/4)
    counts: np.ndarray           # int64 [B]
    strides_w: np.ndarray        # int64 [B]
    bodies: List[np.ndarray]     # uint8 fixed regions (keys + metadata)
    # per-block ZERO-COPY value rows (views over the raw bodies): the
    # decode path never materializes them — the compaction job concats
    # every input's parts ONCE when stage C starts gathering survivors
    value_parts: List[object]

    @property
    def values(self):
        """This file's value rows as one ValueArray (lazy concat —
        only the single-file callers pay it)."""
        from yugabyte_tpu.ops.slabs import ValueArray
        return (ValueArray.concat(self.value_parts) if self.value_parts
                else ValueArray.empty_rows(0))


def parse_raw_file(raw: bytes, handles: Sequence[Tuple[int, int, int]]
                   ) -> RawFileBlocks:
    """Split one data file's bytes into CRC-checked raw block regions.

    Corruption surfaces here, typed, BEFORE anything uploads or any
    value byte is trusted — the codec twin of the native shell's
    prepare()-time checks."""
    counts: List[int] = []
    strides_w: List[int] = []
    bodies: List[np.ndarray] = []
    vals: List[object] = []
    mv = memoryview(raw)   # zero-copy block/body slicing
    for off, size, _cnt in handles:
        n_b, stride, body = block_format.split_raw_block(
            mv[off: off + size])
        counts.append(n_b)
        strides_w.append(stride // 4)
        bodies.append(np.frombuffer(
            body, dtype=np.uint8,
            count=block_format.fixed_region_bytes(n_b, stride)))
        vals.append(block_format.raw_block_values(n_b, stride, body))
    return RawFileBlocks(
        n=int(sum(counts)),
        w=max([int(s) for s in strides_w], default=1),
        counts=np.asarray(counts, dtype=np.int64),
        strides_w=np.asarray(strides_w, dtype=np.int64),
        bodies=bodies,
        value_parts=vals)


def _quantize_width(w: int) -> int:
    # pack_cols' width formula (== run_merge.quantize_width): decoded
    # staging must land on the same bucket as host staging
    return 1 << max(2, (w - 1).bit_length() if w > 1 else 1)


def decode_file_to_staged(rfb: RawFileBlocks, device=None) -> StagedCols:
    """Upload one file's raw fixed regions and decode them on device into
    the StagedCols matrix stage_slab would have produced (bit-identical;
    differential-tested in tests/test_block_codec.py)."""
    from yugabyte_tpu.utils.metrics import pipeline_span
    if rfb.n == 0:
        raise BlockCodecUnsupported("empty file has nothing to stage")
    with pipeline_span("decode") as sp:
        return _decode_file_to_staged(rfb, device, sp)


def _decode_file_to_staged(rfb: RawFileBlocks, device, sp) -> StagedCols:
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.ops.run_merge import _donation_supported
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch
    n = rfb.n
    n_pad = bucket_size(n)
    from yugabyte_tpu.storage.bucket_health import health_board
    _board = health_board()
    if not _board.allow_device("block_decode", (1, n_pad)):
        # parked bucket (recent fault / sticky mismatch): the caller's
        # BlockCodecUnsupported handling takes the native byte shell
        raise BlockCodecUnsupported("decode bucket parked by the "
                                    "health board")
    w_pad = _quantize_width(rfb.w)
    # Per-block CONTIGUOUS region slices laid straight into ONE buffer
    # in the cols layout.  All memcpy-class (vectorized widening of the
    # u16/u8 regions included): the per-entry transform work (byteswap,
    # TTL limb math, stats) happens in the kernel.
    cols_in = np.zeros((_ROW_WORDS + w_pad, n_pad), dtype=np.uint32)
    cols_in[0, n:] = np.uint32(0xFFFFFFFF)   # PAD_SENTINEL key_len
    cols_in[1, n:] = np.uint32(0xFFFFFFFF)   # PAD_SENTINEL doc_key_len
    cols_in[_ROW_WORDS:, n:] = np.uint32(0xFFFFFFFF)   # pad keys: last
    pos = 0
    for n_b, sw, body in zip(rfb.counts, rfb.strides_w, rfb.bodies):
        n_b = int(n_b)
        sw = int(sw)
        sl = slice(pos, pos + n_b)
        ks = n_b * sw * 4                      # key-slab bytes
        kv = np.frombuffer(body, dtype="<u4",
                           count=n_b * sw).reshape(n_b, sw)
        cols_in[_ROW_WORDS: _ROW_WORDS + sw, sl] = kv.T
        cols_in[0, sl] = np.frombuffer(body, dtype="<u2", count=n_b,
                                       offset=ks)
        cols_in[1, sl] = np.frombuffer(body, dtype="<u2", count=n_b,
                                       offset=ks + 2 * n_b)
        cols_in[2, sl] = np.frombuffer(body, dtype="<u4", count=n_b,
                                       offset=ks + 4 * n_b)
        cols_in[3, sl] = np.frombuffer(body, dtype="<u4", count=n_b,
                                       offset=ks + 8 * n_b)
        cols_in[4, sl] = np.frombuffer(body, dtype="<u4", count=n_b,
                                       offset=ks + 12 * n_b)
        cols_in[5, sl] = np.frombuffer(body, dtype=np.uint8, count=n_b,
                                       offset=ks + 16 * n_b)
        # the ttl region is 8*n bytes at a possibly-odd alignment: read
        # through an aligned u8 copy, then de-interleave the i64 limbs
        t = np.frombuffer(body, dtype=np.uint8, count=8 * n_b,
                          offset=ks + 17 * n_b).copy().view("<u4")
        cols_in[6, sl] = t[0::2]
        cols_in[7, sl] = t[1::2]
        pos += n_b

    device_faults.maybe_fault("dispatch")
    donate = _donation_supported()
    fn = _block_decode_fused_donated if donate else _block_decode_fused

    def _dispatch():
        # fresh uploads each dispatch: the donated variant consumed the
        # previous input matrix, but the host array is intact
        ci = (jax.device_put(cols_in, device) if device is not None
              else jnp.asarray(cols_in))
        return fn(ci, jnp.int32(n))

    cols, is_const_d, first_d = _dispatch()
    try:
        device_faults.maybe_fault("result")
        is_const = np.asarray(is_const_d)
        first = np.asarray(first_d)
    except Exception as e:  # noqa: BLE001 — device-fault containment
        if not device_faults.is_device_fault(e):
            raise
        # one retry of the same (jit-cached) launch, like the merge
        # handle's relaunch; a second failure takes the native fallback
        from yugabyte_tpu.ops.run_merge import _chunk_retry_counter
        from yugabyte_tpu.utils.trace import TRACE
        _chunk_retry_counter().increment()
        TRACE("block_codec: device fault at decode download (%r) — "
              "retrying the launch once", e)
        try:
            cols, is_const_d, first_d = _dispatch()
            device_faults.maybe_fault("result")
            is_const = np.asarray(is_const_d)
            first = np.asarray(first_d)
        except Exception as e2:  # noqa: BLE001 — post-retry containment
            if device_faults.is_device_fault(e2):
                # retry exhausted: park the decode bucket before the
                # fault unwinds to the job-level native fallback
                _board.record_fault(
                    "block_decode", (1, n_pad),
                    reason=f"decode {type(e2).__name__}: {e2}")
            raise
    sort_rows, n_sort = build_sort_schedule(w_pad, is_const)
    record_kernel_dispatch("kernel_block_decode", n, n_pad)
    _board.record_device("block_decode", (1, n_pad), n,
                         sp.elapsed_ms() / 1e3)
    codec_metrics()["decode_blocks"].increment(len(rfb.bodies))
    return StagedCols(cols, sort_rows, n_sort, n, n_pad, w_pad,
                      is_const, first)


def encode_span(st: StagedCols, n_rows: int, w_out: int, values,
                block_entries: int, compress: bool):
    """Assemble the finished block bytes of one survivor span.

    st: the span's gathered cols (device); n_rows real rows; w_out the
    output key stride in words (max real input stride — the native
    shell's rule, so files stay byte-identical); values: the span's
    host-side value rows (tombstone rewrite already applied).
    Returns (blocks, index_items, bloom_hashes, first_key, last_key) in
    the exact write_base_file vocabulary."""
    from yugabyte_tpu.storage.bucket_health import health_board
    from yugabyte_tpu.utils.metrics import pipeline_span
    _board = health_board()
    if not _board.allow_device("block_encode", (1, st.n_pad)):
        # parked encode bucket: the job unwinds its partial outputs and
        # re-runs through the native byte shell, byte-identically
        raise BlockCodecUnsupported("encode bucket parked by the "
                                    "health board")
    with pipeline_span("encode") as sp:
        return _encode_span(st, n_rows, w_out, values, block_entries,
                            compress, _board, sp)


def _encode_span(st: StagedCols, n_rows: int, w_out: int, values,
                 block_entries: int, compress: bool, _board, sp):
    import zlib as _zlib
    from yugabyte_tpu.ops import device_faults
    from yugabyte_tpu.utils.metrics import record_kernel_dispatch
    device_faults.maybe_fault("dispatch")

    def _download():
        # device-side row slicing before the D2H: only the real rows and
        # the real output stride cross the link, not the pad tail
        (keys_d, kl2, dkl2, ht_hi_d, ht_lo_d, wid_d, fl4, ttl_d,
         h_hi_d, h_lo_d) = _block_encode_fused(st.cols_dev)
        device_faults.maybe_fault("result")
        # contiguous: a TPU array's host copy keeps the device's tiled
        # strides, and the byte view below needs a contiguous last axis
        return (np.ascontiguousarray(keys_d[:n_rows, :w_out]),
                np.asarray(kl2[: (n_rows + 1) // 2]),
                np.asarray(dkl2[: (n_rows + 1) // 2]),
                np.asarray(ht_hi_d[:n_rows]),
                np.asarray(ht_lo_d[:n_rows]),
                np.asarray(wid_d[:n_rows]),
                np.asarray(fl4[: (n_rows + 3) // 4]),
                np.asarray(ttl_d[:, :n_rows]),
                np.asarray(h_hi_d[:n_rows]),
                np.asarray(h_lo_d[:n_rows]))

    try:
        outs = _download()
    except Exception as e:  # noqa: BLE001 — device-fault containment
        if not device_faults.is_device_fault(e):
            raise
        # retry-once: the span cols are NOT donated (the write-through
        # install reads them after this), so re-dispatch is legal
        from yugabyte_tpu.ops.run_merge import _chunk_retry_counter
        from yugabyte_tpu.utils.trace import TRACE
        _chunk_retry_counter().increment()
        TRACE("block_codec: device fault at encode download (%r) — "
              "retrying the launch once", e)
        try:
            outs = _download()
        except Exception as e2:  # noqa: BLE001 — post-retry containment
            if device_faults.is_device_fault(e2):
                _board.record_fault(
                    "block_encode", (1, st.n_pad),
                    reason=f"encode {type(e2).__name__}: {e2}")
            raise
    keys, kl2, dkl2, ht_hi, ht_lo, wid, fl4, ttl, h_hi, h_lo = outs
    keys_u8 = keys.view(np.uint8).reshape(n_rows, w_out * 4)
    kl = kl2.view("<u2")[:n_rows]
    dkl = dkl2.view("<u2")[:n_rows]
    fl = fl4.view(np.uint8)[:n_rows]
    # ttl rows are [hi20, lo] — the pack_cols 20/32 microsecond split
    ttl_us = ((ttl[0].astype(np.uint64) << np.uint64(20))
              | ttl[1].astype(np.uint64))
    ttl_ms = (ttl_us // np.uint64(1000)).astype("<i8")

    hashes = (h_hi.astype(np.uint64) << np.uint64(32)) \
        | h_lo.astype(np.uint64)

    def key_at(i: int) -> bytes:
        return keys_u8[i, : int(kl[i])].tobytes()

    blocks: List[bytes] = []
    index_items: List[Tuple[bytes, int, int, int]] = []
    data_off = 0
    voffs = values.offsets
    for s in range(0, n_rows, block_entries):
        e = min(s + block_entries, n_rows)
        vo = (voffs[s: e + 1] - voffs[s]).astype("<u4")
        body = b"".join([
            keys_u8[s:e].tobytes(),
            kl[s:e].tobytes(), dkl[s:e].tobytes(),
            ht_hi[s:e].tobytes(), ht_lo[s:e].tobytes(),
            wid[s:e].tobytes(), fl[s:e].tobytes(),
            ttl_ms[s:e].tobytes(), vo.tobytes(),
            values.data[voffs[s]: voffs[e]].tobytes(),
        ])
        raw_len = len(body)
        bflags = 0
        stored = body
        if compress:
            c = _zlib.compress(body, 1)
            if len(c) < raw_len:
                stored = c
                bflags = 1
        header = block_format._HEADER.pack(
            block_format.BLOCK_MAGIC, e - s, w_out * 4, bflags,
            len(stored), raw_len)
        crc = _zlib.crc32(header[4:] + stored)
        blk = header + stored + np.uint32(crc).tobytes()
        blocks.append(blk)
        index_items.append((key_at(e - 1), data_off, len(blk), e - s))
        data_off += len(blk)
    first_key = key_at(0) if n_rows else b""
    last_key = key_at(n_rows - 1) if n_rows else b""
    record_kernel_dispatch("kernel_block_encode", n_rows, st.n_pad)
    _board.record_device("block_encode", (1, st.n_pad), n_rows,
                         sp.elapsed_ms() / 1e3)
    codec_metrics()["encode_blocks"].increment(len(blocks))
    return blocks, index_items, hashes, first_key, last_key


# ---------------------------------------------------------------------------
# Prewarm (PrewarmKernelsOp folds this into the startup compile pass)
# ---------------------------------------------------------------------------

# (n_pad, w_pad) lattice the manifest declares: the flush-sized and
# once-compacted row buckets of _PREWARM_SHAPES at the default key width
_PREWARM_DECODE = ((1 << 16, 4), (1 << 18, 4))


def prewarm_block_codec() -> Prewarm:
    """Ahead-of-traffic compile of the codec buckets (mirrors
    run_merge.prewarm_buckets; called by PrewarmKernelsOp)."""
    from yugabyte_tpu.ops.run_merge import _donation_supported
    pw = Prewarm("block_codec")
    sdt = jax.ShapeDtypeStruct
    donate = _donation_supported()
    fn = _block_decode_fused_donated if donate else _block_decode_fused
    for n_pad, w_pad in _PREWARM_DECODE:
        pw.warm(f"block_decode (n_pad={n_pad} w_pad={w_pad})",
                lambda: fn.lower(*decode_avals(n_pad, w_pad)).compile())
        pw.warm(f"block_encode (n_pad={n_pad} w_pad={w_pad})",
                lambda: _block_encode_fused.lower(
                    sdt((_ROW_WORDS + w_pad, n_pad), jnp.uint32)).compile())
    return pw


def decode_avals(n_pad: int, w_pad: int):
    """The decode program's abstract arg shapes for one (n_pad, w_pad)
    bucket — shared by prewarm and the manifest generator so they can
    never drift apart."""
    sdt = jax.ShapeDtypeStruct
    return (sdt((_ROW_WORDS + w_pad, n_pad), jnp.uint32),
            sdt((), jnp.int32))
