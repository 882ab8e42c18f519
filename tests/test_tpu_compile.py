"""AOT compiles for a described TPU v5e: every kernel family of the main
path, lowered for a chip that is described and not attached, at the
smallest real bucket (the (2, 2^16) merge bucket of _PREWARM_SHAPES).

What interpret mode and the CPU backend cannot show — a slice the tiling
refuses, a kernel over its VMEM budget, a program that does not fit HBM —
the TPU compiler raises here, at no chip time. Nothing runs: a passing
compile is not a chip run.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports this file), and every compile happens in this process
with the persistent compilation cache off (an executable for a described
device cannot be read back without the chip).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

_ROW_WORDS = 8
_W = 4            # default quantized key width (words)
_N_CMP = 8        # full compare schedule at w=4 on the n_cmp lattice
sdt = jax.ShapeDtypeStruct
_u32 = sdt((), jnp.uint32)
_i32 = sdt((), jnp.int32)
_b1 = sdt((), jnp.bool_)


def _cols(n):
    return sdt((_ROW_WORDS + _W, n), jnp.uint32)


def _run_merge_network(k_pad, m, is_major=True):
    from yugabyte_tpu.ops import run_merge
    n = k_pad * m
    return run_merge._merge_gc_runs_fused, (_cols(n), sdt((_N_CMP,), jnp.int32), sdt((n,), jnp.int32),
                _u32, _u32, _u32, _u32), dict(
        k_pad=k_pad, m=m, w=_W, n_cmp=_N_CMP, is_major=is_major,
        retain_deletes=False, snapshot=False, lexsort=False)


def _pallas_merge(k_pad, m, is_major=True):
    from yugabyte_tpu.ops import pallas_merge, run_merge
    r = _ROW_WORDS + _W
    n = k_pad * m
    cmp_rows, _ = run_merge._cmp_schedule(_W, np.zeros(r, dtype=bool))
    rp = ((r + 1 + 7) // 8) * 8
    return pallas_merge._pallas_merge_gc_fused, (
        _cols(n), sdt((n,), jnp.int32), _u32, _u32, _u32, _u32), dict(
        k_pad=k_pad, m=m, w=_W, cmp_rows_t=tuple(int(x) for x in cmp_rows),
        tile=min(pallas_merge.default_tile(rp), m), is_major=is_major,
        retain_deletes=False, snapshot=False, interpret=False)


def _merge_gc_fused(n_pad):
    from yugabyte_tpu.ops import merge_gc
    return merge_gc._merge_gc_fused, (
        _cols(n_pad), sdt((4 + _W,), jnp.int32), _i32,
        _u32, _u32, _u32, _u32), dict(
        w=_W, is_major=True, retain_deletes=False)


def _scan_fused(n_pad):
    from yugabyte_tpu.ops import scan
    return scan._scan_fused, (
        _cols(n_pad), sdt((4 + _W,), jnp.int32), _i32,
        _u32, _u32, _u32, _u32,
        sdt((_W,), jnp.uint32), _i32, sdt((_W,), jnp.uint32), _i32), dict(
        w=_W, has_lower=False, has_upper=False, upper_truncated=False)


def _pushdown_args(n_pad, p_pad, has_vals=True):
    from yugabyte_tpu.ops.scan import _VAL_ROWS, VAL_WORDS
    return (_cols(n_pad),
            sdt((_VAL_ROWS, n_pad if has_vals else 1), jnp.uint32),
            sdt((4 + _W,), jnp.int32), _i32, _u32, _u32, _u32, _u32,
            sdt((_W,), jnp.uint32), _i32, sdt((_W,), jnp.uint32), _i32,
            _b1, _b1,
            sdt((p_pad,), jnp.uint32), sdt((p_pad,), jnp.int32),
            sdt((p_pad,), jnp.int32),
            sdt((p_pad,), jnp.uint32), sdt((p_pad,), jnp.uint32),
            sdt((p_pad, VAL_WORDS), jnp.uint32), sdt((p_pad,), jnp.int32))


def _scan_filtered(n_pad, presorted=True):
    from yugabyte_tpu.ops import scan
    p_pad = scan.PRED_SLOTS[0]
    return scan._scan_filtered_fused, _pushdown_args(n_pad, p_pad), dict(
        w=_W, p_pad=p_pad, presorted=presorted)


def _scan_agg(n_pad, presorted=True):
    from yugabyte_tpu.ops import scan
    p_pad, c_pad = scan.PRED_SLOTS[0], scan.AGG_SLOTS[0]
    aggs = tuple(sdt((c_pad,), jnp.uint32) for _ in range(3))
    return scan._scan_agg_fused, _pushdown_args(n_pad, p_pad) + aggs, dict(
        w=_W, p_pad=p_pad, c_pad=c_pad, has_vals=True, presorted=presorted)


def _fnv64(b=1024):
    from yugabyte_tpu.ops import point_read
    return point_read._fnv64_fused, (
        sdt((b, _W), jnp.uint32), sdt((b,), jnp.int32)), dict(w=_W)


def _bloom_probe(b=1024, m_words=1 << 14):
    from yugabyte_tpu.ops import point_read
    return point_read._bloom_probe_fused, (
        sdt((b,), jnp.uint32), sdt((b,), jnp.uint32),
        sdt((m_words,), jnp.uint32), _u32, _i32), {}


def _locate(n_pad, b=1024, use_model=True):
    from yugabyte_tpu.ops import point_read
    from yugabyte_tpu.storage.learned_index import LINDEX_SEGMENTS
    seg = LINDEX_SEGMENTS + 1
    return point_read._locate_gather_fused, (
        _cols(n_pad), _i32, sdt((b, _W), jnp.uint32), sdt((b,), jnp.int32),
        _u32, _u32, sdt((seg,), jnp.uint32), sdt((seg,), jnp.uint32),
        sdt((seg,), jnp.int32), _i32, _i32), dict(w=_W, use_model=use_model)


def _index_fit(n_pad):
    from yugabyte_tpu.ops import point_read
    from yugabyte_tpu.storage.learned_index import LINDEX_SEGMENTS
    return point_read._index_fit_fused, (_cols(n_pad), _i32), dict(
        n_segments=LINDEX_SEGMENTS, w=_W)


def _block_decode(n_pad):
    from yugabyte_tpu.ops import block_codec
    return block_codec._block_decode_fused, \
        block_codec.decode_avals(n_pad, _W), {}


def _block_encode(n_pad):
    from yugabyte_tpu.ops import block_codec
    return block_codec._block_encode_fused, (_cols(n_pad),), {}


def _survivor_positions(n):
    from yugabyte_tpu.ops import run_merge
    return run_merge._survivor_positions, (sdt((n,), jnp.bool_),), {}


def _scan_group_agg(n_pad, presorted=True):
    """The typed, grouped aggregate at Q1's shape class."""
    from yugabyte_tpu.ops import scan_group as sg
    from yugabyte_tpu.ops.scan import VAL_WORDS
    c_pad, t_pad = sg.SHAPE_CLASSES[-1]
    p, f = sg.PRED_PAD, sg.MAX_FACTORS
    u32, i32 = jnp.uint32, jnp.int32
    return sg._scan_group_agg_fused, _pushdown_args(n_pad, 1)[:14] + (
        sdt((c_pad,), u32), sdt((p,), i32), sdt((p,), i32), sdt((p,), u32),
        sdt((p,), u32), sdt((p, VAL_WORDS), u32), sdt((p,), i32),
        sdt((2,), i32), sdt((t_pad, f), i32), sdt((t_pad, f), i32),
        sdt((t_pad, f), u32), sdt((t_pad, f), u32)), dict(
        w=_W, c_pad=c_pad, t_pad=t_pad, minmax=False, presorted=presorted)


def _gather_staged(n, n_out_pad):
    from yugabyte_tpu.ops import run_merge
    return run_merge._gather_staged_output, (
        _cols(n), sdt((n,), jnp.int32), sdt((n,), jnp.int32),
        sdt((n,), jnp.bool_), _i32, _i32), dict(n_out_pad=n_out_pad)


def _restage_concat(k_pad, m):
    from yugabyte_tpu.ops import run_merge
    return run_merge._restage_concat, (
        tuple(_cols(m) for _ in range(k_pad)),
        sdt((k_pad,), jnp.int32)), dict(w=_W, m=m, k_pad=k_pad)


def _chunk_split_search(k_pad, m):
    from yugabyte_tpu.ops import run_merge
    w_route = run_merge._W_ROUTE_CHUNK
    return run_merge._chunk_split_search, (
        _cols(k_pad * m), sdt((k_pad,), jnp.int32),
        sdt((7, w_route), jnp.uint32)), dict(
        k_pad=k_pad, m=m, w_route=w_route, n_iters=int(m).bit_length() + 1)


def _carve_chunk(k_pad, m, m_c):
    from yugabyte_tpu.ops import run_merge
    return run_merge._carve_chunk, (
        _cols(k_pad * m), sdt((k_pad,), jnp.int32),
        sdt((k_pad,), jnp.int32)), dict(m=m, m_c=m_c, k_pad=k_pad)


def single_chip_specs(k_pad=2, m=1 << 16, n_scan=1 << 16):
    """name -> (jitted, abstract args, statics) for one chip, sized by
    the merge bucket (k_pad, m) and the resident scan size n_scan."""
    n = k_pad * m
    return {
        "run_merge_fused-network-major": _run_merge_network(k_pad, m),
        "run_merge_fused-network-minor": _run_merge_network(
            k_pad, m, is_major=False),
        "pallas_merge-major": _pallas_merge(k_pad, m),
        "pallas_merge-minor": _pallas_merge(k_pad, m, is_major=False),
        "merge_gc_fused": _merge_gc_fused(n_scan),
        "scan_fused": _scan_fused(n_scan),
        "scan_filtered": _scan_filtered(n_scan),
        "scan_filtered-merge": _scan_filtered(n_scan, presorted=False),
        "scan_agg": _scan_agg(n_scan),
        "scan_group_agg": _scan_group_agg(n_scan),
        "point_read_probe-fnv64": _fnv64(),
        "point_read_probe-bloom": _bloom_probe(),
        "point_read_locate": _locate(n_scan),
        "index_fit": _index_fit(n_scan),
        "block_decode": _block_decode(m),
        "block_encode": _block_encode(m),
        "gather_staged-survivor_positions": _survivor_positions(n),
        "gather_staged-output": _gather_staged(n, m),
        "restage_concat": _restage_concat(k_pad, m),
        "chunk_carve-split_search": _chunk_split_search(k_pad, m),
        "chunk_carve-carve": _carve_chunk(k_pad, m, m // 4),
    }


def place(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: sdt(a.shape, a.dtype, sharding=sharding), tree)


def compile_spec(spec, sharding):
    """Lower + compile one spec with every arg on `sharding`; returns
    (compiled, seconds)."""
    fn, args, statics = spec
    t0 = time.monotonic()
    compiled = fn.lower(*place(args, sharding), **statics).compile()
    return compiled, time.monotonic() - t0


def dist_compact_lowered(mesh, rows_per_shard, is_major=True):
    """The key-range-sharded step as the dist job launches it: cols
    sharded along dim 1, cutoff scalars replicated."""
    from yugabyte_tpu.parallel import dist_compact as dist
    n_shards = mesh.devices.size
    capacity = dist._quantized_capacity(rows_per_shard, n_shards, 2.0)
    cols = sdt((_ROW_WORDS + _W, n_shards * rows_per_shard), jnp.uint32,
               sharding=NamedSharding(mesh, P(None, "shard")))
    rep = sdt((), jnp.uint32, sharding=NamedSharding(mesh, P()))
    return dist.dist_compact_fn(mesh, capacity, is_major).lower(
        cols, rep, rep, rep, rep)


def pool_wave_lowered(mesh, k_pad, m, is_major=True):
    """The multi-tablet pool's wave program: one job per device."""
    from yugabyte_tpu.parallel import dist_compact as dist
    s = mesh.devices.size
    n = k_pad * m
    sh = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    return dist.pool_wave_fn(mesh, k_pad, m, _W, _N_CMP, is_major, False,
                             False).lower(
        sdt((s, _ROW_WORDS + _W, n), jnp.uint32, sharding=sh),
        sdt((s, _N_CMP), jnp.int32, sharding=sh),
        sdt((n,), jnp.int32, sharding=rep),
        sdt((s, 4), jnp.uint32, sharding=sh))


# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# twins of a case above (same program but for one static): compiled by
# the sizing rehearsal, left out here to keep the file near two minutes
_TWINS = {"run_merge_fused-network-minor", "scan_filtered-merge"}


@pytest.mark.parametrize("name", sorted(set(single_chip_specs()) - _TWINS))
def test_family_compiles_for_v5e(name, one_chip):
    compiled, _s = compile_spec(single_chip_specs()[name], one_chip)
    if name.startswith("pallas_merge"):
        assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


def test_dist_compact_compiles_on_four_chip_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("shard",))
    compiled = dist_compact_lowered(mesh, rows_per_shard=1 << 14).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "all-gather" in text


def test_pool_wave_compiles_on_four_chip_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("shard",))
    pool_wave_lowered(mesh, 2, 1 << 16).compile()
