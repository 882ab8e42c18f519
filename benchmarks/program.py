"""Everything the benchmark takes from the program besides the system under
test itself: its counters (one flat name -> number snapshot that the
`counter_share` readers diff), JAX's own compile events, the device's facts.

`CompileClock`, `counters()` and `ZERO_COUNTERS` are copies of
`chip_smoke.py`'s (PR 22).
"""

import os


class CompileClock:
    """Sums what JAX reports of its own compiles (cache loads included)."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


ZERO_COUNTERS = ("offload_decisions_forced_total",
                 "kernel_pallas_fallback_total",
                 "compaction_device_fallback_total",
                 "point_read_device_fallback_total",
                 "device_shadow_mismatch_total")


def counters() -> dict:
    """Flat snapshot of the program's own counters: routing and fallback
    counters, the compaction pipeline's stage milliseconds (host-clock
    slices; `device` there is wall time with transfers and waits, not busy
    time) and the serve path's per-stage histogram sums (host-clock slices
    of host stages; `device_dispatch` is host wall around the device call)."""
    from yugabyte_tpu.ops.block_codec import codec_metrics
    from yugabyte_tpu.ops.point_read import point_read_snapshot
    from yugabyte_tpu.storage import offload_policy
    from yugabyte_tpu.storage.compaction import _storage_fallback_counter
    from yugabyte_tpu.storage.integrity import shadow_mismatch_counter
    from yugabyte_tpu.utils import latency
    from yugabyte_tpu.utils.metrics import (kernel_metrics,
                                            pipeline_stage_totals)
    km = kernel_metrics()
    oc = offload_policy._offload_counters()
    pr = point_read_snapshot()
    cm = codec_metrics()
    out = {
        "offload_decisions_device_total": oc["device"].value(),
        "offload_decisions_native_total": oc["native"].value(),
        "offload_decisions_forced_total": oc["forced"].value(),
        "kernel_pallas_merge_total": km.counter(
            "kernel_pallas_merge_total", "").value(),
        "kernel_network_merge_total": km.counter(
            "kernel_network_merge_total", "").value(),
        "kernel_pallas_fallback_total": km.counter(
            "kernel_pallas_fallback_total", "").value(),
        "compaction_device_fallback_total":
            _storage_fallback_counter().value(),
        "point_read_device_fallback_total": pr["device_fallbacks"],
        "point_read_batched_keys_total": pr["batched_keys"],
        "device_shadow_mismatch_total": shadow_mismatch_counter().value(),
        "compaction_block_encode_fallback_total":
            cm["encode_fallbacks"].value(),
        "compaction_block_encode_device_total": cm["encode_blocks"].value(),
    }
    for stage, ms in pipeline_stage_totals().items():
        out[f"compaction_pipeline_stage_{stage}_total_ms"] = ms
    for op, page in latency.serve_path_attribution_page().items():
        out[f"serve_path_{op}_e2e_ms"] = float(page["e2e"].get("sum") or 0.0)
        out[f"serve_path_{op}_e2e_count"] = float(
            page["e2e"].get("count") or 0.0)
        for stage, snap in page["stages"].items():
            out[f"serve_path_{op}_{stage}_ms"] = float(snap.get("sum") or 0.0)
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def device_facts(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak on the fullest chip, as the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def steer_rehearsal() -> None:
    """`--rehearse` only: on the CPU backend let the program take the paths
    it takes on a TPU (a COLD bucket's first job goes to the device, the
    merge is the Pallas kernel in interpret mode) — what
    tests/test_chip_smoke.py steers, nothing else."""
    os.environ["YBTPU_MERGE_IMPL"] = "pallas"
    from yugabyte_tpu.storage import bucket_health
    bucket_health._on_tpu = lambda: True
    bucket_health.health_board().reset()
