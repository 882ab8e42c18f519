"""`trace`: a quantity of the trace reduction (benchmarks/trace_reduce.py),
some per unit of the work the driver noted for the traced span. Spec key
`quantity` names one of QUANTITIES. A trace in which no device operation
ran, or a span with no noted work, gives None — never 0."""

from benchmarks import roofline


def idle_share_pct(red, notes, device_kind):
    if red["idle_share"] is None:
        return None
    return 100.0 * red["idle_share"]


def busy_ms_per_mrow(red, notes, device_kind):
    rows = notes.get("bench_rows_in", 0)
    if not rows or red["busy_s"] <= 0:
        return None
    return red["busy_s"] * 1e3 / (rows / 1e6)


def bytes_roofline_pct(red, notes, device_kind):
    """Least seconds for the noted bytes at the chip's peak bytes/s, over
    the device's busy seconds in the same span."""
    n_bytes = notes.get("bench_min_device_bytes", 0)
    if not n_bytes or red["busy_s"] <= 0:
        return None
    return 100.0 * roofline.bytes_bound_s(n_bytes, device_kind) \
        / red["busy_s"]


QUANTITIES = {"idle_share_pct": idle_share_pct,
              "busy_ms_per_mrow": busy_ms_per_mrow,
              "bytes_roofline_pct": bytes_roofline_pct}


def read(spec: dict, observed: dict):
    red = observed.get("trace")
    if red is None:
        return None
    return QUANTITIES[spec["quantity"]](red, observed["deltas"],
                                        observed["device_kind"])
