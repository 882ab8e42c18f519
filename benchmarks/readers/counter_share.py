"""`counter_share`: a ratio of deltas over the traced span, of the program's
own counters (benchmarks/program.counters) and the driver's own notes
(names starting `bench_`). Spec keys: `numerator` and `denominator` (lists
of names, summed), `scale` (100 for a percentage). Nothing to read — a
missing name or a denominator that did not move — returns None."""


def read(spec: dict, observed: dict):
    values = observed["deltas"]
    try:
        num = sum(values[n] for n in spec["numerator"])
        den = sum(values[n] for n in spec["denominator"])
    except KeyError:
        return None
    if den <= 0:
        return None
    return float(spec.get("scale", 1)) * num / den
