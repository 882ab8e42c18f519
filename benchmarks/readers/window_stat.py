"""`window_stat`: a number the driver itself takes over the whole window of
the traced run, on the host's clock (spec key `stat` names it). For a
statistic that a user would see but that is too unsteady from run to run to
carry a bound as an end-to-end metric. A window without it gives None."""


def read(spec: dict, observed: dict):
    return observed["window"].get(spec["stat"])
