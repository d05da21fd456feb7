"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips, in % (``trace_reduce``), in the BP3 cell."""


def read(r):
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
