"""The whole run's share of the chips' peak FLOP/s over the traced
window, in %: the elements completed in it times the program's
operations per element (``work.py``), over the window, the chips and the
peak (``peaks.json``).  It bounds every kernel's gain, busy or idle."""


def read(r):
    flops = r.window.elements * r.flops_per_element
    return 100.0 * flops / (r.trace["window_s"] * r.chips
                            * r.peaks["flops_per_s"])
