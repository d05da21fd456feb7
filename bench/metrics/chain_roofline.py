"""The stage programs' share of their roofline, in %: the least time the
chip could take for the program's work on the elements of the traced
window (``work.py`` counts, ``peaks.json``), over the device's busy time
summed over the cell's chips.  Everything the device runs for the chain
is in that time: kernels, relayouts, slices and reductions."""
from bench import work


def read(r):
    least = r.window.elements * work.roofline_seconds(
        r.flops_per_element, r.bytes_per_element, r.peaks)
    return 100.0 * least / sum(r.trace["busy_s_by_chip"].values())
