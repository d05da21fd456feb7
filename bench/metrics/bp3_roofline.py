"""The BP3 stage's share of its roofline, in %: the least time the chip
could take for BP3's work on the elements of the traced window (the
operations and bytes of ``programs/ceed_bp3.py``, at the peaks of
``peaks.json``), over the device's busy time summed over the cell's
chips.  The one fused stage is all the device runs for the operator:
its contractions, the slices of each batch out of the resident field
and the reduction of the outputs are all in that time."""
from bench import work
from bench.programs import ceed_bp3


def read(r):
    busy = sum(r.trace["busy_s_by_chip"].values())
    if not busy:
        return None
    least = r.window.elements * work.roofline_seconds(
        ceed_bp3.flops_per_element(r.p), ceed_bp3.bytes_per_element(r.p),
        r.peaks)
    return 100.0 * least / busy
