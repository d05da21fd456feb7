"""The fused inverse Helmholtz kernel's share of its own roofline, in %:
the least time the chip could take for the kernel's work on the
elements of the traced window (the paper's Eq. 2 operations, u and D in
and v out; ``work.py``, ``peaks.json``), over the device time of the
operations the kernel runs as (``pallas_call`` name ``inverse_helmholtz``),
summed over the cell's chips.  Nothing where the kernel did not run."""
import re

from bench import work

KERNEL = re.compile(r"^%inverse_helmholtz(\.\d+)?$")


def read(r):
    seconds = sum(s for name, s in r.trace["op_seconds"].items()
                  if KERNEL.match(name))
    if not seconds:
        return None
    least = r.window.elements * work.roofline_seconds(
        work.inverse_helmholtz_flops(r.p), work.stream_bytes(r.p, 3),
        r.peaks)
    return 100.0 * least / seconds
