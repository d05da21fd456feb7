"""Host time per batch in ``run_chain``'s checksums, in ms: ``checksum``
spans, one a call -- float64 sums of the outputs brought back, or the
sum of the batches' sums reduced on the device -- summed over the window
and divided by its batches."""


def read(r):
    spans = r.spans.closed("checksum")
    if not spans or not r.window.batches:
        return None
    return 1e3 * sum(s.duration for s in spans) / r.window.batches
