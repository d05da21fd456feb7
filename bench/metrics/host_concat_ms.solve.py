"""Host time per batch in ``run_chain``'s concatenation of the outputs
it brought back, in ms: ``concat`` spans, one a call, summed over the
window and divided by its batches.  Nothing where outputs stay on the
device."""


def read(r):
    spans = r.spans.closed("concat")
    if not spans or not r.window.batches:
        return None
    return 1e3 * sum(s.duration for s in spans) / r.window.batches
