"""Host time per batch in ``StagePipelineDriver``'s retire, in ms:
``retire`` spans, each the ``reduce_fn`` of one batch -- its outputs
brought back to the host when the traffic collects them (the ``wait``
and ``transfer`` inside), a reduction dispatched on the device
otherwise -- summed over the window and divided by its batches.
Nothing where the program emits no ``retire`` span."""


def read(r):
    spans = r.spans.closed("retire")
    if not spans or not r.window.batches:
        return None
    return 1e3 * sum(s.duration for s in spans) / r.window.batches
