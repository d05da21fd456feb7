"""Host time spent dispatching stage programs (``dispatch`` spans, all
stages) per batch of the window, in ms."""


def read(r):
    spans = r.spans.closed("dispatch")
    if not spans or not r.window.batches:
        return None
    return 1e3 * sum(s.duration for s in spans) / r.window.batches
