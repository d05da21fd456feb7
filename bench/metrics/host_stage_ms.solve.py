"""Mean host staging span per batch, in ms: ``stage-host``, the
``StagePipelineDriver``'s device_put of one batch's host streams."""


def read(r):
    spans = r.spans.closed("stage-host")
    if not spans:
        return None
    return 1e3 * sum(s.duration for s in spans) / len(spans)
