"""Host time per batch in ``run_chain``'s retire and sync, in ms: inside
each ``run`` span (the driver's loop over one call's batches), the time
that no staging span (``stage-host``) or dispatching span (``slot``,
``dispatch``, ``handoff``) covers.  That is the driver retiring each
batch -- its ``reduce_fn``, which brings the batch's whole outputs to the
host when the traffic collects them and dispatches a reduction on the
device otherwise -- and its ``sync`` spans, each waiting for a batch's
result, with the loop's own bookkeeping and its slicing of each batch
out of the call's inputs."""
import bisect

STAGING_OR_DISPATCH = ("stage-host", "slot", "dispatch", "handoff")


def read(r):
    runs = r.spans.closed("run")
    if not runs or not r.window.batches:
        return None
    busy = sorted((s.t0, s.t1) for s in r.spans.spans
                  if s.cat in STAGING_OR_DISPATCH and s.t1 >= 0)
    starts = [a for a, _ in busy]
    retire = 0.0
    for run in runs:
        covered, end = 0.0, run.t0
        for a, b in busy[bisect.bisect_left(starts, run.t0):
                         bisect.bisect_right(starts, run.t1)]:
            a, b = max(a, end), min(b, run.t1)
            if b > a:
                covered += b - a
                end = b
        retire += run.duration - covered
    return 1e3 * retire / r.window.batches
