"""How far the planner's price of a batch (``plan.cost.t_pipelined``)
lies from the measured time per batch of the traced window, as a share
of the measured time, in %."""


def read(r):
    if not r.window.batches or not r.plan_s_per_batch:
        return None
    measured = r.window.wall_s / r.window.batches
    return 100.0 * abs(measured - r.plan_s_per_batch) / measured
