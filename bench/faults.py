"""Faults planted in the timed path underneath a run, to see the
comparison that decides ``correct`` come out false.

Each entry of :data:`FAULTS` is a context manager that breaks the
program while it is open and restores it after.  The benchmark's tests
run whole cells on the CPU under each (``tests/test_check.py``), and
``fault_readings.py`` reads on the chip, at a cell's own size, the
numbers a fault gives.  The benchmark's own runs never plant one.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict


def answer_altered(v):
    """One element's outputs moved by 1."""
    return v.at[0].add(1.0)


def half_the_batch_left_out(v):
    return v.at[v.shape[0] // 2:].set(0.0)


@contextlib.contextmanager
def outputs_broken(fault: Callable):
    """``fault`` applied to every output stream where the chain's last
    stage hands it on, in every call, collected or not."""
    from repro.cfd import simulation

    orig = simulation.chain_stage_fns

    def chain_stage_fns(chain, shared_for_stage, out_names, **kw):
        fns = orig(chain, shared_for_stage, out_names, **kw)
        last = fns[-1]

        def run_last(staged, carry):
            live = last(staged, carry)
            return {q: fault(v) if q in out_names else v
                    for q, v in live.items()}

        return fns[:-1] + [run_last]

    simulation.chain_stage_fns = chain_stage_fns
    try:
        yield
    finally:
        simulation.chain_stage_fns = orig


@contextlib.contextmanager
def device_sums_broken(make_fault: Callable):
    """The on-device reduction of each batch's outputs, which the calls
    that keep their outputs on the device run, replaced by
    ``make_fault()``: outputs brought back to the host are untouched."""
    import jax.numpy as jnp

    from repro.cfd import simulation

    class Numpy:
        def __init__(self, fault):
            self.sum = fault

        def __getattr__(self, name):
            return getattr(jnp, name)

    orig = simulation.jnp
    simulation.jnp = Numpy(make_fault())
    try:
        yield
    finally:
        simulation.jnp = orig


def _sum_of(fault: Callable):
    import jax.numpy as jnp

    return lambda: (lambda v: jnp.sum(fault(v)))


def _drifting_sum():
    """Each batch's sum a little further off than the one before, as
    when an accumulator is not cleared between calls."""
    import jax.numpy as jnp

    taken = [0]

    def drifting(v):
        taken[0] += 1
        return jnp.sum(v) + 1e-3 * taken[0]

    return drifting


FAULTS: Dict[str, Callable] = {
    "answer_altered": lambda: outputs_broken(answer_altered),
    "half_the_batch_left_out":
        lambda: outputs_broken(half_the_batch_left_out),
    "device_sum_altered":
        lambda: device_sums_broken(_sum_of(answer_altered)),
    "device_sum_half":
        lambda: device_sums_broken(_sum_of(half_the_batch_left_out)),
    "device_sum_drifts": lambda: device_sums_broken(_drifting_sum),
}
