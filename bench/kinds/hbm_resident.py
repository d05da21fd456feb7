"""``hbm_resident``: every element input of the configuration's ``n_eq``
elements lives in HBM, made on the device from the seed.  Each call
sweeps the whole field with ``system.run(inputs=field)``, as a
time-stepping solver applies its operator each step, and the outputs are
reduced on the device: the window's answers are each call's checksums.

Compared, after the window:

* ``SAMPLE_PER_BATCH`` rows of every batch of one more call over the
  same field, with its outputs brought back (``check.py``);
* ``checksum_err`` -- the widest gap, over every window call and output,
  between the call's checksum and the float64 sum of that output brought
  back, as a share of the sum of its magnitudes.  Every call sweeps the
  same field with the same programs, so each window call has to add up
  the outputs that were compared;
* ``sweeps_differ`` -- the window calls whose checksums are not bitwise
  those of the first: the same programs on the same field give the same
  sums, so this is an exact comparison.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from bench import traffic

#: Rows per block of the host sums, so that they need no second copy.
SUM_BLOCK = 1 << 15


def sums(a: np.ndarray):
    """The float64 sum of ``a`` and of its magnitudes."""
    total = mag = 0.0
    for lo in range(0, len(a), SUM_BLOCK):
        block = a[lo:lo + SUM_BLOCK]
        total += float(np.sum(block, dtype=np.float64))
        mag += float(np.sum(np.abs(block), dtype=np.float64))
    return total, mag


class Kind(traffic.Kind):
    def setup(self) -> None:
        self.make_inputs()
        # one whole sweep: the stage programs and every batch's slice
        self._sweep(None)

    def make_inputs(self) -> None:
        n = self.cfg["n_eq"]
        seeds = self._input_seeds()
        by_var = {var: traffic.uniform_on_device(
                      seeds[var], (n,) + self.elem_shape)
                  for var in self.program.ELEMENT_INPUTS}
        self.field = {q: by_var[var] for q, var in self.inputs.items()}
        jax.block_until_ready(self.field)
        self.n = n
        self._checksums: List[Dict[str, float]] = []
        self._checks: Dict[str, float] = {}

    def batches(self):
        for lo in range(0, self.n - self.E + 1, self.E):
            yield {var: self.field[q][lo:lo + self.E]
                   for q, var in self.inputs.items()}

    def _sweep(self, tracer, collect: bool = False):
        return self._call(tracer, inputs=self.field, n_eq=self.n,
                          collect_outputs=collect)

    def window(self, seconds: float, tracer=None) -> traffic.Window:
        calls = batches = elements = failed = 0
        call_s = []
        t0 = time.perf_counter()
        while True:
            tc = time.perf_counter()
            res = self._sweep(tracer)
            call_s.append(time.perf_counter() - tc)
            calls += 1
            batches += res.batches
            elements += res.elements
            failed += sum(1 for v in res.checksums.values()
                          if not np.isfinite(v))
            self._checksums.append(dict(res.checksums))
            if time.perf_counter() - t0 >= seconds:
                break
        return traffic.Window(time.perf_counter() - t0, calls, batches,
                              elements, failed, call_s=call_s)

    def samples(self):
        res = self._sweep(None, collect=True)
        rows = traffic.sample_rows(self.sample_rng, res.batches, self.E)
        outs = {var: res.outputs[q][rows] for q, var in self.outputs.items()}
        gap = 0.0
        for q in self.outputs:
            total, mag = sums(res.outputs[q])
            for cs in self._checksums:
                gap = max(gap, abs(cs[q] - total) / mag
                          if np.isfinite(cs[q]) else float("inf"))
        first = self._checksums[0]
        self._checks = {
            "checksum_err": gap,
            "sweeps_differ": float(sum(cs != first
                                       for cs in self._checksums[1:])),
        }
        del res
        # the sampled rows' inputs, batch by batch through the host: a
        # gather on the device would relayout the whole field first
        ins = {var: [] for var in self.inputs.values()}
        for b, batch in enumerate(self.batches()):
            mine = rows[(rows >= b * self.E) & (rows < (b + 1) * self.E)]
            for var, a in batch.items():
                ins[var].append(np.asarray(a)[mine - b * self.E])
        return self.shared, traffic.ins_cat(ins), outs

    def checks(self) -> Dict[str, float]:
        return dict(self._checks)

    def free(self) -> None:
        self.field = None
