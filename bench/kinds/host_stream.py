"""``host_stream``: the mesh lives in host memory and streams through the
chip.  A pool of ``batches_per_call`` batches of the plan's E elements
is made once from the seed in set-up; each call is
``system.run(inputs=pool, collect_outputs=True)``, so every output
stream comes back to the host.  Compared: ``SAMPLE_PER_BATCH`` rows of
every batch of every window call, from that call's own outputs."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import traffic


class Kind(traffic.Kind):
    def setup(self) -> None:
        self.make_inputs()
        # every shape the window uses: each stage program at E, the
        # slicing and the transfers back
        self._call(None, inputs=self.pool, n_eq=self.n, max_batches=2,
                   collect_outputs=True)

    def make_inputs(self) -> None:
        n = self.mix["batches_per_call"] * self.E
        seeds = self._input_seeds()
        by_var = {
            var: np.asarray(traffic.uniform_on_device(
                seeds[var], (n,) + self.elem_shape))
            for var in self.program.ELEMENT_INPUTS
        }
        self.pool = {q: by_var[var] for q, var in self.inputs.items()}
        self.n = n
        self._got: Dict[str, List[np.ndarray]] = {
            v: [] for v in self.program.OUTPUTS}
        self._rows: List[np.ndarray] = []

    def batches(self):
        """The window's inputs by variable, one batch of E rows at a time
        (for the control, at the cell's own batch size)."""
        for lo in range(0, self.n, self.E):
            yield {var: self.pool[q][lo:lo + self.E]
                   for q, var in self.inputs.items()}

    def window(self, seconds: float, tracer=None) -> traffic.Window:
        calls = batches = elements = 0
        call_s = []
        t0 = time.perf_counter()
        while True:
            tc = time.perf_counter()
            res = self._call(tracer, inputs=self.pool, n_eq=self.n,
                             collect_outputs=True)
            call_s.append(time.perf_counter() - tc)
            rows = traffic.sample_rows(self.sample_rng, res.batches, self.E)
            for q, var in self.outputs.items():
                self._got[var].append(res.outputs[q][rows])
            self._rows.append(rows)
            calls += 1
            batches += res.batches
            elements += res.elements
            del res
            if time.perf_counter() - t0 >= seconds:
                break
        return traffic.Window(time.perf_counter() - t0, calls, batches,
                              elements, call_s=call_s)

    def samples(self):
        rows = np.concatenate(self._rows)
        ins = {var: self.pool[q][rows] for q, var in self.inputs.items()}
        outs = {v: np.concatenate(a) for v, a in self._got.items()}
        return self.shared, ins, outs

    def free(self) -> None:
        self.pool = None
