"""``resident_qdata``: ``hbm_resident`` for a program whose element
streams differ in shape, such as quadrature data (geometric factors at
q^3 points) beside a solution at p^3 nodes.  Each element input is made
on the device at the per-element shape of its chain stream; everything
else -- the whole field swept once per call, outputs reduced on the
device, ``checksum_err`` and ``sweeps_differ`` after the window -- is
the parent kind's."""
from __future__ import annotations

import jax

from bench import traffic

Resident = traffic.load_kind("hbm_resident")


class Kind(Resident):
    def make_inputs(self) -> None:
        chain = self.system.chain
        shape = {
            n: tuple(node.shape)
            for i in range(len(chain.stages))
            for n, node in chain.host_element_inputs(i)
        }
        n = self.cfg["n_eq"]
        seeds = self._input_seeds()
        by_var = {var: traffic.uniform_on_device(seeds[var],
                                                 (n,) + shape[var])
                  for var in self.program.ELEMENT_INPUTS}
        self.field = {q: by_var[var] for q, var in self.inputs.items()}
        jax.block_until_ready(self.field)
        self.n = n
        self._checksums = []
        self._checks = {}
