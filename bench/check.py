"""The comparison that decides ``correct``.

For every output stream of the program, the sampled rows that the timed
path produced are compared with ``reference.py`` in float64 on the same
inputs and shared operands:

    <output>_err = max |out - ref| / max |ref|   over the sampled rows

A non-finite output reads ``inf``.  A traffic kind may add numbers of its
own (``Kind.checks``), such as how far the window's on-device checksums
lie from the outputs compared here.  Each number has its own limit in the
configuration's ``limits``; ``PERF.md`` gives the readings each limit
was set from (sound runs of the program on a dozen seeds or more, and
the three-pass control of ``control.py``).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench import reference

#: Rows per block of the float64 reference, so that it fits any host.
BLOCK = 1024


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def reference_outputs(program, shared: dict, ins: dict,
                      ops=reference) -> Dict[str, np.ndarray]:
    """The program's outputs from ``ops`` (float64 ``reference`` by
    default), in blocks of :data:`BLOCK` rows."""
    n = len(next(iter(ins.values())))
    blocks = [program.outputs(ops, shared,
                              {k: v[lo:lo + BLOCK] for k, v in ins.items()})
              for lo in range(0, n, BLOCK)]
    return {q: np.concatenate([np.asarray(b[q], np.float64) for b in blocks])
            for q in program.OUTPUTS}


def compare(program, shared: dict, ins: dict, outs: dict,
            limits: Dict[str, float]) -> Dict[str, dict]:
    """``{"<output>_err": {"value": x, "limit": y}}`` for every output."""
    want = reference_outputs(program, shared, ins)
    checks = {}
    for q in program.OUTPUTS:
        name = f"{q}_err"
        if outs[q].shape != want[q].shape:
            raise ValueError(f"{q}: outputs {outs[q].shape} != reference "
                             f"{want[q].shape}")
        checks[name] = {"value": rel_err(outs[q], want[q]),
                        "limit": float(limits[name])}
    return checks


def limited(values: Dict[str, float],
            limits: Dict[str, float]) -> Dict[str, dict]:
    """Numbers computed elsewhere, each beside its limit in ``limits``."""
    return {name: {"value": float(v), "limit": float(limits[name])}
            for name, v in values.items()}


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
