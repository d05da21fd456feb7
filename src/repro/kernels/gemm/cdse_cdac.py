"""CHARM-style two-class tile candidates for the GEMM-chain kernel.

CHARM composes heterogeneous accelerators from two design classes: CDSE
enumerates *large* tile configurations that maximize steady-state
throughput for big operands, CDAC keeps *small* dedicated accelerators
whose latency (fill cost) stays low for small operands.  The TPU analog
of a tile configuration is the kernel's ``block_elements``: big blocks
amortize the grid's per-step overhead, small blocks keep the VMEM
working set (and the per-dispatch latency) low.

``tile_candidates`` enumerates power-of-two blocks, filters them by the
plan's VMEM budget (the resource constraint), splits them into the two
classes, and ranks each by modeled throughput -- the search space the
measured block autotuner (``flow.compile(tune_blocks=True)``) walks
before depositing the measured winner in the profile store.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

from ...memory import layout
from ...memory.channels import MemoryTarget
from .gemm import GemmRecipe
from .ops import block_working_set_bytes

#: Working-set fraction of the VMEM budget separating the two classes:
#: blocks using more than this are "cdse" (large/throughput), the rest
#: "cdac" (small/latency).
LARGE_CLASS_FRACTION = 0.25

#: Default per-dispatch overhead used by the throughput ranking (one
#: kernel launch per block; same order as ``dse.DISPATCH_OVERHEAD_S``).
DEFAULT_OVERHEAD_S = 50e-6


@dataclasses.dataclass(frozen=True)
class TileCandidate:
    """One feasible ``block_elements`` choice for the GEMM-chain kernel."""

    klass: str                  # "cdse" (large) | "cdac" (small)
    block_elements: int
    working_set_bytes: int
    #: modeled elements/second: block roofline plus dispatch overhead
    predicted_throughput: float


def tile_candidates(
    recipe: GemmRecipe,
    target: MemoryTarget,
    *,
    bytes_per_scalar: int = 4,
    overhead_s: float = DEFAULT_OVERHEAD_S,
    max_block: int = 2048,
    batch_elements: Optional[int] = None,
) -> List[TileCandidate]:
    """Enumerate, filter, and throughput-rank block-size candidates.

    Power-of-two blocks up to ``max_block`` are kept when their VMEM
    working set on ``target`` fits its ``layout.vmem_budget_bytes`` (the
    budget the kernel is compiled with) and, when ``batch_elements`` is
    given, when they divide the batch (the Pallas grid requires it).
    Each survivor is classed large ("cdse") or small ("cdac") by
    working-set fraction and ranked by modeled throughput on the
    target's roofline: ``be / (overhead + flops/peak + io_bytes/bw)``.
    Returns candidates sorted best-first; empty when even a 1-element
    block exceeds VMEM.
    """
    budget = layout.vmem_budget_bytes(target)
    flops = recipe.flops_per_element()
    out_slots = {slot for _, slot in recipe.outputs}
    io_scalars = sum(
        math.prod(shape) for _, shape, is_elem in recipe.inputs if is_elem
    ) + sum(math.prod(recipe.slot_shape(s)) for s in out_slots)

    out: List[TileCandidate] = []
    be = 1
    while be <= max_block:
        ws = block_working_set_bytes(
            recipe, be, target, bytes_per_scalar=bytes_per_scalar
        )
        divides = batch_elements is None or batch_elements % be == 0
        fits = ws <= budget and (
            batch_elements is None or be <= batch_elements
        )
        if fits and divides:
            t = (
                overhead_s
                + be * flops / target.peak_flops
                + be * io_scalars * bytes_per_scalar / target.hbm_bw
            )
            out.append(TileCandidate(
                klass=(
                    "cdse" if ws > budget * LARGE_CLASS_FRACTION
                    else "cdac"
                ),
                block_elements=be,
                working_set_bytes=ws,
                predicted_throughput=be / t,
            ))
        be *= 2
    out.sort(key=lambda c: -c.predicted_throughput)
    return out
