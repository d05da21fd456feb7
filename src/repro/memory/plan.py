"""The plan's building blocks and the analytic cost model beneath them.

A plan is the artifact the paper's Olympus flow produces implicitly when
it instantiates Fig. 14: which array lives in which pseudo-channel, how
many ping/pong replicas each stream keeps resident, how big a batch (E)
is, and what the transfer/compute overlap is predicted to cost.  This
module holds the pieces every plan is made of -- :class:`BufferSpec`
(one buffer and its channels), :class:`CostBreakdown` (one stage's
predicted per-batch time) and :func:`predict_cost`, the three-term model
that prices it -- so the chain planner (``memory.chain``) and the
explorer above it (``memory.dse``) price plans with one model, and the
planner never imports the explorer.  Everything here is pure data or
pure arithmetic.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Tuple

from .channels import MemoryTarget

#: Throughput of each scalar policy relative to the target's native
#: matmul peak (TPU: bf16 MXU; f32 runs at half rate, f64 and the
#: integer-emulated fixed-point formats far below).
POLICY_EFFICIENCY = {
    "bfloat16": 1.0,
    "float32": 0.5,
    "float64": 0.125,
    "fixed32_q8.24": 0.25,
    "fixed64_q24.40": 0.0625,
}


def host_stream_bytes(buffers: Iterable["BufferSpec"]) -> int:
    """Host-link bytes moved per batch (in + out streams, padded)."""
    return sum(b.batch_bytes for b in buffers if b.role in ("in", "out"))


def hbm_stream_bytes(buffers: Iterable["BufferSpec"]) -> int:
    """Device-memory traffic per batch: every stream crosses HBM once;
    stage intermediates and chain-resident streams cross twice (the
    producer writes, the consumer reads back) -- but never the host
    link."""
    total = 0
    for b in buffers:
        if b.role in ("in", "out"):
            total += b.batch_bytes
        elif b.role in ("inter", "resident"):
            total += 2 * b.batch_bytes
    return total


def channels_used(buffers: Iterable["BufferSpec"]) -> int:
    """Distinct pseudo-channel ids the buffers map to."""
    used = set()
    for b in buffers:
        used.update(b.channels)
    return len(used)


@dataclasses.dataclass(frozen=True)
class BufferSpec:
    """One device-resident buffer and its pseudo-channel placement.

    Roles:
      * ``in``     -- host-streamed input (E-element batch; a K-deep
                      prefetch pipeline keeps K+2 replicas resident:
                      K staged + 1 computing + 1 retiring -- Fig. 14a's
                      ping/pong pair generalized, plus the slot JAX
                      frees only when the async compute completes).
      * ``out``    -- device-produced batch streamed back / reduced.
      * ``shared`` -- batch-invariant operand (the paper's S matrix),
                      resident once.
      * ``inter``  -- scheduled-group intermediate (staged backend): an
                      HBM round-trip between dataflow stages.
      * ``resident`` -- chain stream (``memory.chain``): a producer
                      stage's output consumed by a later stage of the
                      same ProgramChain.  It stays in HBM -- written once,
                      read once, never crossing the host link.
    """

    name: str
    role: str
    shape: Tuple[int, ...]      # per-element shape (element axis excluded)
    element_bytes: int          # unpadded bytes per element record
    padded_bytes: int           # after burst/word packing
    batch_bytes: int            # padded_bytes * E (shared: padded_bytes)
    replicas: int               # concurrently-resident copies
    channels: Tuple[int, ...]   # assigned pseudo-channel ids
    group: str = ""             # producing schedule group (inter only)

    @property
    def resident_bytes(self) -> int:
        """HBM footprint: one batch per ping/pong replica."""
        return self.batch_bytes * self.replicas

    @property
    def padding_overhead(self) -> float:
        """Fraction of the buffer that is alignment padding."""
        if self.element_bytes == 0:
            return 0.0
        return self.padded_bytes / self.element_bytes - 1.0


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """Predicted per-batch seconds under the three-term transfer model."""

    t_compute: float     # FLOPs / (peak * policy efficiency * CUs)
    t_hbm: float         # device-memory traffic / assigned-channel bw
    t_host: float        # host->device stream / host link bw
    t_overhead: float    # per-dispatch launch/sync cost
    t_serial: float      # no overlap: host + max(compute, hbm) + overhead
    t_pipelined: float   # K-deep overlap: max(host, compute, hbm) + overhead

    @property
    def bottleneck(self) -> str:
        """The dominating term's label (the correction-fit key)."""
        terms = {
            "compute": self.t_compute,
            "hbm": self.t_hbm,
            "host-link": self.t_host,
        }
        return max(terms, key=terms.get)


def predict_cost(
    target: MemoryTarget,
    *,
    policy: str,
    batch_elements: int,
    flops_per_element: int,
    host_bytes: int,
    hbm_bytes: int,
    channels_used: int,
    prefetch_depth: int,
    cu_count: int,
    n_batches: Optional[int] = None,
) -> CostBreakdown:
    """Per-batch time under the three-term overlap model.

    Device bandwidth is what the *assigned channels* deliver (the paper's
    point: unmapped pseudo-channels are wasted bandwidth); the host link
    is shared across replicated CUs.  The model is monotone: more
    bandwidth or more FLOP/s never predicts a slower batch.
    """
    eff = POLICY_EFFICIENCY.get(policy, 0.25)
    t_compute = (
        batch_elements * flops_per_element / (target.peak_flops * eff * cu_count)
    )
    bw = target.channel_bw * min(max(1, channels_used), target.n_channels)
    t_hbm = hbm_bytes / (bw * cu_count)
    t_host = host_bytes / target.host_link_bw
    t_over = target.dispatch_overhead_s
    t_serial = t_host + max(t_compute, t_hbm) + t_over
    if prefetch_depth == 0:
        t_pipelined = t_serial
    else:
        t_pipelined = max(t_host, t_compute, t_hbm) + t_over
        if n_batches:
            # pipeline fill: K transfers before the first compute (never
            # more than the batches that exist beyond the first)
            fill = min(prefetch_depth, n_batches - 1)
            t_pipelined += fill * t_host / n_batches
    return CostBreakdown(
        t_compute=t_compute, t_hbm=t_hbm, t_host=t_host, t_overhead=t_over,
        t_serial=t_serial, t_pipelined=t_pipelined,
    )
