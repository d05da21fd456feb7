"""Buffer layout: map a program's streams onto the channel model.

Performs the paper's section-3 sizing decisions explicitly:

  * **stream discovery** -- element-streamed inputs/outputs vs. shared
    (batch-invariant) operands, straight from ``ir.Program.element_vars``;
    with a staged schedule, per-group intermediates become HBM round-trip
    buffers too (``core.schedule`` exposes their byte counts).
  * **packing/padding** -- each element record is padded to the target's
    burst quantum (the paper packs p^3 scalars into 256-bit HBM words).
  * **batch sizing** -- E is derived so one batch's combined stream I/O
    fills one pseudo-channel, exactly the rule behind
    ``SimConfig.batch_for_channel`` but computed from the program instead
    of hardcoded in the driver.
  * **channel assignment** -- round-robin placement of every replica
    (ping/pong copies for a K-deep prefetch) over the pseudo-channels.
  * **VMEM block sizing** -- the largest per-dispatch element block whose
    working set fits the target's on-chip memory, which is what drives
    the Pallas kernel's ``block_elements`` (the paper's PLM sizing).

``ProgramChain`` planning (``memory.chain``) reuses these primitives with
a shared :class:`ChannelAllocator` so all stages of a multi-operator
program place their buffers without conflicts.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from ..core import ir
from ..core.schedule import Schedule
from .channels import MemoryTarget, channels_for, pad_to_burst
from .plan import BufferSpec


def element_streams(prog: ir.Program):
    """Split program arrays into (element inputs, element outputs, shared).

    Element arrays carry the implicit leading batch axis; shared arrays
    (the paper's S operator) are broadcast across the batch.
    """
    elem = set(prog.element_vars)
    ins = [(n, v) for n, v in prog.inputs.items() if n in elem]
    outs = [(n, v) for n, v in prog.outputs.items() if n in elem]
    shared = [(n, v) for n, v in prog.inputs.items() if n not in elem]
    return ins, outs, shared


def stream_bytes_per_element(prog: ir.Program, bytes_per_scalar: int) -> int:
    """Unpadded host-stream bytes per element (in + out), the quantity
    ``SimConfig.batch_for_channel`` divides a channel by."""
    ins, outs, _ = element_streams(prog)
    return sum(v.size for _, v in ins + outs) * bytes_per_scalar


def auto_batch_elements(
    prog: ir.Program,
    target: MemoryTarget,
    *,
    bytes_per_scalar: int,
    channel_bytes: Optional[int] = None,
    n_eq: Optional[int] = None,
) -> int:
    """The paper's E: largest batch whose stream I/O fits one channel.

    ``n_eq`` caps E at the problem size (no point staging a batch larger
    than the whole simulation).
    """
    cb = channel_bytes if channel_bytes is not None else target.channel_bytes
    per = stream_bytes_per_element(prog, bytes_per_scalar)
    e = max(1, cb // per)
    if n_eq is not None:
        e = min(e, max(1, n_eq))
    return int(e)


class ChannelAllocator:
    """Round-robin pseudo-channel assignment (Fig. 14's array->channel
    map).  A buffer spanning more channels than exist wraps -- capacity
    feasibility is checked globally by the DSE, not here.  One take never
    repeats a channel (no double-booking within one replica set); chain
    planning shares a single allocator across all stages so no two
    stages' hot streams pile onto channel 0.

    ``base`` offsets the allotted ids into a global channel namespace:
    heterogeneous chain planning runs one allocator per device group, so
    a stream lands on the pseudo-channels of the group that owns its
    producing stage (group 0 gets ids ``[0, n0)``, group 1 gets
    ``[n0, n0+n1)``, ...)."""

    def __init__(self, n_channels: int, base: int = 0):
        self.n = n_channels
        self.base = base
        self.next = 0

    def take(self, count: int) -> Tuple[int, ...]:
        """Allot the next ``count`` channel ids round-robin (capped at
        the channel count -- wide buffers stripe what exists)."""
        count = max(1, count)
        ids = tuple(
            self.base + (self.next + i) % self.n
            for i in range(min(count, self.n))
        )
        self.next = (self.next + count) % self.n
        return ids


#: Backwards-compatible alias (pre-chain name).
_ChannelAllocator = ChannelAllocator


def make_buffer(
    name: str,
    node: ir.Node,
    role: str,
    replicas: int,
    *,
    target: MemoryTarget,
    bytes_per_scalar: int,
    batch_elements: int,
    alloc: ChannelAllocator,
    group: str = "",
) -> BufferSpec:
    """Size, pad, and channel-assign one stream (shared by single-program
    and chain planning)."""
    eb = node.size * bytes_per_scalar
    pb = pad_to_burst(eb, target)
    bb = pb * batch_elements if role != "shared" else pb
    ch = alloc.take(replicas * channels_for(bb, target))
    return BufferSpec(
        name=name, role=role, shape=tuple(node.shape),
        element_bytes=eb, padded_bytes=pb, batch_bytes=bb,
        replicas=replicas, channels=ch, group=group,
    )


def build_buffers(
    prog: ir.Program,
    target: MemoryTarget,
    *,
    bytes_per_scalar: int,
    batch_elements: int,
    prefetch_depth: int,
    schedule: Optional[Schedule] = None,
) -> Tuple[BufferSpec, ...]:
    """Assign every stream of the program to sized, channel-mapped buffers."""
    ins, outs, shared = element_streams(prog)
    alloc = ChannelAllocator(target.n_channels)
    bufs: List[BufferSpec] = []

    # K-deep prefetch keeps K staged batches, one computing, and -- since
    # JAX allocates fresh buffers instead of swapping a ping/pong pair in
    # place -- one retiring batch whose async compute has not yet freed
    # it.  Peak input residency is therefore K+2 (K=1 is the paper's
    # ping/pong pair plus the retiring slot).
    in_replicas = prefetch_depth + 2 if prefetch_depth > 0 else 1
    out_replicas = 2 if prefetch_depth > 0 else 1  # result drains while next computes

    def add(name, node, role, replicas, group=""):
        bufs.append(
            make_buffer(
                name, node, role, replicas, target=target,
                bytes_per_scalar=bytes_per_scalar,
                batch_elements=batch_elements, alloc=alloc, group=group,
            )
        )

    for name, node in ins:
        add(name, node, "in", in_replicas)
    for name, node in outs:
        add(name, node, "out", out_replicas)
    for name, node in shared:
        add(name, node, "shared", 1)

    # staged backend: group-boundary intermediates are HBM round-trips
    if schedule is not None:
        out_uids = {v.uid for v in prog.outputs.values()}
        input_uids = {v.uid for v in prog.inputs.values()}
        for g in schedule.groups:
            streamed = [
                n for n in g.out_streams
                if n.uid not in out_uids and n.uid not in input_uids
            ]
            for i, node in enumerate(streamed):
                add(f"{g.name}.s{i}", node, "inter", 1, group=g.name)
    return tuple(bufs)


# ---------------------------------------------------------------------------
# on-chip (VMEM / PLM) block sizing -- what drives the Pallas kernel's
# block_elements (the paper sizes its PLM buffers the same way)
# ---------------------------------------------------------------------------


#: Lanes of a TPU vector register: the minor split of a tiled element axis.
LANES = 128
#: Sublanes of a 32-bit (sublane, lane) tile: rows per in-kernel chunk.
SUBLANES = 8


def sublane_tile(itemsize: int) -> int:
    """Rows of one (sublane, lane) tile for a dtype: 8 for 32-bit, 16
    for 16-bit (two values pack into one 32-bit sublane)."""
    return SUBLANES * max(1, 4 // itemsize)


def padded_block_bytes(size: int, rows: int, itemsize: int) -> int:
    """VMEM bytes of one tiled element window of ``size`` scalars per
    element, ``(*shape, rows, 128)``, after (sublane, lane) padding."""
    t = sublane_tile(itemsize)
    return size * (-(-rows // t) * t) * LANES * itemsize


def working_set_bytes(
    stream_sizes: Sequence[int],
    intermediate_size: int,
    shared_size: int,
    block_elements: int,
    itemsize: int,
    target: MemoryTarget,
) -> int:
    """On-chip bytes while one element block flows through a fused kernel.

    ``stream_sizes`` are the scalars per element of every element input
    and output, ``intermediate_size`` those of the largest in-kernel
    value, ``shared_size`` the batch-invariant operands' scalars.

    A ``tiled_vmem`` target (a TPU) holds the element axis as
    ``(rows, 128)`` tiles (``kernels.tiling``): every stream window is
    padded to whole (sublane, lane) tiles and double-buffered by the grid
    pipeline, and the kernel keeps two chunk-sized copies of the largest
    value live (a contraction's input and output).  A dataflow PLM holds
    one unpadded slice per stream plus two block-sized intermediates --
    its double buffer is the half of on-chip memory that
    :func:`vmem_budget_bytes` holds back.
    """
    if not target.tiled_vmem:
        return (shared_size + block_elements * (
            sum(stream_sizes) + 2 * intermediate_size)) * itemsize
    rows = -(-max(1, block_elements) // LANES)
    windows = 2 * sum(
        padded_block_bytes(s, rows, itemsize) for s in stream_sizes
    )
    scratch = 2 * padded_block_bytes(
        intermediate_size, min(SUBLANES, rows), itemsize
    )
    return shared_size * itemsize + windows + scratch


def block_working_set_bytes(
    prog: ir.Program, block_elements: int, target: MemoryTarget, *,
    bytes_per_scalar: int,
) -> int:
    """:func:`working_set_bytes` of one element block of ``prog``: every
    element stream's block window, the live copies of the largest
    intermediate, plus the batch-invariant operands held resident."""
    ins, outs, shared = element_streams(prog)
    inter = max(
        (n.size for n in prog.toposort() if not isinstance(n, ir.Input)),
        default=0,
    )
    return working_set_bytes(
        [v.size for _, v in ins + outs], inter,
        sum(v.size for _, v in shared), block_elements, bytes_per_scalar,
        target,
    )


def vmem_budget_bytes(target: MemoryTarget) -> int:
    """The on-chip bytes one kernel block may use: half the target's
    VMEM/PLM.  On a TPU this is the ``vmem_limit_bytes`` the Pallas
    kernels are compiled with (the working set counts the double
    buffers itself); on a dataflow PLM the other half is its ping/pong
    copy."""
    return target.vmem_bytes // 2


def vmem_block_elements(
    prog: ir.Program,
    target: MemoryTarget,
    *,
    bytes_per_scalar: int,
) -> int:
    """Largest power-of-two element block whose working set fits the
    target's :func:`vmem_budget_bytes` (mirroring ``core.schedule``'s
    VMEM budget)."""
    budget = vmem_budget_bytes(target)
    be = 1
    while block_working_set_bytes(
        prog, be * 2, target, bytes_per_scalar=bytes_per_scalar
    ) <= budget:
        be *= 2
    return be


def pad_batch_for_block(
    e: int,
    block_cap: int,
    *,
    limit: Optional[int] = None,
    caps: Optional[Sequence[int]] = None,
) -> Tuple[int, int]:
    """Auto-pad E to a block-composite size (ROADMAP: a prime-ish
    natural E must never force the Pallas block divisor tiny).

    Rounds E up to the next multiple of the (power-of-two) VMEM block
    cap, so ``largest_divisor_leq(E, cap) == cap`` -- the paper pads the
    tail batch the same way it pads records to HBM words.  E is left
    alone when its natural block is already at least half the cap (no
    filler for a near-optimal divisor); for chain planning, pass every
    stage's cap via ``caps`` so that check covers the *smallest* stage
    too (a multiple of the largest power-of-two cap divides the rest).
    ``limit`` (the problem size ``n_eq``) bounds the padded batch: when
    rounding up would exceed it, E snaps *down* to the nearest block
    multiple instead (never below one block).  Returns ``(padded_e,
    pad)`` with ``pad = padded_e - e`` (negative when snapped down);
    the plan reports the pad so the host knows how many tail elements
    per batch are filler.
    """
    all_caps = [block_cap] + [c for c in (caps or ())]
    block_cap = max(all_caps)
    if block_cap <= 1 or e <= block_cap:
        return e, 0
    if all(
        c <= 1 or e <= c or largest_divisor_leq(e, c) * 2 >= c
        for c in all_caps
    ):
        return e, 0  # natural E already composite enough: no filler
    up = -(-e // block_cap) * block_cap
    if limit is None or up <= limit:
        return up, up - e
    down = (e // block_cap) * block_cap
    if down >= block_cap:
        return down, down - e
    return e, 0


def largest_divisor_leq(n: int, bound: int) -> int:
    """Largest divisor of ``n`` that is <= ``bound`` (>= 1).  Pallas grids
    require block_elements to divide the batch, so the VMEM-derived block
    is snapped to the nearest feasible divisor of E."""
    n, bound = max(1, n), max(1, bound)
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            if d <= bound:
                best = max(best, d)
            if n // d <= bound:
                best = max(best, n // d)
        d += 1
    return best
