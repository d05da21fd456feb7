"""Element-minor block layout shared by the Pallas kernels.

Mosaic stores the two minor dimensions of every VMEM array in (8, 128)
tiles of 32-bit words (sublanes x lanes).  An element block laid out as
``(BE, p, p, p)`` would pad its ``(p, p)`` minor pair to whole tiles --
``(16, 128)`` for p = 11, about 17x the bytes -- and every mode
contraction would need a reshape or transpose across those minor
dimensions, which Mosaic refuses ("unsupported shape cast").

So the kernels take element tensors as ``(p, ..., p, rows, 128)``: the
element axis is split over (sublane rows, lanes) and every tensor mode
is a leading, untiled axis.  A block of a multiple of 8 rows fills its
tiles exactly, a mode contraction is a weighted sum of whole
``(rows, 128)`` slabs on the VPU (exact float32, no MXU pass), and an
index permutation is a re-stacking of leading axes.  The layout change
happens in XLA around the ``pallas_call`` (:func:`to_tiles`,
:func:`from_tiles`); the kernels compute one chunk of
:data:`SUBLANES` rows at a time, so in-kernel temporaries and code size
do not grow with the block.  The VMEM this layout takes is counted by
``memory.layout.working_set_bytes``, which the planner sizes blocks with.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..memory import channels, layout
from ..memory.layout import LANES, SUBLANES


def resolve_impl(impl: str) -> str:
    """``"auto"`` -> the compiled kernel on a TPU, the same kernel in
    interpret mode on the CPU, an error anywhere else.  ``"xla"`` (the
    jnp reference) only ever runs when asked for by name: nothing stands
    in for a kernel without saying so."""
    if impl in ("pallas", "interpret", "xla"):
        return impl
    if impl != "auto":
        raise ValueError(f"unknown kernel impl {impl!r}")
    backend = jax.default_backend()
    if backend == "tpu":
        return "pallas"
    if backend == "cpu":
        return "interpret"
    raise RuntimeError(
        f"no Pallas TPU kernel can run on the {backend!r} backend"
    )


def block_rows(n_elements: int, block_elements: int) -> Tuple[int, int]:
    """``(rows per grid block, total padded rows)`` for a batch.

    The element axis becomes ``ceil(n / 128)`` rows of 128 lanes.  A
    block smaller than the batch is rounded to a whole number of
    :data:`SUBLANES`-row chunks (Mosaic needs the second-minor block
    dimension to be a multiple of 8 or the full extent); a batch of at
    most 8 rows is one block.  Rows are padded to a multiple of the
    block, and the padding is cut off again by :func:`from_tiles`.
    """
    rows = -(-n_elements // LANES)
    if rows <= SUBLANES:
        return rows, rows
    br = -(-max(1, block_elements) // LANES)
    br = -(-br // SUBLANES) * SUBLANES
    br = min(br, -(-rows // SUBLANES) * SUBLANES)
    return br, -(-rows // br) * br


def chunk_rows(rows_per_block: int) -> int:
    """Rows computed per in-kernel step (divides the block)."""
    return min(SUBLANES, rows_per_block)


def to_tiles(x: jax.Array, padded_rows: int) -> jax.Array:
    """``(E, *shape)`` -> ``(*shape, padded_rows, 128)``, zero padded."""
    e, shape = x.shape[0], x.shape[1:]
    pad = padded_rows * LANES - e
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * len(shape))
    x = x.reshape((padded_rows, LANES) + shape)
    r = len(shape)
    return jnp.transpose(x, tuple(range(2, r + 2)) + (0, 1))


def from_tiles(y: jax.Array, n_elements: int) -> jax.Array:
    """Inverse of :func:`to_tiles`: ``(*shape, rows, 128)`` -> ``(E, *shape)``."""
    r = y.ndim - 2
    y = jnp.transpose(y, (r, r + 1) + tuple(range(r)))
    return y.reshape((-1,) + y.shape[2:])[:n_elements]


def element_spec(shape: Sequence[int], rows_per_block: int) -> pl.BlockSpec:
    """BlockSpec of one element tensor's tiled window at grid step g."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec(
        tuple(shape) + (rows_per_block, LANES),
        lambda g, _z=zeros: _z + (g, 0),
    )


def smem_spec() -> pl.BlockSpec:
    """A whole shared matrix resident in scalar memory (its entries are
    the VPU contractions' scalar weights)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def chunk_index(rank: int, start, rows: int) -> tuple:
    """Ref index of rows ``[start, start + rows)`` of a tiled tensor."""
    return (slice(None),) * rank + (pl.ds(start, rows), slice(None))


def for_each_chunk(rows_per_block: int, body) -> None:
    """Run ``body(row_start)`` for every chunk of the block."""
    ch = chunk_rows(rows_per_block)
    n = rows_per_block // ch
    if n == 1:
        body(0)
        return

    def step(c, carry):
        body(pl.multiple_of(c * ch, ch))
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def compiler_params(
    vmem_limit_bytes: Optional[int], interpret: bool
) -> pltpu.CompilerParams:
    """The grid is a parallel loop over element blocks.  The VMEM budget
    is passed explicitly so Mosaic enforces the planner's number:
    ``vmem_limit_bytes`` is the budget of the target that sized the
    block (``memory.layout.vmem_budget_bytes``); None means the budget
    of the TPU this process runs on (``channels.detect_target`` raises
    for a TPU kind with no datasheet).  Interpret mode has no VMEM."""
    if vmem_limit_bytes is None and not interpret:
        vmem_limit_bytes = layout.vmem_budget_bytes(channels.detect_target())
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def contract(x: jax.Array, m_ref, mode: int, mat_dim: int, p: int,
             perm: Sequence[int]) -> jax.Array:
    """``y = sum_l M[l, a] * x[.., l at mode, ..]`` on tiled values.

    ``x`` is ``(p,)*r + (rows, 128)``; ``m_ref`` holds the (p, p)
    matrix in SMEM and ``mat_dim`` names its contracted axis.  The new
    axis ``a`` replaces ``mode``; ``perm`` then orders the element-local
    axes of the in-place result (output axis i is in-place axis
    ``perm[i]``).
    """
    r = x.ndim - 2
    slabs = [jax.lax.index_in_dim(x, l, axis=mode, keepdims=False)
             for l in range(p)]
    ys = []
    for a in range(p):
        acc = None
        for l in range(p):
            w = m_ref[l, a] if mat_dim == 0 else m_ref[a, l]
            term = w * slabs[l]
            acc = term if acc is None else acc + term
        ys.append(acc)
    perm = tuple(perm)
    rest = [q for q in perm if q != mode]
    if rest == sorted(rest):
        # the other axes keep their order: stack the new one in place
        return jnp.stack(ys, axis=perm.index(mode))
    return permute_leading(jnp.stack(ys, axis=mode), perm[:r])


def permute_leading(y: jax.Array, perm: Sequence[int]) -> jax.Array:
    """Transpose the leading (untiled) axes by re-stacking slabs, which
    Mosaic lowers without touching the tiled minor dimensions."""
    perm = tuple(perm)
    if perm == tuple(range(len(perm))):
        return y
    src = perm[0]
    sub = tuple(q - (q > src) for q in perm[1:])
    return jnp.stack([
        permute_leading(
            jax.lax.index_in_dim(y, i, axis=src, keepdims=False), sub
        )
        for i in range(y.shape[src])
    ], axis=0)
