"""Fused Inverse-Helmholtz Pallas TPU kernel -- the paper's dataflow CU.

Adaptation notes:

  * The FPGA CU streams one element through 7 pipelined loop nests with
    FIFO links; here a *block of elements* flows through the same 7
    stages entirely inside VMEM -- crossing a stage boundary never touches
    HBM, which is the TPU equivalent of the FIFO stream.
  * "Lane packing" (splitting the 256-bit AXI bus into parallel lanes) is
    realized by packing the element axis into the vector lanes: the block
    is ``(p, p, p, rows, 128)`` (``kernels.tiling``), so every mode
    contraction is p^2 scalar-weighted sums of whole ``(rows, 128)``
    slabs -- one element per lane, exact float32 on the VPU.
  * Host<->HBM double buffering is Pallas grid pipelining: while block g
    computes, block g+1's DMA from HBM is in flight (automatic ping/pong).
  * S sits in SMEM for the whole grid: its entries are the contractions'
    scalar weights.

Grid: (padded rows // rows per block,).  Each step computes its block
one 8-row chunk at a time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import tiling


DEFAULT_BLOCK_ELEMENTS = 128


def _helmholtz_chunk(s_ref, D, u, p: int):
    """t = (S^T (x)3) u, r = D * t, v = (S (x)3) r on one tiled chunk."""
    t = u
    for mode in range(3):                 # t_ijk = sum S_il S_jm S_kn u_lmn
        t = tiling.contract(t, s_ref, mode, 1, p, (0, 1, 2))
    v = D * t
    for mode in range(3):                 # v_ijk = sum S_li S_mj S_nk r_lmn
        v = tiling.contract(v, s_ref, mode, 0, p, (0, 1, 2))
    return v


def _kernel(s_ref, d_ref, u_ref, v_ref, *, p: int, rows: int):
    def chunk(start):
        idx = tiling.chunk_index(3, start, tiling.chunk_rows(rows))
        v = _helmholtz_chunk(
            s_ref,
            d_ref[idx].astype(jnp.float32),
            u_ref[idx].astype(jnp.float32),
            p,
        )
        v_ref[idx] = v.astype(v_ref.dtype)

    tiling.for_each_chunk(rows, chunk)


@functools.partial(
    jax.jit,
    static_argnames=("block_elements", "interpret", "vmem_limit_bytes"),
)
def inverse_helmholtz_pallas(
    S: jax.Array,
    D: jax.Array,
    u: jax.Array,
    *,
    block_elements: int = DEFAULT_BLOCK_ELEMENTS,
    interpret: bool = False,
    vmem_limit_bytes: Optional[int] = None,
) -> jax.Array:
    """Batched fused Inverse Helmholtz.  S: (p,p); D,u: (E,p,p,p).
    ``vmem_limit_bytes``: see ``tiling.compiler_params``."""
    E, p = u.shape[0], u.shape[1]
    be = min(block_elements, E)
    if E % be != 0:
        raise ValueError(f"element count {E} not divisible by block {be}")
    rows, padded = tiling.block_rows(E, be)
    spec = tiling.element_spec((p, p, p), rows)
    out = pl.pallas_call(
        functools.partial(_kernel, p=p, rows=rows),
        grid=(padded // rows,),
        in_specs=[tiling.smem_spec(), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((p, p, p, padded, tiling.LANES),
                                       u.dtype),
        compiler_params=tiling.compiler_params(vmem_limit_bytes, interpret),
        interpret=interpret,
        name="inverse_helmholtz",
    )(S.astype(jnp.float32), tiling.to_tiles(D, padded),
      tiling.to_tiles(u, padded))
    return tiling.from_tiles(out, E)
