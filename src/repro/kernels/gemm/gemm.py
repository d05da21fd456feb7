"""Tiled Pallas GEMM-chain kernel: one fused CU for any stage program
made of shared-matrix mode contractions and elementwise ops.

The Helmholtz kernel hand-fuses one fixed 7-stage dataflow.  This module
generalizes the same idiom -- a block of elements flows through the
whole op chain inside VMEM, laid out element-minor as
``(p, ..., p, rows, 128)`` (``kernels.tiling``) -- to *any* recipe
extracted from a stage program by ``flow.patterns.match_gemm_chain``:

  * **contract**: ``y[.., a at mode m, ..] = sum_l M[l, a] * x[.., l, ..]``
    as p^2 scalar-weighted sums of whole ``(rows, 128)`` slabs, with
    ``M`` in SMEM; the recipe's ``perm`` re-stacks leading axes, so no
    value ever changes its tiled minor dimensions.
  * **ewise**: add/sub/mul/div between element values, plus unary
    neg/scale -- the Hadamard steps of the CFD chain.

One recipe covers the interpolation stage (3 contractions), the gradient
stage (3 outputs sharing an input), any single schedule-derived stage,
and the fully fused pipeline -- which is exactly what the cost-driven
stage fusion pass needs: fused stages re-match to this kernel class.

Grid: one step per block of element rows.  Shared matrices stay in
SMEM for the whole grid; element tensors stream one block per step with
the grid pipeline double-buffering the HBM DMA.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import tiling


DEFAULT_BLOCK_ELEMENTS = 128

#: ewise ops the kernel (and the matcher) accept.
EWISE_OPS = ("add", "sub", "mul", "div", "neg", "scale")


@dataclasses.dataclass(frozen=True)
class GemmRecipe:
    """A hashable, IR-free description of one GEMM-chain stage.

    ``inputs`` lists every program input as ``(name, shape, is_element)``
    -- element tensors are rank-r all-``p`` cubes carrying the batch
    axis, shared inputs are ``(p, p)`` contraction matrices.  Value
    slots number the inputs first (in order) and then one slot per op
    result, so ``ops`` and ``outputs`` reference values positionally:

      * ``("contract", src_slot, mat_slot, mode, mat_dim, perm)`` --
        contract the matrix's ``mat_dim`` axis against tensor mode
        ``mode``, then permute the element-local axes of the in-place
        result by ``perm`` (identity for in-place contractions; the
        gradient einsums move the new free axis to the front);
      * ``("ewise", op, lhs_slot, rhs_slot, const)`` -- ``rhs_slot`` is
        ``-1`` for unary ops, ``const`` is None unless ``op=='scale'``.

    ``outputs`` maps output names to slots.  Built by
    ``flow.patterns.match_gemm_chain``; hashable so compiled kernels
    cache per (recipe, block, interpret).
    """

    p: int
    inputs: Tuple[Tuple[str, Tuple[int, ...], bool], ...]
    ops: Tuple[Tuple, ...]
    outputs: Tuple[Tuple[str, int], ...]

    @property
    def n_inputs(self) -> int:
        return len(self.inputs)

    def slot_shape(self, slot: int) -> Tuple[int, ...]:
        """Element-local shape of a value slot (no batch axis)."""
        shapes = [shape for _, shape, _ in self.inputs]
        for op in self.ops:
            if op[0] == "contract":
                shapes.append(shapes[op[1]])
            else:
                shapes.append(shapes[op[2]])
        return shapes[slot]

    def flops_per_element(self) -> int:
        """Mirror of ``ir.Node.flops`` summed over the recipe."""
        total = 0
        for op in self.ops:
            if op[0] == "contract":
                total += 2 * self.p * math.prod(self.slot_shape(op[1]))
            else:
                total += math.prod(self.slot_shape(op[2]))
        return total


def _ewise(eop: str, a, b, const):
    if eop == "add":
        return a + b
    if eop == "sub":
        return a - b
    if eop == "mul":
        return a * b
    if eop == "div":
        return a / b
    if eop == "neg":
        return -a
    if eop == "scale":
        return a * const
    raise ValueError(f"unknown ewise op {eop!r}")  # matcher emits EWISE_OPS


def apply_recipe(recipe: GemmRecipe, vals, *, f32=jnp.float32):
    """Run the op chain over whole-batch values (axis 0 is the element
    axis) with plain einsums: the XLA reference the kernel is checked
    against.  Contractions run at full float32 precision."""
    vals = list(vals)
    letters = "bcdefghijkmnopqrstuvwxyz"      # 'a' is new, 'l' the batch
    for op in recipe.ops:
        if op[0] == "contract":
            _, src, mat, mode, mat_dim, perm = op
            x = vals[src]
            r = x.ndim - 1
            ax = letters[:r]
            new = "a"
            m_sub = (ax[mode] + new) if mat_dim == 0 else (new + ax[mode])
            in_place = ax[:mode] + new + ax[mode + 1:]
            out = "".join(in_place[q] for q in perm)
            vals.append(jnp.einsum(
                f"{m_sub},l{ax}->l{out}", vals[mat], x,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=f32,
            ))
        else:
            _, eop, lhs, rhs, const = op
            vals.append(_ewise(
                eop, vals[lhs], vals[rhs] if rhs >= 0 else None, const
            ))
    return vals


def _kernel(*refs, recipe: GemmRecipe, rows: int):
    """One grid step: every element input's window and the SMEM
    matrices in, every output's window out, one 8-row chunk at a time."""
    n_in = recipe.n_inputs
    in_refs, out_refs = refs[:n_in], refs[n_in:]

    def chunk(start):
        ch = tiling.chunk_rows(rows)
        vals = []
        for (_, shape, is_elem), ref in zip(recipe.inputs, in_refs):
            vals.append(
                ref[tiling.chunk_index(len(shape), start, ch)]
                .astype(jnp.float32) if is_elem else ref
            )
        for op in recipe.ops:
            if op[0] == "contract":
                _, src, mat, mode, mat_dim, perm = op
                vals.append(tiling.contract(
                    vals[src], vals[mat], mode, mat_dim, recipe.p, perm
                ))
            else:
                _, eop, lhs, rhs, const = op
                vals.append(_ewise(
                    eop, vals[lhs], vals[rhs] if rhs >= 0 else None, const
                ))
        for (_, slot), ref in zip(recipe.outputs, out_refs):
            y = vals[slot]
            ref[tiling.chunk_index(y.ndim - 2, start, ch)] = (
                y.astype(ref.dtype)
            )

    tiling.for_each_chunk(rows, chunk)


@functools.lru_cache(maxsize=None)
def _pallas_fn(recipe: GemmRecipe, block_elements: int, interpret: bool,
               vmem_limit_bytes: Optional[int]):
    """Build (and cache) the jitted pallas_call for one recipe/block."""

    def call(*arrays):
        e = next(
            a.shape[0]
            for (_, _, is_elem), a in zip(recipe.inputs, arrays) if is_elem
        )
        be = min(block_elements, e)
        if e % be != 0:
            raise ValueError(
                f"element count {e} not divisible by block {be}"
            )
        rows, padded = tiling.block_rows(e, be)
        in_specs, operands = [], []
        for (_, shape, is_elem), a in zip(recipe.inputs, arrays):
            if is_elem:
                in_specs.append(tiling.element_spec(shape, rows))
                operands.append(tiling.to_tiles(a, padded))
            else:
                in_specs.append(tiling.smem_spec())
                operands.append(a.astype(jnp.float32))
        out_dtype = arrays[0].dtype
        shapes = [recipe.slot_shape(slot) for _, slot in recipe.outputs]
        got = pl.pallas_call(
            functools.partial(_kernel, recipe=recipe, rows=rows),
            grid=(padded // rows,),
            in_specs=in_specs,
            out_specs=[tiling.element_spec(s, rows) for s in shapes],
            out_shape=[
                jax.ShapeDtypeStruct(
                    tuple(s) + (padded, tiling.LANES), out_dtype
                )
                for s in shapes
            ],
            compiler_params=tiling.compiler_params(
                vmem_limit_bytes, interpret
            ),
            interpret=interpret,
            name="gemm_chain",
        )(*operands)
        return [tiling.from_tiles(y, e) for y in got]

    return jax.jit(call)


def gemm_chain_pallas(
    recipe: GemmRecipe,
    env: Dict[str, jax.Array],
    *,
    block_elements: int = DEFAULT_BLOCK_ELEMENTS,
    interpret: bool = False,
    vmem_limit_bytes: Optional[int] = None,
) -> Dict[str, jax.Array]:
    """Run one recipe through the tiled Pallas kernel.  ``env`` maps the
    recipe's input names to arrays (element tensors batched on axis 0);
    ``vmem_limit_bytes``: see ``tiling.compiler_params``."""
    arrays = tuple(env[name] for name, _, _ in recipe.inputs)
    got = _pallas_fn(
        recipe, block_elements, interpret, vmem_limit_bytes
    )(*arrays)
    return {name: out for (name, _), out in zip(recipe.outputs, got)}


@functools.lru_cache(maxsize=None)
def _ref_fn(recipe: GemmRecipe):
    def call(*arrays):
        vals = [a.astype(jnp.float32) for a in arrays]
        vals = apply_recipe(recipe, vals)
        return [
            vals[slot].astype(arrays[0].dtype)
            for _, slot in recipe.outputs
        ]

    return jax.jit(call)


def gemm_chain_ref(
    recipe: GemmRecipe, env: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    """Pure-jnp reference: the same recipe applied to the whole batch
    with einsums (element axis 0)."""
    arrays = tuple(env[name] for name, _, _ in recipe.inputs)
    got = _ref_fn(recipe)(*arrays)
    return {name: out for (name, _), out in zip(recipe.outputs, got)}
