"""Jit'd public wrappers for the tiled GEMM-chain kernel.

``make_pallas_impl(recipe)`` returns the batched callable
``core.emit.compile_program(backend='pallas')`` expects: the Pallas
kernel, compiled on a TPU and interpreted on the CPU -- the same
dispatch contract as the Helmholtz kernel's ``ops``."""
from __future__ import annotations

import math
from typing import Dict, Literal, Optional

import jax

from ...memory import layout
from ...memory.channels import MemoryTarget
from .. import tiling
from .gemm import (DEFAULT_BLOCK_ELEMENTS, GemmRecipe, gemm_chain_pallas,
                   gemm_chain_ref)

Impl = Literal["auto", "pallas", "interpret", "xla"]


def block_working_set_bytes(
    recipe: GemmRecipe, block_elements: int, target: MemoryTarget, *,
    bytes_per_scalar: int = 4,
) -> int:
    """VMEM bytes while one element block flows through the kernel on
    ``target``: the element in/out windows, the live copies of the
    largest intermediate and the shared matrices
    (``memory.layout.working_set_bytes``).  Mirrors
    ``memory.layout.block_working_set_bytes`` on the recipe's program."""
    shared = sum(
        math.prod(shape) for _, shape, is_elem in recipe.inputs
        if not is_elem
    )
    out_slots = {slot for _, slot in recipe.outputs}
    streams = [
        math.prod(shape) for _, shape, is_elem in recipe.inputs if is_elem
    ] + [math.prod(recipe.slot_shape(s)) for s in out_slots]
    inter = max(
        (math.prod(recipe.slot_shape(recipe.n_inputs + k))
         for k in range(len(recipe.ops))),
        default=0,
    )
    return layout.working_set_bytes(
        streams, inter, shared, block_elements, bytes_per_scalar, target,
    )


def gemm_chain(
    recipe: GemmRecipe,
    env: Dict[str, jax.Array],
    *,
    impl: Impl = "auto",
    block_elements: int = DEFAULT_BLOCK_ELEMENTS,
    vmem_limit_bytes: Optional[int] = None,
) -> Dict[str, jax.Array]:
    """Run one GEMM-chain recipe: the kernel (``tiling.resolve_impl``
    decides compiled or interpreted), or the einsum reference when
    ``impl="xla"`` asks for it."""
    impl = tiling.resolve_impl(impl)
    if impl == "xla":
        return gemm_chain_ref(recipe, env)
    return gemm_chain_pallas(
        recipe, env, block_elements=block_elements,
        interpret=impl == "interpret", vmem_limit_bytes=vmem_limit_bytes,
    )


def make_pallas_impl(
    recipe: GemmRecipe,
    impl: Impl = "auto",
    block_elements: int = DEFAULT_BLOCK_ELEMENTS,
    vmem_limit_bytes: Optional[int] = None,
):
    """Adapter for ``core.emit.compile_program(backend='pallas')``."""

    def batched_fn(env):
        return gemm_chain(
            recipe, env, impl=impl, block_elements=block_elements,
            vmem_limit_bytes=vmem_limit_bytes,
        )

    return batched_fn
