"""Jit'd public wrappers for the fused Inverse-Helmholtz kernel.

``inverse_helmholtz(S, D, u)`` runs the Pallas kernel: compiled by
Mosaic on a TPU, in interpret mode on the CPU (``impl="auto"``, see
``tiling.resolve_impl``); ``impl="xla"`` asks for the pure-jnp reference
by name.  The signature is
what ``repro.core.emit.compile_program(backend='pallas')`` expects as
``pallas_impl`` for the Inverse-Helmholtz program.
"""
from __future__ import annotations

from typing import Literal, Optional

import jax

from ...memory import layout
from ...memory.channels import MemoryTarget
from .. import tiling
from .helmholtz import inverse_helmholtz_pallas, DEFAULT_BLOCK_ELEMENTS
from .ref import inverse_helmholtz_ref

Impl = Literal["auto", "pallas", "interpret", "xla"]


def block_working_set_bytes(
    p: int, block_elements: int, target: MemoryTarget, *,
    bytes_per_scalar: int = 4,
) -> int:
    """VMEM bytes while one element block flows through the fused
    kernel on ``target``: the u/D/v windows, the live t/r intermediates
    and the resident S (``memory.layout.working_set_bytes``).  Matches
    ``memory.layout.block_working_set_bytes`` on the Inverse-Helmholtz
    program."""
    return layout.working_set_bytes(
        (p ** 3,) * 3, p ** 3, p * p, block_elements, bytes_per_scalar,
        target,
    )


def inverse_helmholtz(
    S: jax.Array,
    D: jax.Array,
    u: jax.Array,
    *,
    impl: Impl = "auto",
    block_elements: int = DEFAULT_BLOCK_ELEMENTS,
    vmem_limit_bytes: Optional[int] = None,
) -> jax.Array:
    impl = tiling.resolve_impl(impl)
    if impl == "xla":
        return jax.jit(inverse_helmholtz_ref)(S, D, u)
    return inverse_helmholtz_pallas(
        S, D, u, block_elements=block_elements,
        interpret=impl == "interpret", vmem_limit_bytes=vmem_limit_bytes,
    )


def make_pallas_impl(
    impl: Impl = "auto", block_elements: int = DEFAULT_BLOCK_ELEMENTS,
    vmem_limit_bytes: Optional[int] = None,
):
    """Adapter for core.emit.compile_program(backend='pallas')."""

    def batched_fn(env):
        v = inverse_helmholtz(
            env["S"], env["D"], env["u"], impl=impl,
            block_elements=block_elements,
            vmem_limit_bytes=vmem_limit_bytes,
        )
        return {"v": v}

    return batched_fn
