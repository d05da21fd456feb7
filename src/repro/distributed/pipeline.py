"""Pipeline parallelism (GPipe schedule) via shard_map + ppermute.

The uniform decoder's stacked blocks (L, ...) are sharded over a ``stage``
mesh axis (typically the ``pod`` axis: PP across the slow DCN links is the
classic multi-pod layout, keeping high-bandwidth TP inside a pod).

Schedule: M microbatches through S stages in M + S - 1 ticks.  Every tick,
activations hop stage i -> i+1 with ppermute; stage 0 feeds new
microbatches; the last stage collects outputs.  Bubble fraction is
(S-1)/(M+S-1) -- the launcher picks M >= 4*S by default.

This module is deliberately generic: it takes any ``block_apply``-style
stage function, so tests drive it with tiny MLPs and the launcher can wrap
transformer blocks.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,                       # (M, mb, ...) microbatched input
    *,
    mesh: Mesh,
    stage_axis: str = "pod",
) -> jax.Array:
    """Run x through S pipeline stages; returns (M, mb, ...) outputs.

    ``stage_params`` leaves must have a leading stage axis of size S
    (sharded over ``stage_axis``); ``stage_fn(local_params, x)`` applies
    one stage's layers.
    """
    S = mesh.shape[stage_axis]
    M = x.shape[0]

    def per_stage(params_local, x_local):
        # params_local leaves: (1, ...) -- this stage's slice
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(stage_axis)
        mb_shape = x_local.shape[1:]
        out_buf = jnp.zeros((M,) + mb_shape, x_local.dtype)
        carry = jnp.zeros(mb_shape, x_local.dtype)

        def tick(t, state):
            carry, out_buf = state
            # stage 0 ingests microbatch t (if any); others take the wire
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = jax.lax.dynamic_index_in_dim(
                x_local, mb_idx, axis=0, keepdims=False
            )
            inp = jnp.where(stage == 0, fresh, carry)
            active = (t - stage >= 0) & (t - stage < M)
            y = stage_fn(params_here, inp)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # collect on the last stage
            out_idx = jnp.clip(t - stage, 0, M - 1)
            collect = active & (stage == S - 1)
            cur = jax.lax.dynamic_index_in_dim(
                out_buf, out_idx, axis=0, keepdims=False
            )
            upd = jnp.where(collect, y, cur)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, upd, out_idx, axis=0
            )
            # ship activations forward (ring; last->0 ignored)
            perm = [(i, (i + 1) % S) for i in range(S)]
            carry = jax.lax.ppermute(y, stage_axis, perm)
            return (carry, out_buf)

        carry, out_buf = jax.lax.fori_loop(
            0, M + S - 1, tick, (carry, out_buf)
        )
        # broadcast the last stage's outputs to every stage (psum of a
        # single non-zero contribution; ppermute requires unique sources)
        contrib = jnp.where(stage == S - 1, out_buf, jnp.zeros_like(out_buf))
        return jax.lax.psum(contrib, stage_axis)

    other_axes = tuple(a for a in mesh.axis_names if a != stage_axis)
    pspec = jax.tree.map(
        lambda a: P(stage_axis, *([None] * (a.ndim - 1))), stage_params
    )
    return shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def microbatch(x: jax.Array, n: int) -> jax.Array:
    """(B, ...) -> (n, B/n, ...)"""
    B = x.shape[0]
    if B % n:
        raise ValueError(f"batch {B} not divisible into {n} microbatches")
    return x.reshape((n, B // n) + x.shape[1:])


def unmicrobatch(x: jax.Array) -> jax.Array:
    return x.reshape((-1,) + x.shape[2:])
