"""Pure-jnp oracle for the fused Inverse-Helmholtz kernel.

Shapes: S (p, p) shared; D, u (E, p, p, p) per element; out v (E, p, p, p).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def inverse_helmholtz_ref(S, D, u):
    t = jnp.einsum("il,jm,kn,elmn->eijk", S, S, S, u, precision=_HIGHEST)
    r = D * t
    v = jnp.einsum("li,mj,nk,elmn->eijk", S, S, S, r, precision=_HIGHEST)
    return v
