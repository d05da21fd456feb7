"""The reference in the precision just below the configurations'.

The configurations state float32, which the program keeps on a TPU by
running every contraction at HIGHEST precision (the XLA stages) or as
exact float32 multiply-adds (the Pallas kernels).  The step below that,
the one a later change would be tempted to take, is three bfloat16
passes (``Precision.HIGH``): each operand is split into a bfloat16 high
part and a bfloat16 low part, and the three larger of the four partial
products are summed in float32.  This module spells those three passes
out, so that the control reads the same on any backend; the check
(``check.py``) has to reject what it computes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bf16(x):
    """x rounded to bfloat16's 8 significant bits, kept in float32.
    ``reduce_precision`` and not a round trip through ``astype``: XLA
    may drop a float32 -> bfloat16 -> float32 pair as excess precision
    in one fusion and keep it in another, which leaves the low part
    zero and makes the three passes one."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _dot(M, x, axis: int):
    y = jnp.tensordot(M, x, axes=([1], [axis]),
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.moveaxis(y, 0, axis)


def mode(M, x, axis: int):
    """``reference.mode`` as three bfloat16 passes."""
    mh, ml = _split(M)
    xh, xl = _split(x)
    return _dot(ml, xh, axis) + _dot(mh, xl, axis) + _dot(mh, xh, axis)


def mul(a, b):
    return a * b


def asarray(a):
    return jnp.asarray(a, jnp.float32)
