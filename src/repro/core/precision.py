"""Scalar-type policies (the `base2` dialect analogue).

The paper treats the scalar representation as a compiler knob: double,
then fixed-point ap_fixed<64,24> (Q24.40) and ap_fixed<32,8> (Q8.24),
validated at MSE 9.39e-22 and 3.58e-12 respectively on [-1, 1]-normalized
CFD data.  We keep the exact Q-formats, implemented with JAX integer
arithmetic, plus the TPU-native float ladder (f64/f32/bf16) which is the
MXU's own "cheap multiplier" analogue.

Fixed-point evaluation requires 64-bit integers and therefore runs under
``jax.enable_x64`` (the emitter wraps calls).  Like the paper, the
conversion from/to double lives on the host side of the boundary
(``encode``/``decode``), and the compute graph stays in integer form.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

enable_x64 = jax.enable_x64


@dataclasses.dataclass(frozen=True)
class FloatPolicy:
    """Plain float computation at a given dtype."""

    dtype: str = "float32"  # float64 | float32 | bfloat16
    accum_dtype: Optional[str] = None  # einsum accumulation type

    @property
    def name(self) -> str:
        return self.dtype

    @property
    def is_fixed_point(self) -> bool:
        return False

    @property
    def bits(self) -> int:
        return jnp.dtype(self.dtype).itemsize * 8

    @property
    def precision(self) -> jax.lax.Precision:
        """Matmul precision that keeps the policy's word: a TPU runs a
        float32 matmul at DEFAULT precision as one bfloat16 pass, so
        float32 and float64 ask for HIGHEST (full float32 products);
        bfloat16 is the one pass it names."""
        if self.dtype == "bfloat16":
            return jax.lax.Precision.DEFAULT
        return jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class FixedPointPolicy:
    """Qm.n fixed point: ``total_bits`` storage with ``frac_bits`` fraction.

    The paper's formats:
      * fixed64 = Q24.40 -> FixedPointPolicy(64, 40)
      * fixed32 = Q8.24  -> FixedPointPolicy(32, 24)

    Values are assumed range-normalized (|x| bounded by the integer part),
    matching the paper's observation that the physical quantities can be
    rescaled into [-1, 1].
    """

    total_bits: int = 32
    frac_bits: int = 24

    def __post_init__(self) -> None:
        if self.total_bits not in (32, 64):
            raise ValueError("fixed point storage must be int32 or int64")
        if not 0 < self.frac_bits < self.total_bits:
            raise ValueError("frac_bits out of range")

    @property
    def name(self) -> str:
        m = self.total_bits - self.frac_bits
        return f"fixed{self.total_bits}_q{m}.{self.frac_bits}"

    @property
    def is_fixed_point(self) -> bool:
        return True

    @property
    def bits(self) -> int:
        return self.total_bits

    @property
    def storage_dtype(self):
        return jnp.int32 if self.total_bits == 32 else jnp.int64

    @property
    def scale(self) -> float:
        return float(2 ** self.frac_bits)

    # -- host-side conversions (paper: done in host code, saves FPGA area) --
    def encode(self, x) -> jax.Array:
        scaled = jnp.round(jnp.asarray(x, jnp.float64) * self.scale)
        return scaled.astype(self.storage_dtype)

    def decode(self, q) -> jax.Array:
        return q.astype(jnp.float64) / self.scale

    # -- device-side arithmetic ---------------------------------------------
    def fadd(self, a, b):
        return a + b

    def fsub(self, a, b):
        return a - b

    def fmul(self, a, b):
        """(a * b) >> frac_bits with a wide intermediate, round-to-nearest.

        int32 storage: exact via an int64 intermediate.
        int64 storage: the 128-bit product is emulated by a 32/32 limb
        split.  ``al*bl`` is computed in uint64 (exact: both < 2^32);
        cross terms fit signed int64 while |q-values| < 2^31 on the high
        limb, i.e. decoded magnitudes < 2^23 for Q24.40 -- exactly the
        headroom the paper's 24 integer bits provide.
        """
        f = self.frac_bits
        if self.total_bits == 32:
            wide = a.astype(jnp.int64) * b.astype(jnp.int64)
            wide = wide + (np.int64(1) << (f - 1))  # round to nearest
            return (wide >> f).astype(self.storage_dtype)
        # int64 path: a = ah*2^32 + al, b = bh*2^32 + bl (al, bl unsigned).
        mask = (np.int64(1) << 32) - 1
        ah, al = a >> 32, (a & mask).astype(jnp.uint64)
        bh, bl = b >> 32, (b & mask).astype(jnp.uint64)
        lo = ((al * bl) >> np.uint64(f)).astype(jnp.int64)  # exact in uint64
        cross = ah * bl.astype(jnp.int64) + al.astype(jnp.int64) * bh
        shift = f - 32  # f > 32 for Q24.40
        cross = (cross + (np.int64(1) << (shift - 1))) >> shift
        hi = (ah * bh) << (64 - f)
        return hi + cross + lo

    def fdiv(self, a, b):
        wide_a = a.astype(jnp.int64) << self.frac_bits if self.total_bits == 32 else a << 0
        if self.total_bits == 32:
            return (wide_a // b.astype(jnp.int64)).astype(self.storage_dtype)
        # 64-bit: divide via float64 reciprocal (documented approximation)
        rec = 1.0 / (b.astype(jnp.float64) / self.scale)
        return self.encode(self.decode(a) * rec)

    def contract(self, a, b, subscripts: str):
        """Fixed-point einsum: per-product rescale, then integer sum.

        Products are shifted *before* accumulation so partial sums stay in
        range (the HLS flow sizes its accumulators identically).  The
        contraction is expressed as broadcast-multiply + sum, acceptable
        at CFD operator sizes (p <= 16)."""
        in_spec, out_spec = subscripts.split("->")
        sa, sb = in_spec.split(",")
        # broadcast to the union index space
        union = sa + "".join(c for c in sb if c not in sa)
        dims = {}
        for c, d in zip(sa, a.shape):
            dims[c] = d
        for c, d in zip(sb, b.shape):
            dims[c] = d
        def expand(x, s):
            shape = tuple(dims[c] if c in s else 1 for c in union)
            perm_src = [s.index(c) for c in union if c in s]
            x = jnp.transpose(x, perm_src)
            return jnp.reshape(x, shape)

        prod = self.fmul(
            jnp.broadcast_to(expand(a, sa), tuple(dims[c] for c in union)),
            jnp.broadcast_to(expand(b, sb), tuple(dims[c] for c in union)),
        )
        sum_axes = tuple(i for i, c in enumerate(union) if c not in out_spec)
        res = jnp.sum(prod, axis=sum_axes, dtype=self.storage_dtype)
        # reorder to out_spec
        remaining = [c for c in union if c in out_spec]
        perm = [remaining.index(c) for c in out_spec]
        return jnp.transpose(res, perm)


Policy = object  # FloatPolicy | FixedPointPolicy

F64 = FloatPolicy("float64")
F32 = FloatPolicy("float32")
BF16 = FloatPolicy("bfloat16", accum_dtype="float32")
FIXED64 = FixedPointPolicy(64, 40)  # the paper's ap_fixed<64,24> (Q24.40)
FIXED32 = FixedPointPolicy(32, 24)  # the paper's ap_fixed<32,8>  (Q8.24)

POLICIES = {p.name: p for p in (F64, F32, BF16, FIXED64, FIXED32)}
