"""The algorithmic work of the paper's operators, per element.

These counts depend on the mathematics only, never on how the program
implements it: a roofline share built on them reads the same work
whether XLA, a Pallas kernel or a fused relayout computes the operator,
so it cannot pass 100% honestly.  A program's module under
``bench/programs/`` adds up its operators' counts.
"""
from __future__ import annotations


def mode_contraction_flops(p: int) -> int:
    """One (p, p) matrix applied along one mode of a (p, p, p) tensor:
    p^3 outputs, each p multiplies and p adds."""
    return 2 * p ** 4


def inverse_helmholtz_flops(p: int) -> int:
    """The paper's Eq. 2: (12p + 1) p^3 -- six mode contractions and the
    pointwise product with D."""
    return (12 * p + 1) * p ** 3


def interpolation_flops(p: int) -> int:
    """(A x A x A) u: three mode contractions."""
    return 3 * mode_contraction_flops(p)


def gradient_flops(p: int) -> int:
    """gx, gy and gz: one mode contraction each."""
    return 3 * mode_contraction_flops(p)


def stream_bytes(p: int, n_streams: int, bytes_per_scalar: int = 4) -> int:
    """Least HBM traffic of ``n_streams`` element tensors of p^3 scalars,
    each read or written once."""
    return n_streams * bytes_per_scalar * p ** 3


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the operations
    over peak FLOP/s and the bytes over peak HBM bandwidth."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])

