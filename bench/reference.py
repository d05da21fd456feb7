"""Plain float64 arithmetic of the paper's operators, on element batches.

Element tensors are ``(n, p, p, p)`` with the element axis first.  This
is the benchmark's own copy: it imports nothing of the program, so a
change to the program cannot move the yardstick.  ``programs/*.py``
compose these operators (``mode``, ``mul``, ``asarray``) into each
program's outputs; ``control.py`` gives the same three in a lower
precision.
"""
from __future__ import annotations

import numpy as np


def mode(M: np.ndarray, x: np.ndarray, axis: int) -> np.ndarray:
    """y[.., i, ..] = sum_l M[i, l] x[.., l, ..] along ``axis`` (1..3)."""
    y = np.tensordot(M, x, axes=([1], [axis]))
    return np.moveaxis(y, 0, axis)


def mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return a * b


def asarray(a) -> np.ndarray:
    return np.asarray(a, dtype=np.float64)
