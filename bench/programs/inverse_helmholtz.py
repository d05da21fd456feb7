"""The paper's Fig. 2 inverse Helmholtz operator:
t = (S^T x S^T x S^T) u, r = D * t, v = (S x S x S) r."""
from __future__ import annotations

from bench import work

ELEMENT_INPUTS = ("u", "D")
SHARED = ("S",)
OUTPUTS = ("v",)


def flops_per_element(p: int) -> int:
    return work.inverse_helmholtz_flops(p)


def bytes_per_element(p: int, bytes_per_scalar: int = 4) -> int:
    """u and D in, v out."""
    return work.stream_bytes(p, 3, bytes_per_scalar)


def helmholtz(ops, S, D, u):
    t = ops.mode(S, ops.mode(S, ops.mode(S, u, 1), 2), 3)
    r = ops.mul(D, t)
    St = S.T
    return ops.mode(St, ops.mode(St, ops.mode(St, r, 1), 2), 3)


def outputs(ops, shared: dict, ins: dict) -> dict:
    S = ops.asarray(shared["S"])
    return {"v": helmholtz(ops, S, ops.asarray(ins["D"]),
                           ops.asarray(ins["u"]))}
