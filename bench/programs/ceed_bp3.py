"""CEED bake-off problem BP3: the scalar stiffness operator on hexahedral
elements at q = p + 1 quadrature points a direction,

    ur = (Bd x B x B) u,  us = (B x Bd x B) u,  ut = (B x B x Bd) u
    [vr vs vt] = G [ur us ut]
    y = (Bd x B x B)^T vr + (B x Bd x B)^T vs + (B x B x Bd)^T vt

with ``B`` and ``Bd`` the basis and its derivative at the quadrature
points, (q, p), shared by every element, and G the symmetric 3 x 3
geometric factor at each of the q^3 points, six element streams."""
from __future__ import annotations

ELEMENT_INPUTS = ("u", "Grr", "Grs", "Grt", "Gss", "Gst", "Gtt")
SHARED = ("B", "Bd")
OUTPUTS = ("y",)


def quadrature_points(p: int) -> int:
    return p + 1


def tensor_product_flops(p: int) -> int:
    """One (q, p) matrix along each of three modes, mode 1 first, from p^3
    nodes to q^3 points; the transposed application from q^3 back to p^3
    counts the same."""
    q = quadrature_points(p)
    return 2 * q * p ** 3 + 2 * q * q * p * p + 2 * q ** 3 * p


def flops_per_element(p: int) -> int:
    """Three tensor-product gradients, three transposed, the geometric
    factor (three products and two adds a component at each point) and
    the two adds of the three transposed terms."""
    q = quadrature_points(p)
    return 6 * tensor_product_flops(p) + 15 * q ** 3 + 2 * p ** 3


def bytes_per_element(p: int, bytes_per_scalar: int = 4) -> int:
    """u and the six geometric factors in, y out."""
    q = quadrature_points(p)
    return bytes_per_scalar * (2 * p ** 3 + 6 * q ** 3)


def outputs(ops, shared: dict, ins: dict) -> dict:
    B, Bd = (ops.asarray(shared[k]) for k in SHARED)
    u = ops.asarray(ins["u"])
    Grr, Grs, Grt, Gss, Gst, Gtt = (ops.asarray(ins[k])
                                    for k in ELEMENT_INPUTS[1:])
    grad = ((Bd, B, B), (B, Bd, B), (B, B, Bd))

    def apply(X, Y, Z, x):
        return ops.mode(Z, ops.mode(Y, ops.mode(X, x, 1), 2), 3)

    ur, us, ut = (apply(X, Y, Z, u) for X, Y, Z in grad)
    v = (ops.mul(Grr, ur) + ops.mul(Grs, us) + ops.mul(Grt, ut),
         ops.mul(Grs, ur) + ops.mul(Gss, us) + ops.mul(Gst, ut),
         ops.mul(Grt, ur) + ops.mul(Gst, us) + ops.mul(Gtt, ut))
    yr, ys, yt = (apply(X.T, Y.T, Z.T, vc)
                  for (X, Y, Z), vc in zip(grad, v))
    return {"y": yr + ys + yt}
