"""The paper's Sec. 5 application: interpolation -> gradient -> inverse
Helmholtz, with gy, gz and v streamed out (``examples/cfd_pipeline.cfd``
layout: gx is (x, y, z), gy is (y, x, z), gz is (z, x, y))."""
from __future__ import annotations

from bench import work
from bench.programs.inverse_helmholtz import helmholtz

ELEMENT_INPUTS = ("u", "D")
SHARED = ("A", "Dx", "Dy", "Dz", "S")
OUTPUTS = ("gy", "gz", "v")


def flops_per_element(p: int) -> int:
    return (work.interpolation_flops(p) + work.gradient_flops(p)
            + work.inverse_helmholtz_flops(p))


def bytes_per_element(p: int, bytes_per_scalar: int = 4) -> int:
    """u and D in, gy, gz and v out."""
    return work.stream_bytes(p, 5, bytes_per_scalar)


def outputs(ops, shared: dict, ins: dict) -> dict:
    A, Dx, Dy, Dz, S = (ops.asarray(shared[k]) for k in SHARED)
    w = ops.mode(A, ops.mode(A, ops.mode(A, ops.asarray(ins["u"]), 1), 2),
                 3)
    gx = ops.mode(Dx, w, 1)
    gy = ops.mode(Dy, w, 2).transpose(0, 2, 1, 3)
    gz = ops.mode(Dz, w, 3).transpose(0, 3, 1, 2)
    return {"gy": gy, "gz": gz,
            "v": helmholtz(ops, S, ops.asarray(ins["D"]), gx)}
