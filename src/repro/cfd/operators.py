"""The paper's three CFD operators, built through the DSL-to-executable
flow (core.api), with selectable backend/precision -- the per-kernel
equivalent of the Olympus "Optimize" step.

The composed application (interpolation -> gradient -> inverse
Helmholtz) is no longer hand-wired here: :data:`CFD_PIPELINE_SRC` is the
whole pipeline as one CFDlang program, and :func:`build_cfd_chain`
compiles it through ``repro.flow`` -- the generic tool flow derives the
stage programs, the inter-stage residency, and (for ``pallas`` stages)
the kernel dispatch that ~180 lines of builder code used to encode.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

from .. import flow
from ..core import api, dsl
from ..core.emit import CompiledProgram
from ..core.precision import POLICIES
from ..kernels.helmholtz import ops as helmholtz_ops
from ..memory import layout
from ..memory.chain import ChainPlan, ProgramChain
from ..memory.plan import MemoryPlan


def pallas_block_elements(plan: Optional[MemoryPlan] = None) -> int:
    """Resolve the Pallas kernel's block size from a MemoryPlan.

    The plan carries the VMEM-budgeted block (``block_elements``, a
    divisor of its E); without one the kernel default stands.
    """
    if plan is not None and plan.block_elements:
        return plan.block_elements
    return helmholtz_ops.DEFAULT_BLOCK_ELEMENTS


def build_inverse_helmholtz(
    p: int = 11,
    *,
    policy="float32",
    backend: str = "xla",
    optimize: bool = True,
    max_groups: Optional[int] = None,
    block_elements: Optional[int] = None,
    plan: Optional[MemoryPlan] = None,
    donate_args: Sequence[str] = (),
) -> CompiledProgram:
    """Compile the Inverse Helmholtz operator (paper Fig. 2).

    backend:
      * ``xla``    -- factorized einsum chain, one jitted program.
      * ``staged`` -- one jitted stage per scheduled group (dataflow view).
      * ``pallas`` -- the fused TPU kernel (kernels/helmholtz); on CPU use
        kernel tests' interpret mode instead.  Its ``block_elements``
        defaults to the plan's VMEM-budgeted block when a MemoryPlan is
        given (explicit ``block_elements`` still wins).
    """
    pallas_impl = None
    if backend == "pallas":
        be = (
            block_elements if block_elements is not None
            else pallas_block_elements(plan)
        )
        pallas_impl = helmholtz_ops.make_pallas_impl(
            block_elements=be,
            vmem_limit_bytes=(
                layout.vmem_budget_bytes(plan.target) if plan is not None
                else None
            ),
        )
    return api.compile_cfdlang(
        dsl.INVERSE_HELMHOLTZ_SRC.format(p=p),
        element_vars=("u", "D", "v"),
        policy=policy,
        optimize=optimize,
        backend=backend,
        max_groups=max_groups,
        pallas_impl=pallas_impl,
        donate_args=donate_args,
    )


def build_interpolation(
    n: int = 11,
    m: int = 11,
    *,
    policy="float32",
    backend: str = "xla",
    optimize: bool = True,
    max_groups: Optional[int] = None,
) -> CompiledProgram:
    return api.compile_cfdlang(
        dsl.INTERPOLATION_SRC.format(n=n, m=m),
        element_vars=("u", "v"),
        policy=policy,
        optimize=optimize,
        backend=backend,
        max_groups=max_groups,
    )


def build_gradient(
    nx: int = 8,
    ny: int = 7,
    nz: int = 6,
    *,
    policy="float32",
    backend: str = "xla",
    optimize: bool = True,
    max_groups: Optional[int] = None,
) -> CompiledProgram:
    return api.compile_cfdlang(
        dsl.GRADIENT_SRC.format(nx=nx, ny=ny, nz=nz),
        element_vars=("u", "gx", "gy", "gz"),
        policy=policy,
        optimize=optimize,
        backend=backend,
        max_groups=max_groups,
    )


def chain_stage_block_elements(
    chain_plan: Optional[ChainPlan], stage: str
) -> Optional[int]:
    """The VMEM-budgeted block a ChainPlan assigned to one stage (None
    when no plan, or the plan does not know the stage)."""
    if chain_plan is None:
        return None
    for sp in chain_plan.stages:
        if sp.name == stage and sp.block_elements:
            return sp.block_elements
    return None


#: The paper's full application as ONE CFDlang program: interpolation
#: (A), gradient (Dx/Dy/Dz), and inverse Helmholtz (S, D) over a shared
#: element stream.  ``repro.flow`` cuts it into the three pipeline
#: stages at the declared temporaries -- no builder code per operator.
CFD_PIPELINE_SRC = """
var input  A  : [{p} {p}]
var input  Dx : [{p} {p}]
var input  Dy : [{p} {p}]
var input  Dz : [{p} {p}]
var input  S  : [{p} {p}]
var input elem u  : [{p} {p} {p}]
var input elem D  : [{p} {p} {p}]
var output elem gy : [{p} {p} {p}]
var output elem gz : [{p} {p} {p}]
var output elem v  : [{p} {p} {p}]
var w  : [{p} {p} {p}]
var gx : [{p} {p} {p}]
var t  : [{p} {p} {p}]
var r  : [{p} {p} {p}]
w = A # A # A # u . [[1 6][3 7][5 8]]
gx = Dx # w . [[1 2]]
gy = Dy # w . [[1 3]]
gz = Dz # w . [[1 4]]
t = S # S # S # gx . [[1 6][3 7][5 8]]
r = D * t
v = S # S # S # r . [[0 6][2 7][4 8]]
"""

#: The canonical stage cuts: interpolation owns ``w``, the gradient its
#: three derivatives, the Helmholtz stage the final solve.
CFD_PIPELINE_STAGES = (
    ("interp", ("w",)),
    ("grad", ("gx", "gy", "gz")),
    ("helmholtz", ("v",)),
)


def compile_cfd_pipeline(
    p: int = 11,
    *,
    policy="float32",
    backends: Union[str, Tuple[str, str, str]] = "xla",
    stage_blocks=None,
    **flow_kwargs,
) -> "flow.CompiledSystem":
    """Compile the whole CFD application through ``repro.flow`` at the
    paper's operator-granularity stage cuts."""
    if isinstance(backends, str):
        backends = (backends, backends, backends)
    return flow.compile(
        CFD_PIPELINE_SRC.format(p=p),
        name=f"cfd_pipeline_p{p}",
        policy=policy,
        stages=CFD_PIPELINE_STAGES,
        backends=backends,
        stage_blocks=stage_blocks,
        **flow_kwargs,
    )


def build_cfd_chain(
    p: int = 11,
    *,
    policy="float32",
    backends: Union[str, Tuple[str, str, str]] = "xla",
    helmholtz_plan: Optional[MemoryPlan] = None,
    chain_plan: Optional[ChainPlan] = None,
) -> ProgramChain:
    """The paper's full application as one ProgramChain:

        interpolation -> gradient -> inverse Helmholtz

    Compiled end-to-end from :data:`CFD_PIPELINE_SRC` by ``repro.flow``:
    the flow extracts the three stage programs, wires interpolation's
    ``w`` into the gradient and the gradient's ``gx`` into the Helmholtz
    solve (both HBM-resident -- no host round-trip), and streams
    ``gy``/``gz``/``v`` back to the host.

    For a Pallas Helmholtz stage, pass the ChainPlan back in as
    ``chain_plan`` so the kernel's block size comes from the plan's
    per-stage VMEM budget (plan first against a plan-only chain, then
    rebuild the executable chain with the plan):

        ch = build_cfd_chain(p)                       # plan-only (xla)
        plan = chain.plan_chain(ch, backends=("xla", "xla", "pallas"))
        ch = build_cfd_chain(p, backends=("xla", "xla", "pallas"),
                             chain_plan=plan)
        simulation.run_chain(ch, plan)
    """
    blocks = {}
    blk = chain_stage_block_elements(chain_plan, "helmholtz")
    if blk is None and helmholtz_plan is not None and (
            helmholtz_plan.block_elements):
        blk = helmholtz_plan.block_elements
    if blk:
        blocks["helmholtz"] = blk
    return compile_cfd_pipeline(
        p, policy=policy, backends=backends, stage_blocks=blocks
    ).chain


def flops_per_element(p: int) -> int:
    """Paper Eq. (2)."""
    return (12 * p + 1) * p ** 3
