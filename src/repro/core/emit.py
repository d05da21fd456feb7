"""Backend: IR -> executable JAX (the C99-emission analogue).

Where the paper emits HLS-ready C99 + pragmas and lets Vitis build the CU,
we emit JAX callables and let XLA (or Pallas, for matched patterns) build
the TPU program.  Three backends, mirroring the paper's design space:

  * ``xla``     -- the whole program as one jitted function (XLA fuses
    freely).  This is the default production path.
  * ``staged``  -- one jitted function *per scheduled group*, executed in
    sequence with materialized intermediates.  This models the FIFO-
    streamed dataflow CU and is what the per-stage analysis/benchmarks
    inspect (paper's Dataflow 1/2/3/7-compute experiments).
  * ``pallas``  -- the whole program as one hand-tiled kernel
    (``pallas_impl``, found by ``flow.patterns``); ``flow.compile``
    compiles a stage no kernel matches with ``xla`` and warns.

Batching over the implicit element loop is vmap over axis 0 of the
element-marked inputs/outputs; sharding the element axis over the mesh is
layered on top by ``repro.cfd.simulation`` / the launchers (the paper's CU
replication).
"""
from __future__ import annotations

import dataclasses
import string
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import ir
from .precision import FixedPointPolicy, FloatPolicy
from .schedule import Schedule, schedule as make_schedule

_LETTERS = string.ascii_letters


def einsum_spec(node: ir.Einsum) -> str:
    """Render integer index ids as an einsum subscript string."""
    ids: List[int] = []
    for subs in node.in_subs:
        for i in subs:
            if i not in ids:
                ids.append(i)
    if len(ids) > len(_LETTERS):
        raise ir.IRError("einsum with > 52 distinct indices")
    letter = {i: _LETTERS[k] for k, i in enumerate(ids)}
    ins = ",".join("".join(letter[i] for i in subs) for subs in node.in_subs)
    out = "".join(letter[i] for i in node.out_subs)
    return f"{ins}->{out}"


# ---------------------------------------------------------------------------
# node evaluation
# ---------------------------------------------------------------------------


def _eval_einsum_float(node: ir.Einsum, args: Sequence[jax.Array], policy: FloatPolicy):
    spec = einsum_spec(node)
    kwargs = {"precision": policy.precision}
    if policy.accum_dtype is not None:
        kwargs["preferred_element_type"] = jnp.dtype(policy.accum_dtype)
    out = jnp.einsum(spec, *args, **kwargs)
    return out.astype(policy.dtype)


def _eval_einsum_fixed(node: ir.Einsum, args, policy: FixedPointPolicy):
    spec = einsum_spec(node)
    if len(args) == 1:
        # transpose/diag/reduce: integer-safe through jnp.einsum
        return jnp.einsum(spec, args[0])
    if len(args) == 2:
        return policy.contract(args[0], args[1], spec)
    # n-ary: left-fold (the rewriter normally factorizes these away)
    raise ir.IRError(
        "fixed-point backend requires factorized (binary) einsums; "
        "run rewrite.optimize first"
    )


def _eval_ewise(node: ir.Ewise, args, policy):
    if isinstance(policy, FixedPointPolicy):
        if node.op == "add":
            return policy.fadd(*args)
        if node.op == "sub":
            return policy.fsub(*args)
        if node.op == "mul":
            return policy.fmul(*args)
        if node.op == "div":
            return policy.fdiv(*args)
        raise ir.IRError(f"fixed-point ewise {node.op} unsupported")
    a = args[0]
    if node.op == "add":
        return a + args[1]
    if node.op == "sub":
        return a - args[1]
    if node.op == "mul":
        return a * args[1]
    if node.op == "div":
        return a / args[1]
    if node.op == "neg":
        return -a
    if node.op == "scale":
        return a * node.const
    raise ir.IRError(f"unknown ewise op {node.op}")


def evaluate(
    prog: ir.Program,
    env: Dict[str, jax.Array],
    policy=FloatPolicy("float32"),
) -> Dict[str, jax.Array]:
    """Evaluate the program for ONE element, given named input arrays."""
    vals: Dict[int, jax.Array] = {}
    for name, inp in prog.inputs.items():
        if name not in env:
            raise KeyError(f"missing input {name!r}")
        x = env[name]
        if isinstance(policy, FloatPolicy):
            x = jnp.asarray(x, policy.dtype)
        vals[inp.uid] = x

    for node in prog.toposort():
        if node.uid in vals:
            continue
        args = [vals[o.uid] for o in node.operands()]
        if isinstance(node, ir.Einsum):
            if isinstance(policy, FixedPointPolicy):
                vals[node.uid] = _eval_einsum_fixed(node, args, policy)
            else:
                vals[node.uid] = _eval_einsum_float(node, args, policy)
        elif isinstance(node, ir.Ewise):
            vals[node.uid] = _eval_ewise(node, args, policy)
        else:
            raise ir.IRError(f"cannot evaluate {node!r}")
    return {name: vals[n.uid] for name, n in prog.outputs.items()}


# ---------------------------------------------------------------------------
# compiled artifacts
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompiledProgram:
    """A compiled tensor-expression program.

    ``element_fn``  -- single-element callable (dict -> dict).
    ``batched_fn``  -- vmapped over the element axis of element vars.
    ``stage_fns``   -- per-group callables (staged backend only).
    """

    program: ir.Program
    policy: object
    element_fn: Callable[..., Dict[str, jax.Array]]
    batched_fn: Callable[..., Dict[str, jax.Array]]
    schedule: Optional[Schedule] = None
    stage_fns: Optional[List[Callable]] = None
    backend: str = "xla"
    #: inputs whose device buffers XLA may reuse for outputs (a
    #: MemoryPlan hint; the driver must not reuse them after a call)
    donate_args: Tuple[str, ...] = ()

    def __call__(self, **env):
        return self.batched_fn(env)


def _element_callable(prog: ir.Program, policy) -> Callable:
    def fn(env: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return evaluate(prog, env, policy)

    return fn


def _batched_callable(
    prog: ir.Program,
    policy,
    *,
    donate_args: Sequence[str] = (),
    jit: bool = True,
) -> Callable:
    """Batched callable; with ``jit`` the list-form function is jitted so
    per-array donation hints (from a MemoryPlan) can be applied."""
    names = list(prog.inputs)
    elem = set(prog.element_vars)
    unknown = [n for n in donate_args if n not in names]
    if unknown:
        raise ValueError(f"donate_args not program inputs: {unknown}")

    def list_fn(*arrays):
        env = dict(zip(names, arrays))
        return evaluate(prog, env, policy)

    in_axes = tuple(0 if n in elem else None for n in names)
    vfn = jax.vmap(list_fn, in_axes=in_axes, out_axes=0)
    if jit:
        donate = tuple(i for i, n in enumerate(names) if n in donate_args)
        vfn = jax.jit(vfn, donate_argnums=donate)

    def fn(env: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return vfn(*[env[n] for n in names])

    return fn


def _staged_callables(
    prog: ir.Program, sched: Schedule, policy
) -> Tuple[List[Callable], Callable]:
    """One jitted fn per group; driver threads streams between them."""
    name_of: Dict[int, str] = {v.uid: k for k, v in prog.inputs.items()}

    stage_fns: List[Callable] = []
    stage_sigs: List[Tuple[List[int], List[int]]] = []
    for group in sched.groups:
        in_uids = [n.uid for n in group.in_streams]
        out_uids = [n.uid for n in group.out_streams]
        nodes = list(group.nodes)

        def run_group(args: List[jax.Array], *, _nodes=nodes, _in=tuple(in_uids)):
            vals: Dict[int, jax.Array] = dict(zip(_in, args))
            for node in _nodes:
                a = [vals[o.uid] for o in node.operands()]
                if isinstance(node, ir.Einsum):
                    if isinstance(policy, FixedPointPolicy):
                        vals[node.uid] = _eval_einsum_fixed(node, a, policy)
                    else:
                        vals[node.uid] = _eval_einsum_float(node, a, policy)
                else:
                    vals[node.uid] = _eval_ewise(node, a, policy)
            return vals

        def stage(args, _run=run_group, _out=tuple(out_uids)):
            vals = _run(list(args))
            return [vals[u] for u in _out]

        stage_fns.append(jax.jit(stage))
        stage_sigs.append((in_uids, out_uids))

    def element_fn(env: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        live: Dict[int, jax.Array] = {}
        for k, v in prog.inputs.items():
            x = env[k]
            if isinstance(policy, FloatPolicy):
                x = jnp.asarray(x, policy.dtype)
            live[v.uid] = x
        for fn, (in_uids, out_uids) in zip(stage_fns, stage_sigs):
            outs = fn([live[u] for u in in_uids])
            live.update(dict(zip(out_uids, outs)))
        return {name: live[n.uid] for name, n in prog.outputs.items()}

    return stage_fns, element_fn


def compile_program(
    prog: ir.Program,
    *,
    policy=FloatPolicy("float32"),
    backend: str = "xla",
    vmem_budget: Optional[int] = None,
    max_groups: Optional[int] = None,
    pallas_impl: Optional[Callable] = None,
    jit: bool = True,
    donate_args: Sequence[str] = (),
) -> CompiledProgram:
    """Compile an IR program to an executable (the Olympus entry point).

    ``pallas_impl``: a callable ``(env) -> outputs`` implementing the whole
    batched program as a fused kernel; used when ``backend='pallas'``.

    ``donate_args``: input names whose buffers XLA may alias for outputs
    (a ``repro.memory`` MemoryPlan hint; ``xla`` backend only).
    """
    if donate_args and (backend != "xla" or not jit):
        raise ValueError(
            "donate_args requires the jitted 'xla' backend "
            f"(got backend={backend!r}, jit={jit})"
        )
    sched = None
    if backend in ("staged",) or vmem_budget is not None or max_groups is not None:
        kwargs = {}
        if vmem_budget is not None:
            kwargs["vmem_budget"] = vmem_budget
        if max_groups is not None:
            kwargs["max_groups"] = max_groups
        bps = policy.bits // 8
        sched = make_schedule(prog, bytes_per_scalar=bps, **kwargs)

    if backend == "pallas":
        if pallas_impl is None:
            raise ValueError("backend='pallas' requires pallas_impl")
        batched = pallas_impl
        element = _element_callable(prog, policy)
        return CompiledProgram(
            program=prog, policy=policy, element_fn=element,
            batched_fn=jax.jit(batched) if jit else batched,
            schedule=sched, backend="pallas",
        )

    if backend == "staged":
        stage_fns, element = _staged_callables(prog, sched, policy)
        names = list(prog.inputs)
        elem = set(prog.element_vars)

        def list_fn(*arrays):
            return element(dict(zip(names, arrays)))

        in_axes = tuple(0 if n in elem else None for n in names)
        vfn = jax.vmap(list_fn, in_axes=in_axes, out_axes=0)

        def batched(env):
            return vfn(*[env[n] for n in names])

        return CompiledProgram(
            program=prog, policy=policy, element_fn=element,
            batched_fn=batched, schedule=sched, stage_fns=stage_fns,
            backend="staged",
        )

    # default: xla
    element = _element_callable(prog, policy)
    batched = _batched_callable(prog, policy, donate_args=donate_args, jit=jit)
    return CompiledProgram(
        program=prog, policy=policy, element_fn=element,
        batched_fn=batched, schedule=sched, backend="xla",
        donate_args=tuple(donate_args),
    )
