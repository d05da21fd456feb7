"""Element-batched simulation driver -- the Olympus system/host layer.

Implements the paper's section 3.1 quantities on the TPU mesh:

  * **batch**: ``E`` elements processed per dispatch.  The paper sizes E
    so a batch fills one 256 MB HBM pseudo-channel; here the sizing (and
    every other memory decision) comes from an explicit
    :class:`repro.memory.MemoryPlan` -- the driver holds no hardcoded
    batch size.
  * **N_b = N_eq / E** batches, **I = N_b / N_cu** iterations, where the
    CU count is the number of mesh devices the element axis is sharded
    over (CU replication == data parallelism over elements).
  * **transfer pipelining**: batch k+K..k+1 transfer host->device while
    batch k computes, through the generic K-deep engine in
    ``repro.memory.pipeline`` (K=1 is the ping/pong channel pair of
    Fig. 14a; K=0 is the serial baseline).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..memory import chain as memchain
from ..memory import channels as memchannels
from ..memory import dse as memdse
from ..memory import pipeline as mempipe
from ..memory.placement import DeviceTopology
from ..memory.plan import MemoryPlan
from .operators import build_inverse_helmholtz, flops_per_element


@dataclasses.dataclass
class SimConfig:
    p: int = 11
    n_eq: int = 2_000_000          # paper: 2M elements simulated
    #: E -- None lets the MemoryPlan auto-size it from the channel model
    batch_elements: Optional[int] = None
    policy: str = "float32"
    backend: str = "xla"
    double_buffer: bool = True
    #: K batches staged ahead; None derives it from ``double_buffer``
    prefetch_depth: Optional[int] = None
    seed: int = 0

    @property
    def depth(self) -> int:
        if self.prefetch_depth is not None:
            return self.prefetch_depth
        return 1 if self.double_buffer else 0

    @property
    def n_batches(self) -> int:
        if self.batch_elements is None:
            raise ValueError(
                "batch_elements unset -- resolve a MemoryPlan first "
                "(simulation.plan_config) or set it explicitly"
            )
        return self.n_eq // self.batch_elements

    def bytes_per_element(self, bytes_per_scalar: int = 4) -> int:
        # u, D in; v out  (S shared, amortized)
        return 3 * self.p ** 3 * bytes_per_scalar

    @classmethod
    def batch_for_channel(cls, p: int, channel_bytes: int = 256 * 2 ** 20,
                          bytes_per_scalar: int = 4) -> int:
        """The paper's E: elements whose I/O fits one HBM channel."""
        return channel_bytes // (3 * p ** 3 * bytes_per_scalar)


def element_mesh(devices=None) -> Mesh:
    """1-D mesh over all local devices: the CU-replication axis."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), ("elements",))


def plan_config(
    cfg: SimConfig,
    *,
    target: Optional[memchannels.MemoryTarget] = None,
    cu_count: int = 1,
) -> MemoryPlan:
    """Resolve the memory architecture for this simulation config.

    Explicit ``cfg.batch_elements`` is honored; otherwise the planner
    auto-sizes E against the target's pseudo-channel capacity.
    """
    return memdse.make_plan(
        cfg.p,
        target=target if target is not None else memchannels.detect_target(),
        policy=cfg.policy,
        backend=cfg.backend,
        batch_elements=cfg.batch_elements,
        prefetch_depth=cfg.depth,
        cu_count=cu_count,
        n_eq=cfg.n_eq,
    )


def _batch_generator(
    p: int, batch_elements: int, n_batches: int, seed: int
) -> Iterator[Dict[str, np.ndarray]]:
    """Deterministic, resumable synthetic element stream ([-1,1] data,
    matching the paper's range normalization)."""
    for b in range(n_batches):
        rng = np.random.default_rng(seed + b)
        yield {
            "D": rng.uniform(-1, 1, (batch_elements, p, p, p)).astype(np.float32),
            "u": rng.uniform(-1, 1, (batch_elements, p, p, p)).astype(np.float32),
        }


@dataclasses.dataclass
class SimResult:
    batches: int
    elements: int
    wall_s: float
    checksum: float
    plan: Optional[MemoryPlan] = None

    @property
    def gflops(self) -> float:
        return 0.0 if self.wall_s == 0 else (
            self.elements * 1e-9 / self.wall_s
        )


def run_simulation(
    cfg: SimConfig,
    *,
    mesh: Optional[Mesh] = None,
    max_batches: Optional[int] = None,
    S: Optional[np.ndarray] = None,
    plan: Optional[MemoryPlan] = None,
    tracer=None,
) -> SimResult:
    """Run the batched Inverse-Helmholtz simulation under a MemoryPlan.

    The plan supplies E, the prefetch depth, and donation hints; pass one
    explicitly (e.g. a DSE winner) or let ``plan_config`` derive it.
    Returns wall time and a checksum; GFLOPS is derived with the paper's
    op-count model by the caller (benchmarks/).

    ``tracer`` (``repro.trace.Tracer``; None = off) records the staging/
    dispatch/sync spans of the K-deep engine plus per-channel host byte
    counters from the plan's buffer table.
    """
    mesh = mesh or element_mesh()
    if plan is None:
        plan = plan_config(cfg, cu_count=int(mesh.devices.size))
    E = plan.batch_elements
    depth = plan.prefetch_depth

    # donation is an accelerator-path optimization; the CPU runtime warns
    # and ignores it, so only forward the hint off-host.  The plan also
    # supplies the Pallas kernel's VMEM-budgeted block_elements.
    donate = plan.donation if jax.default_backend() != "cpu" else ()
    compiled = build_inverse_helmholtz(
        cfg.p, policy=cfg.policy, backend=cfg.backend, donate_args=donate,
        plan=plan,
    )
    rng = np.random.default_rng(cfg.seed + 2 ** 31)
    if S is None:
        S = rng.uniform(-1, 1, (cfg.p, cfg.p)).astype(np.float32)

    elem_sharding = NamedSharding(mesh, P("elements"))
    repl_sharding = NamedSharding(mesh, P())
    S_dev = jax.device_put(S, repl_sharding)

    n_total = cfg.n_eq // E
    n = n_total if max_batches is None else min(max_batches, n_total)

    def stage(batch):
        return {
            k: jax.device_put(v, elem_sharding) for k, v in batch.items()
        }

    if tracer:
        from ..trace.attribution import (COUNTER_CHANNEL_BYTES,
                                         host_channel_bytes)

        ch_bytes = {
            str(c): float(b)
            for c, b in host_channel_bytes(plan.buffers).items()
        }
        inner_stage = stage

        def stage(batch):
            tracer.bump(COUNTER_CHANNEL_BYTES, ch_bytes)
            return inner_stage(batch)

    def compute(staged):
        return compiled.batched_fn({"S": S_dev, **staged})

    t0 = time.perf_counter()
    sums = mempipe.run_pipelined(
        compute,
        _batch_generator(cfg.p, E, n, cfg.seed),
        stage_fn=stage,
        depth=depth,
        reduce_fn=lambda out: jnp.sum(out["v"]),
        tracer=tracer,
        stage_name=plan.operator,
    )
    wall = time.perf_counter() - t0
    checksum = 0.0
    for s in sums:
        checksum += float(s)
    return SimResult(
        batches=n, elements=n * E, wall_s=wall, checksum=checksum, plan=plan
    )


def achieved_gflops(res: SimResult, p: int) -> float:
    """GFLOPS under the paper's Eq. (2)-(3) accounting."""
    n_op = res.elements * flops_per_element(p)
    return n_op / res.wall_s / 1e9 if res.wall_s > 0 else 0.0


# ---------------------------------------------------------------------------
# multi-operator chain driver (interpolation -> gradient -> Helmholtz)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ChainResult:
    """One run of a whole pipeline off a single ChainPlan."""

    batches: int
    elements: int
    wall_s: float
    checksums: Dict[str, float]
    plan: Optional[memchain.ChainPlan] = None
    #: full chain outputs, qualified "stage.output" (collect_outputs=True)
    outputs: Optional[Dict[str, np.ndarray]] = None
    #: whether stages were cross-batch pipelined (one dispatch ring per
    #: stage) or run back-to-back per batch (the serial baseline)
    pipelined_stages: bool = False
    #: per-stage local device groups the run actually executed on (None
    #: when the placement degenerated to the single global mesh)
    placement_groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    #: batch indices the StepMonitor flagged as stragglers (empty when no
    #: monitor was passed or nothing was flagged)
    straggler_batches: Tuple[int, ...] = ()


def _chain_batch_inputs(
    chain: memchain.ProgramChain,
    E: int,
    n_batches: int,
    seed: int,
    inputs: Optional[Dict[str, np.ndarray]],
) -> Iterator[Dict[str, np.ndarray]]:
    """Per-batch host-streamed inputs, qualified "stage.input".

    ``inputs`` supplies full arrays (element-axis leading) to slice;
    otherwise a deterministic synthetic stream is generated, matching
    ``_batch_generator``'s [-1, 1] normalization."""
    names = [
        f"{s.name}.{n}"
        for i, s in enumerate(chain.stages)
        for n, _ in chain.host_element_inputs(i)
    ]
    shapes = {
        f"{s.name}.{n}": v.shape
        for i, s in enumerate(chain.stages)
        for n, v in chain.host_element_inputs(i)
    }
    for b in range(n_batches):
        if inputs is not None:
            yield {q: inputs[q][b * E:(b + 1) * E] for q in names}
        else:
            rng = np.random.default_rng(seed + b)
            yield {
                q: rng.uniform(-1, 1, (E,) + shapes[q]).astype(np.float32)
                for q in names
            }


def chain_stage_fns(
    chain: memchain.ProgramChain,
    shared_for_stage: List[Dict[str, jax.Array]],
    out_names: List[str],
    *,
    stage_es: Optional[List[int]] = None,
    batch_elements: int = 0,
) -> List:
    """One ring callable per stage, ``(staged, carry) -> carry``.

    Stage i reads its bound inputs from the carry (HBM-resident
    handoffs), its shared operands from ``shared_for_stage[i]`` and its
    host streams from ``staged``.  Afterwards the batch keeps only what
    a later stage or the host still reads: a host stream leaves
    ``staged`` once its stage has read it, and a handoff leaves the
    carry after its last consumer -- otherwise every batch in the
    skewed ring would hold every stream of the chain until it retires.
    ``stage_es`` gives stages that run at a smaller E than the chain's
    ``batch_elements`` (re-blocked on device).
    """
    n = len(chain.stages)
    keep_after = []
    for i in range(n):
        keep = set(out_names)
        for j in range(i + 1, n):
            keep.update(f"{chain.stages[p].name}.{o}"
                        for p, o in chain.resolved[j].values())
        keep_after.append(keep)

    def make_stage_fn(i: int, s: memchain.ChainStage):
        batched_fn = s.compiled.batched_fn
        if stage_es and 0 < stage_es[i] < batch_elements:
            batched_fn = mempipe.reblock_batched_fn(
                batched_fn, tuple(s.program.element_vars), stage_es[i]
            )
        keep = keep_after[i]

        def run_stage(staged, carry):
            live: Dict[str, jax.Array] = dict(carry) if carry else {}
            env: Dict[str, jax.Array] = {}
            for name in s.program.inputs:
                if name in chain.resolved[i]:
                    p_idx, out_name = chain.resolved[i][name]
                    env[name] = live[
                        f"{chain.stages[p_idx].name}.{out_name}"
                    ]
                elif name in shared_for_stage[i]:
                    env[name] = shared_for_stage[i][name]
                else:
                    env[name] = staged.pop(f"{s.name}.{name}")
            outs = batched_fn(env)
            for out_name, val in outs.items():
                live[f"{s.name}.{out_name}"] = val
            return {q: v for q, v in live.items() if q in keep}

        return run_stage

    return [make_stage_fn(i, s) for i, s in enumerate(chain.stages)]


def run_chain(
    chain: memchain.ProgramChain,
    plan: Optional[memchain.ChainPlan] = None,
    *,
    n_eq: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    max_batches: Optional[int] = None,
    seed: int = 0,
    inputs: Optional[Dict[str, np.ndarray]] = None,
    shared: Optional[Dict[str, np.ndarray]] = None,
    collect_outputs: bool = False,
    pipeline_stages: Optional[bool] = None,
    tracer=None,
    monitor=None,
    metrics=None,
) -> ChainResult:
    """Execute a whole multi-operator pipeline off one ChainPlan.

    Bound streams (e.g. interpolation's ``w`` into the gradient) never
    leave the device -- exactly the residency the plan prices.  The
    execution schedule comes from the plan's ``pipeline`` spec: in
    pipelined mode each stage gets its own dispatch ring and stage i of
    batch k is dispatched alongside stage i+1 of batch k-1
    (``memory.pipeline.run_stage_pipelined``); in serial mode stages run
    back-to-back per batch -- the paper's baseline, bitwise-equal to the
    pipelined schedule at float32.  ``pipeline_stages`` overrides the
    plan's mode (e.g. to force the serial baseline for an equality
    test or a ladder rung).

    Host-streamed inputs come from ``inputs`` (full arrays, qualified
    "stage.input") or a deterministic synthetic stream; ``shared``
    supplies the batch-invariant operands by bare name (synthesized when
    omitted).

    ``collect_outputs`` returns the concatenated chain outputs for
    verification against an unchained reference; by default only a
    checksum per output crosses back (the plan's host-out streams are
    still priced -- the reduction is a measurement convenience, as in
    ``run_simulation``).

    ``tracer`` (``repro.trace.Tracer``; None = off) records the full
    span hierarchy -- chain run -> per-stage slot -> dispatch/handoff --
    plus per-channel host byte, pad-element and CU-occupancy counters
    from the plan, ready for ``repro.trace.attribution``.  ``monitor``
    (a ``runtime.StepMonitor``) watches per-batch retire times; flagged
    batches are annotated on their sync spans and reported in
    ``ChainResult.straggler_batches``.  ``metrics`` (a ``repro.metrics``
    registry) records the driver's always-on per-stage dispatch/stall
    histograms keyed by the plan signature.  None changes results.
    """
    mesh = mesh or element_mesh()
    if n_eq is None and inputs:
        # the data bounds the problem -- derive n_eq before planning so
        # the auto-sized E can never exceed what the arrays hold
        n_eq = min(v.shape[0] for v in inputs.values())
    local_devices = list(mesh.devices.flatten())
    if plan is None:
        plan = memchain.plan_chain(
            chain, target=memchannels.detect_target(),
            cu_count=len(local_devices),
            # per-device kind derivation: a mixed local pool becomes a
            # grouped topology instead of N copies of device 0's platform
            topology=DeviceTopology.from_jax(local_devices),
            n_eq=n_eq,
        )
    planned = tuple(sp.backend for sp in plan.stages)
    compiled = tuple(s.backend for s in chain.stages)
    if planned != compiled:
        warnings.warn(
            f"run_chain: plan backends {planned} differ from the "
            f"compiled chain's {compiled}; executing the compiled chain. "
            "Rebuild it for the plan (e.g. operators.build_cfd_chain("
            "backends=..., chain_plan=plan)) to run as planned.",
            RuntimeWarning,
        )
    E = plan.batch_elements
    pipe = plan.pipeline
    if pipe is None:  # legacy plan: derive the spec from the stage Ks
        pipe = memchain.derive_pipeline(
            [sp.prefetch_depth for sp in plan.stages]
        )
    stage_depths = list(pipe.stage_depths)
    if len(stage_depths) != len(chain.stages):
        # a plan from a differently-staged compile still executes the
        # compiled chain (warned above): carry the plan's deepest K as
        # host staging and keep its mode with depth-1 rings
        stage_depths = [max(stage_depths)] + (
            [1 if pipe.pipelined else 0] * (len(chain.stages) - 1)
        )
    if pipeline_stages is None:
        pipeline_stages = pipe.pipelined
    if pipeline_stages:
        depths = stage_depths
        # forcing the mode on cannot pipeline a plan with no inter-stage
        # ring depth: execution (and the reported flag) stays serial
        pipeline_stages = len(depths) > 1 and any(d > 0 for d in depths[1:])
    else:
        # serial baseline: host staging only, stages back-to-back
        depths = [max(stage_depths)] + [0] * (len(chain.stages) - 1)
    if n_eq is None:
        n_eq = E * (max_batches if max_batches else 4)
    if inputs is not None:
        avail = min(v.shape[0] for v in inputs.values())
        if E > avail:
            raise ValueError(
                f"plan batch E={E} exceeds the provided input arrays "
                f"({avail} elements); re-plan with n_eq or pass larger "
                "inputs"
            )
        # never slice past the data: an oversized n_eq would otherwise
        # run empty batches while reporting their elements as work done
        n_eq = min(n_eq, avail)
    n_total = max(1, n_eq // E)
    n = n_total if max_batches is None else min(max_batches, n_total)

    elem_sharding = NamedSharding(mesh, P("elements"))
    repl_sharding = NamedSharding(mesh, P())

    # placement execution: one dispatch ring per device group.  A plan
    # whose stage count matches the compiled chain and whose device
    # groups fit the local pool runs each stage element-sharded over its
    # own group's mesh, with the HBM-resident handoff resharded where it
    # crosses groups; every degenerate placement (single device, plan
    # for a bigger machine, stage-count mismatch) falls back to the
    # single global mesh -- the exact pre-placement path.
    place = getattr(plan, "placement", None)
    groups = None
    if place is not None and place.devices_used[-1] >= len(local_devices):
        warnings.warn(
            f"run_chain: plan placement spans "
            f"{place.topology.n_devices} device(s) but only "
            f"{len(local_devices)} are local; executing on the local "
            "mesh instead.",
            RuntimeWarning,
        )
    elif place is not None and place.n_stages == len(chain.stages):
        groups = mempipe.placement_meshes(place, devices=local_devices)
    if groups is not None:
        stage_meshes = [element_mesh(list(g)) for g in groups]
        stage_elem = [NamedSharding(m, P("elements")) for m in stage_meshes]
        stage_repl = [NamedSharding(m, P()) for m in stage_meshes]
    else:
        stage_elem = [elem_sharding] * len(chain.stages)
        stage_repl = [repl_sharding] * len(chain.stages)

    shared_host: Dict[str, np.ndarray] = {}
    for k, (name, node) in enumerate(sorted(chain.shared_operands().items())):
        if shared is not None and name in shared:
            shared_host[name] = np.asarray(shared[name])
        else:
            rng = np.random.default_rng(seed + 2 ** 31 + k)
            shared_host[name] = rng.uniform(
                -1, 1, node.shape
            ).astype(np.float32)
    # batch-invariant operands live replicated once per distinct device
    # group (one copy total on the single global mesh)
    shared_by_group: Dict = {}
    shared_for_stage: List[Dict[str, jax.Array]] = []
    for i in range(len(chain.stages)):
        key = groups[i] if groups is not None else None
        if key not in shared_by_group:
            shared_by_group[key] = {
                name: jax.device_put(h, stage_repl[i])
                for name, h in shared_host.items()
            }
        shared_for_stage.append(shared_by_group[key])

    out_names = [
        f"{s.name}.{n}"
        for i, s in enumerate(chain.stages)
        for n, _ in chain.chain_outputs(i)
    ]
    #: qualified host stream -> consuming stage (its group stages it)
    owner = {
        f"{s.name}.{n}": i
        for i, s in enumerate(chain.stages)
        for n, _ in chain.host_element_inputs(i)
    }

    def stage_batch(batch):
        return {
            k: jax.device_put(v, stage_elem[owner[k]])
            for k, v in batch.items()
        }

    if tracer:
        from ..trace.attribution import (COUNTER_CHANNEL_BYTES,
                                         COUNTER_OCCUPANCY,
                                         COUNTER_PAD_ELEMENTS,
                                         host_channel_bytes)

        tracer.meta.update({
            "chain": plan.chain, "target": plan.target.name,
            "policy": plan.policy, "signature": plan.signature,
            "batch_elements": E,
        })
        tracer.bump(COUNTER_OCCUPANCY, {
            sp.name: float(sp.cu_count) for sp in plan.stages
        })
        ch_bytes = {
            str(c): float(b)
            for c, b in host_channel_bytes(plan.buffers).items()
        }
        pad = plan.batch_pad_elements
        inner_stage_batch = stage_batch

        def stage_batch(batch):
            tracer.bump(COUNTER_CHANNEL_BYTES, ch_bytes)
            if pad:
                tracer.bump(COUNTER_PAD_ELEMENTS, {"pad": float(pad)})
            return inner_stage_batch(batch)

    # per-stage E_s: a heterogeneous plan runs some stages at a smaller
    # batch than the chain E -- the re-blocking handoff slices the chain
    # batch into E_s sub-batches on device and concatenates the outputs
    # (bitwise-equal to the full-batch call: elements are independent)
    stage_es = [
        plan.stage_e(i) if hasattr(plan, "stage_e") else E
        for i in range(len(plan.stages))
    ]
    if len(stage_es) != len(chain.stages):
        stage_es = [E] * len(chain.stages)
    stage_fns = chain_stage_fns(chain, shared_for_stage, out_names,
                                stage_es=stage_es, batch_elements=E)

    # multi-group handoff: before stage i consumes a batch, reshard the
    # HBM-resident streams it reads from producers on *other* groups
    place_fns = None
    if groups is not None:
        def make_place_fn(i: int):
            moves = sorted(
                f"{chain.stages[p].name}.{out}"
                for p, out in chain.resolved[i].values()
                if groups[p] != groups[i]
            )
            if not moves:
                return None
            sh = stage_elem[i]

            def place(staged, carry):
                carry = dict(carry) if carry else {}
                for q in moves:
                    carry[q] = jax.device_put(carry[q], sh)
                return staged, carry

            return place

        place_fns = [make_place_fn(i) for i in range(len(chain.stages))]

    if collect_outputs:
        reduce_fn = lambda live: jax.device_get(
            {q: live[q] for q in out_names}
        )
    else:
        reduce_fn = lambda live: {
            q: jnp.sum(live[q]) for q in out_names
        }

    m_count0 = monitor.count if monitor is not None else 0
    m_flags0 = len(monitor.flags) if monitor is not None else 0
    root = (tracer.begin("run_chain", "run", 0, chain=plan.chain,
                         batches=n, batch_elements=E,
                         pipelined=bool(pipeline_stages))
            if tracer else None)
    t0 = time.perf_counter()
    per_batch = mempipe.run_stage_pipelined(
        stage_fns,
        _chain_batch_inputs(chain, E, n, seed, inputs),
        stage_fn=stage_batch,
        depths=depths,
        reduce_fn=reduce_fn,
        place_fns=place_fns,
        tracer=tracer,
        monitor=monitor,
        stage_names=[s.name for s in chain.stages],
        metrics=metrics,
        metrics_labels={"plan": plan.signature[:12]} if metrics else None,
    )
    wall = time.perf_counter() - t0
    if root is not None:
        tracer.end(root)
    stragglers: Tuple[int, ...] = ()
    if monitor is not None:
        # monitor counts are 1-based record() calls; one call per retired
        # batch in batch order, on top of whatever the monitor saw before
        stragglers = tuple(
            c - 1 - m_count0 for c in monitor.flags[m_flags0:]
        )

    checksums: Dict[str, float] = {q: 0.0 for q in out_names}
    outputs: Optional[Dict[str, np.ndarray]] = None
    if collect_outputs:
        outputs = {
            q: np.concatenate([np.asarray(b[q]) for b in per_batch])
            for q in out_names
        }
        for q in out_names:
            checksums[q] = float(np.sum(outputs[q], dtype=np.float64))
    else:
        for b in per_batch:
            for q, v in b.items():
                checksums[q] += float(v)
    return ChainResult(
        batches=n, elements=n * E, wall_s=wall, checksums=checksums,
        plan=plan, outputs=outputs, pipelined_stages=bool(pipeline_stages),
        placement_groups=(
            tuple(tuple(sp.devices) for sp in place.stages)
            if groups is not None else None
        ),
        straggler_batches=stragglers,
    )
