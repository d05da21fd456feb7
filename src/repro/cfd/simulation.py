"""Element-batched simulation driver -- the Olympus system/host layer.

Implements the paper's section 3.1 quantities on the TPU mesh:

  * **batch**: ``E`` elements processed per dispatch.  The paper sizes E
    so a batch fills one 256 MB HBM pseudo-channel; here the sizing (and
    every other memory decision) comes from an explicit
    :class:`repro.memory.ChainPlan` -- no batch size is hardcoded
    here.  A single operator runs as a one-stage chain, so
    :func:`run_chain` is the one executor.
  * **N_b = N_eq / E** batches, **I = N_b / N_cu** iterations, where the
    CU count is the number of mesh devices the element axis is sharded
    over (CU replication == data parallelism over elements).
  * **transfer pipelining**: batch k+K..k+1 transfer host->device while
    batch k computes, through the skewed dispatch rings of
    ``repro.memory.pipeline`` (K=1 is the ping/pong channel pair of
    Fig. 14a; K=0 is the serial baseline).
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import math
import os
import sys
import threading
import time
import warnings
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..memory import chain as memchain
from ..memory import channels as memchannels
from ..memory import pipeline as mempipe
from ..memory.placement import DeviceTopology


def element_mesh(devices=None) -> Mesh:
    """1-D mesh over all local devices: the CU-replication axis."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), ("elements",))


# ---------------------------------------------------------------------------
# the chain executor (one stage or many: interpolation -> gradient -> Helmholtz)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ChainResult:
    """One run of a whole pipeline off a single ChainPlan."""

    batches: int
    elements: int
    wall_s: float
    checksums: Dict[str, float]
    plan: Optional[memchain.ChainPlan] = None
    #: full chain outputs, qualified "stage.output" (collect_outputs=True):
    #: one contiguous array per stream, assembled in place as batches
    #: retire.  A later call may write into these arrays again once
    #: nothing but the output pool references them: keep the result, the
    #: dict or any view of an array to keep its bytes.
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


#: rows of one copy task: a few MB, so several workers share one batch
_COPY_BLOCK_BYTES = 4 * 2 ** 20
_copy_pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
_copy_pool_lock = threading.Lock()


def _copy_executor() -> concurrent.futures.ThreadPoolExecutor:
    """The process's output-copy workers, started on first use: one per
    core but the one the dispatch thread keeps (``np.copyto`` releases
    the GIL, so the copies run in parallel)."""
    global _copy_pool
    with _copy_pool_lock:
        if _copy_pool is None:
            _copy_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=max(1, (os.cpu_count() or 1) - 1),
                thread_name_prefix="run_chain-copy",
            )
        return _copy_pool


#: the output arrays the last completed collecting call returned, which
#: the next call writes into again once nothing else references them
_out_pool: List[np.ndarray] = []
_out_pool_lock = threading.Lock()


def _sole_ref_count() -> int:
    """What ``sys.getrefcount`` reads for an object that only one list
    slot holds, read through that slot."""
    held = [object()]
    return sys.getrefcount(held[0])


_SOLE_REF = _sole_ref_count()


def _take_pooled(shape: Tuple[int, ...], dtype) -> Optional[np.ndarray]:
    """Remove from the pool and return an array of exactly ``shape`` and
    ``dtype`` that nothing outside the pool references (no result, dict
    or view of it is alive), or None where there is none."""
    with _out_pool_lock:
        for i in range(len(_out_pool)):
            if (_out_pool[i].shape == shape and _out_pool[i].dtype == dtype
                    and sys.getrefcount(_out_pool[i]) == _SOLE_REF):
                return _out_pool.pop(i)
    return None


class _OutputAssembly:
    """One call's collected outputs, assembled in place as batches retire.

    :meth:`put` (on the dispatch thread, after a batch's transfer) hands
    the batch's host arrays to the copy workers, which write them into
    rows ``[k*E, (k+1)*E)`` of one array per output stream, chosen when
    the first batch arrives: an array an earlier call returned that
    nobody holds any more (:func:`_take_pooled`, already faulted in),
    else a new one; the batch's arrays are released as its copies
    finish.  :meth:`join` waits for the copies still in flight and makes
    the call's outputs the pool; :meth:`abort` cancels or waits for them
    when the call fails, so no worker outlives the call, and leaves the
    pool as it was."""

    def __init__(self, n_batches: int, batch_elements: int) -> None:
        self.n = n_batches
        self.E = batch_elements
        self.outputs: Optional[Dict[str, np.ndarray]] = None
        #: per output stream, the bytes of its array taken from the pool
        self.reused_bytes: Dict[str, float] = {}
        #: (qualified output, bytes, future) per copy task
        self._copies: List[Tuple[str, int, concurrent.futures.Future]] = []

    def put(self, k: int, got: Dict[str, np.ndarray]) -> None:
        E = self.E
        got = {q: np.asarray(v) for q, v in got.items()}
        if self.outputs is None:
            self.outputs = {}
            for q, v in got.items():
                shape = (self.n * E,) + v.shape[1:]
                out = _take_pooled(shape, v.dtype)
                self.reused_bytes[q] = float(out.nbytes if out is not None
                                             else 0)
                self.outputs[q] = (out if out is not None
                                   else np.empty(shape, v.dtype))
        pool = _copy_executor()
        for q, v in got.items():
            dst = self.outputs[q][k * E:(k + 1) * E]
            rows = max(1, _COPY_BLOCK_BYTES // max(1, v[:1].nbytes))
            for lo in range(0, E, rows):
                src = v[lo:lo + rows]
                self._copies.append((q, src.nbytes, pool.submit(
                    np.copyto, dst[lo:lo + rows], src)))

    def hidden_bytes(self) -> Dict[str, float]:
        """Per output stream, the bytes whose copy has finished."""
        done: Dict[str, float] = {}
        for q, nbytes, f in self._copies:
            done[q] = done.get(q, 0.0) + (float(nbytes) if f.done() else 0.0)
        return done

    def join(self) -> Dict[str, np.ndarray]:
        """Wait for every copy; returns the assembled outputs, which
        become the pool in place of what it held."""
        concurrent.futures.wait([f for _, _, f in self._copies])
        copies, self._copies = self._copies, []
        for _, _, f in copies:
            f.result()  # a failed copy raises here
        if self.outputs is not None:
            with _out_pool_lock:
                _out_pool[:] = self.outputs.values()
        return self.outputs

    def abort(self) -> None:
        """Cancel the copies not started and wait for the rest."""
        for _, _, f in self._copies:
            f.cancel()
        concurrent.futures.wait([f for _, _, f in self._copies])
        self._copies = []
        self.outputs = None


def _chain_batch_inputs(
    chain: memchain.ProgramChain,
    E: int,
    n_batches: int,
    seed: int,
    inputs: Optional[Dict[str, np.ndarray]],
    *,
    in_place: frozenset = frozenset(),
    tracer=None,
) -> Iterator[Dict[str, np.ndarray]]:
    """Per-batch host-streamed inputs, qualified "stage.input".

    ``inputs`` supplies full arrays (element-axis leading) to slice;
    otherwise a deterministic synthetic stream is generated, matching
    ``_batch_generator``'s [-1, 1] normalization.  A stream in
    ``in_place`` is not sliced: its stage program reads the batch from
    the whole array, so the batch holds only its start row.  ``tracer``
    records a ``slice`` span around building each batch, closed before
    the batch is yielded, and adds the batch's bytes of every input that
    already lives on the device (a ``jax.Array``) to the
    ``resident_in_bytes`` counter, and one batch of each stream read in
    place to the ``resident_in_place`` counter."""
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

    def batch(b: int) -> Dict[str, np.ndarray]:
        if inputs is not None:
            return {
                q: b * E if q in in_place else inputs[q][b * E:(b + 1) * E]
                for q in names
            }
        rng = np.random.default_rng(seed + b)
        return {
            q: rng.uniform(-1, 1, (E,) + shapes[q]).astype(np.float32)
            for q in names
        }

    if tracer:
        from ..trace.attribution import (CAT_SLICE, COUNTER_RESIDENT_IN_BYTES,
                                         COUNTER_RESIDENT_IN_PLACE)
        from ..trace.tracer import HOST_TRACK

        # from shapes: a stream read in place is never sliced here
        resident_bytes = {
            q: float(E * math.prod(inputs[q].shape[1:])
                     * inputs[q].dtype.itemsize)
            for q in names
            if inputs is not None and isinstance(inputs[q], jax.Array)
        }
        read_in_place = {q: 1.0 for q in names if q in in_place}
    for b in range(n_batches):
        if not tracer:
            yield batch(b)
            continue
        with tracer.span(f"slice b{b}", CAT_SLICE, HOST_TRACK, batch=b):
            item = batch(b)
        if resident_bytes:
            tracer.bump(COUNTER_RESIDENT_IN_BYTES, resident_bytes)
        if read_in_place:
            tracer.bump(COUNTER_RESIDENT_IN_PLACE, read_in_place)
        yield item


@functools.partial(jax.jit,
                   static_argnames=("fn", "rows", "element_keys", "sub"))
def _read_in_place(whole, start, env, *, fn, rows, element_keys, sub):
    """One batch of a stage program that reads some streams in place:
    rows ``[start, start + rows)`` of each array in ``whole`` (program
    input name -> the whole resident array, never donated), sliced
    inside the program, so the host dispatches nothing per stream.
    ``start`` is traced, so one compile serves every batch; ``sub`` > 0
    re-blocks the stage at that E after the slice."""
    env = dict(env)
    for name, a in whole.items():
        env[name] = jax.lax.dynamic_slice_in_dim(a, start, rows)
    if sub:
        fn = mempipe.reblock_batched_fn(fn, element_keys, sub)
    return fn(env)


def chain_stage_fns(
    chain: memchain.ProgramChain,
    shared_for_stage: List[Dict[str, jax.Array]],
    out_names: List[str],
    *,
    stage_es: Optional[List[int]] = None,
    batch_elements: int = 0,
    resident: Optional[List[Dict[str, jax.Array]]] = None,
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
    ``batch_elements`` (re-blocked on device).  ``resident[i]`` maps
    stage i's streams that it reads in place to their whole arrays on
    its device: ``staged`` then holds the batch's start row for each,
    and the stage runs as one program that slices ``batch_elements``
    rows of each and calls the stage's own.
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
        element_keys = tuple(s.program.element_vars)
        sub = (stage_es[i] if stage_es and 0 < stage_es[i] < batch_elements
               else 0)
        whole = resident[i] if resident else {}
        if whole:
            # the reader is keyed on the stage's own program, so every
            # call over a resident field reuses one compile
            batched_fn = functools.partial(
                _read_in_place, fn=batched_fn, rows=batch_elements,
                element_keys=element_keys, sub=sub)
        elif sub:
            batched_fn = mempipe.reblock_batched_fn(
                batched_fn, element_keys, sub
            )
        keep = keep_after[i]

        def run_stage(staged, carry):
            live: Dict[str, jax.Array] = dict(carry) if carry else {}
            env: Dict[str, jax.Array] = {}
            start = None
            for name in s.program.inputs:
                if name in chain.resolved[i]:
                    p_idx, out_name = chain.resolved[i][name]
                    env[name] = live[
                        f"{chain.stages[p_idx].name}.{out_name}"
                    ]
                elif name in shared_for_stage[i]:
                    env[name] = shared_for_stage[i][name]
                elif name in whole:
                    start = staged.pop(f"{s.name}.{name}")
                else:
                    env[name] = staged.pop(f"{s.name}.{name}")
            outs = (batched_fn(whole, start, env) if whole
                    else batched_fn(env))
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
    omitted).  An input that is a ``jax.Array`` on the one device of
    the stage that consumes it is read in place: each batch hands that
    stage only its start row, and the stage program slices the batch
    out of the whole array itself, which it never donates.  Every other
    input is sliced on the host and put on its stage's devices.

    ``collect_outputs`` returns the whole chain outputs, one contiguous
    array per stream, for verification against an unchained reference;
    by default only a checksum per output crosses back (the plan's
    host-out streams are still priced -- the reduction is a measurement
    convenience).  The outputs are assembled in
    place as batches retire: each retired batch's transfer stays on the
    dispatch thread (device memory is freed at retire), and its copy
    into the call's output rows runs on a pool of worker threads while
    the ring goes on; the end of the call waits for the copies still in
    flight.  Outputs and checksums are bitwise those of concatenating
    the per-batch results.  The copies write into host memory already
    faulted in where they can: the arrays the last completed collecting
    call returned stay in a process-wide pool, and a stream takes one of
    exactly its shape and dtype that nothing outside the pool references
    any more -- a caller that keeps the ``ChainResult``, its ``outputs``
    or any view of an array keeps that array's bytes, and the call gets
    a new one.  Every row of a taken array is written again.  A call
    that fails puts nothing in the pool.

    ``tracer`` (``repro.trace.Tracer``; None = off) records the full
    span hierarchy -- chain run -> per-stage slot -> dispatch/handoff --
    plus per-channel host byte, pad-element and CU-occupancy counters
    from the plan, ready for ``repro.trace.attribution``.  ``monitor``
    (a ``runtime.StepMonitor``) watches per-batch retire times; flagged
    batches are annotated on their sync spans and reported in
    ``ChainResult.straggler_batches``.  ``metrics`` (a ``repro.metrics``
    registry) records the driver's always-on per-stage dispatch/stall
    histograms keyed by the plan signature.  None changes results.

    Around the pipeline, the tracer also records ``run_chain``'s own host
    work: ``prepare`` (entry to the root span: plan check, shared
    operands put on the device, stage callables built), a ``slice`` per
    batch, the retire's ``wait`` for and ``transfer`` of each batch's
    outputs with ``collect_outputs`` (bytes brought back in the
    ``host_out_bytes`` counter), then ``concat`` and ``checksum``.
    ``concat`` times the join: the wait for the output copies still in
    flight when the ring drained.  As it begins, the
    ``host_out_hidden_bytes`` counter records, per output stream, the
    bytes whose copy had already finished -- the share of the copy that
    the ring's own work hid.  When the first batch retires, the
    ``host_out_reused_bytes`` counter records, per output stream, the
    bytes of the call's output that went into an array taken from the
    pool.
    """
    prepare = None
    if tracer:
        from ..trace.attribution import (CAT_CHECKSUM, CAT_CONCAT,
                                         CAT_PREPARE, CAT_TRANSFER, CAT_WAIT,
                                         COUNTER_CHANNEL_BYTES,
                                         COUNTER_HOST_OUT_BYTES,
                                         COUNTER_HOST_OUT_HIDDEN_BYTES,
                                         COUNTER_HOST_OUT_REUSED_BYTES,
                                         COUNTER_OCCUPANCY,
                                         COUNTER_PAD_ELEMENTS,
                                         host_channel_bytes)
        from ..trace.tracer import HOST_TRACK

        prepare = tracer.begin("prepare", CAT_PREPARE, HOST_TRACK)
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

    # a device array already on its consuming stage's one device is read
    # in place by the stage program: no slice, no staging
    in_place = frozenset(
        q for q, i in owner.items()
        if inputs is not None and isinstance(inputs[q], jax.Array)
        and len(stage_elem[i].device_set) == 1
        and inputs[q].sharding.device_set == stage_elem[i].device_set
    )
    resident = [
        {n: inputs[f"{s.name}.{n}"] for n, _ in chain.host_element_inputs(i)
         if f"{s.name}.{n}" in in_place}
        for i, s in enumerate(chain.stages)
    ]

    def stage_batch(batch):
        return {
            k: v if k in in_place else jax.device_put(v, stage_elem[owner[k]])
            for k, v in batch.items()
        }

    if tracer:
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
                                stage_es=stage_es, batch_elements=E,
                                resident=resident)

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

    assembly = _OutputAssembly(n, E) if collect_outputs else None
    if collect_outputs:
        retired = [0]

        def reduce_fn(live):
            k = retired[0]
            retired[0] += 1
            outs = {q: live[q] for q in out_names}
            if tracer:
                with tracer.span(f"wait b{k}", CAT_WAIT, HOST_TRACK,
                                 batch=k):
                    jax.block_until_ready(outs)
                with tracer.span(f"transfer b{k}", CAT_TRANSFER, HOST_TRACK,
                                 batch=k):
                    got = jax.device_get(outs)
                tracer.bump(COUNTER_HOST_OUT_BYTES,
                            {q: float(v.nbytes) for q, v in got.items()})
            else:
                got = jax.device_get(outs)
            assembly.put(k, got)
            if tracer and k == 0:
                tracer.bump(COUNTER_HOST_OUT_REUSED_BYTES,
                            assembly.reused_bytes)
    else:
        reduce_fn = lambda live: {
            q: jnp.sum(live[q]) for q in out_names
        }

    m_count0 = monitor.count if monitor is not None else 0
    m_flags0 = len(monitor.flags) if monitor is not None else 0
    if prepare is not None:
        tracer.end(prepare)
    root = (tracer.begin("run_chain", "run", 0, chain=plan.chain,
                         batches=n, batch_elements=E,
                         pipelined=bool(pipeline_stages))
            if tracer else None)
    t0 = time.perf_counter()
    try:
        per_batch = mempipe.run_stage_pipelined(
            stage_fns,
            _chain_batch_inputs(chain, E, n, seed, inputs,
                                in_place=in_place, tracer=tracer),
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
    except BaseException:
        if assembly is not None:
            assembly.abort()
        raise
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
    if assembly is not None:
        sp = None
        if tracer:
            sp = tracer.begin("concat", CAT_CONCAT, HOST_TRACK)
            tracer.bump(COUNTER_HOST_OUT_HIDDEN_BYTES,
                        assembly.hidden_bytes())
        outputs = assembly.join()
        if sp is not None:
            tracer.end(sp)
    sp = tracer.begin("checksum", CAT_CHECKSUM, HOST_TRACK) if tracer else None
    if outputs is not None:
        for q in out_names:
            checksums[q] = float(np.sum(outputs[q], dtype=np.float64))
    else:
        for b in per_batch:
            for q, v in b.items():
                checksums[q] += float(v)
    if sp is not None:
        tracer.end(sp)
    return ChainResult(
        batches=n, elements=n * E, wall_s=wall, checksums=checksums,
        plan=plan, outputs=outputs, pipelined_stages=bool(pipeline_stages),
        placement_groups=(
            tuple(tuple(sp.devices) for sp in place.stages)
            if groups is not None else None
        ),
        straggler_batches=stragglers,
    )
