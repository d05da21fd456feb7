"""Multi-operator chain planning + the run_chain driver: residency,
co-sized E, conflict-free placement, bitwise equivalence with the
unchained reference, and the plan-driven Pallas block size."""
import concurrent.futures
import os
import sys
import threading
import time
import weakref

import numpy as np
import pytest

from conftest import helmholtz_chain
from repro import trace
from repro.cfd import operators, simulation
from repro.memory import chain as mchain
from repro.memory import channels, layout


@pytest.fixture(scope="module")
def cfd_chain():
    return operators.build_cfd_chain(5)


def _chain_inputs(chain, n, p, rng):
    inputs = {
        "interp.u": rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32),
        "helmholtz.D": rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32),
    }
    shared = {
        name: rng.uniform(-1, 1, node.shape).astype(np.float32)
        for name, node in sorted(chain.shared_operands().items())
    }
    return inputs, shared


# ---------------------------------------------------------------------------
# chain structure
# ---------------------------------------------------------------------------


def test_chain_structure(cfd_chain):
    ch = cfd_chain
    assert ch.name == "interp->grad->helmholtz"
    # bound streams (flow-derived from the pipeline source): interp's w
    # feeds the gradient, the gradient's gx feeds the Helmholtz solve
    assert ch.resolved[1] == {"w": (0, "w")}
    assert ch.resolved[2] == {"gx": (1, "gx")}
    assert [n for n, _ in ch.resident_outputs(0)] == ["w"]
    assert [n for n, _ in ch.resident_outputs(1)] == ["gx"]
    # fringe: only unbound element vars touch the host
    assert [n for n, _ in ch.host_element_inputs(0)] == ["u"]
    assert [n for n, _ in ch.host_element_inputs(1)] == []
    assert [n for n, _ in ch.host_element_inputs(2)] == ["D"]
    assert [n for n, _ in ch.chain_outputs(1)] == ["gy", "gz"]
    assert [n for n, _ in ch.chain_outputs(2)] == ["v"]
    assert sorted(ch.shared_operands()) == ["A", "Dx", "Dy", "Dz", "S"]


def test_chain_rejects_bad_bindings():
    interp = operators.build_interpolation(5, 5)
    helm = operators.build_inverse_helmholtz(7)  # shape mismatch vs p=5
    with pytest.raises(mchain.ChainError):
        mchain.ProgramChain([
            ("a", interp), ("b", helm, {"u": "a.v"}),
        ])
    with pytest.raises(mchain.ChainError):
        mchain.ProgramChain([
            ("a", interp), ("b", interp, {"u": "nosuch.v"}),
        ])
    with pytest.raises(mchain.ChainError):  # unqualified binding
        mchain.ProgramChain([
            ("a", interp), ("b", interp, {"u": "v"}),
        ])
    with pytest.raises(mchain.ChainError):  # duplicate stage names
        mchain.ProgramChain([("a", interp), ("a", interp)])


def test_chain_auto_binding_by_name():
    """An input named like an earlier output binds without an explicit
    bindings entry (most recent producer wins)."""
    a = operators.build_interpolation(5, 5)  # u -> v
    b = operators.build_inverse_helmholtz(5)  # u, D -> v ... no 'v' input
    # gradient consumes 'u'; interpolation produces 'v' -- no auto-bind
    chain = mchain.ProgramChain([("s0", a), ("s1", b)])
    assert chain.resolved[1] == {}  # nothing matched by name
    # a second interpolation re-consuming 'u' does NOT bind to s0's 'v'
    chain2 = mchain.ProgramChain([("s0", a), ("s1", a)])
    assert chain2.resolved[1] == {}


# ---------------------------------------------------------------------------
# chain plan: residency, E co-sizing, placement
# ---------------------------------------------------------------------------


def test_chain_plan_fewer_host_bytes_than_standalone(cfd_chain):
    """Acceptance: the chain plan's host-stream bytes are strictly fewer
    than the sum of the three standalone plans at the same E."""
    E = 128
    t = channels.ALVEO_U280
    plan = mchain.plan_chain(cfd_chain, target=t, batch_elements=E)
    standalone = sum(
        mchain.plan_chain(
            mchain.ProgramChain([(s.name, s.compiled)]), target=t,
            batch_elements=E,
        ).host_stream_bytes
        for s in cfd_chain.stages
    )
    assert plan.host_stream_bytes < standalone
    # exactly the bound streams stay resident: interp.w and grad.gx,
    # each saving one host write + one host read
    resident = [b for b in plan.buffers if b.role == "resident"]
    assert sorted(b.name for b in resident) == ["grad.gx", "interp.w"]
    assert standalone - plan.host_stream_bytes == 2 * sum(
        b.batch_bytes for b in resident
    )
    assert plan.resident_stream_bytes == sum(b.batch_bytes for b in resident)


def test_chain_cosized_e_fits_every_stage(cfd_chain):
    """The shared E (before block padding) satisfies the channel rule
    for each stage, at least one stage is tight (E is maximal), and the
    padded E is a multiple of every stage's VMEM block."""
    t = channels.ALVEO_U280
    plan = mchain.plan_chain(cfd_chain, target=t)
    base = plan.batch_elements - plan.batch_pad_elements
    tight = False
    for i in range(len(cfd_chain.stages)):
        per = cfd_chain.stage_stream_bytes_per_element(i, 4)
        assert base * per <= t.channel_bytes
        if (base + 1) * per > t.channel_bytes:
            tight = True
    assert tight
    for sp in plan.stages:
        assert plan.batch_elements % sp.block_elements == 0
    # the padder's contract: for the largest stage cap, the chosen E's
    # block divisor is never below half the cap (prime-ish E padded away)
    max_cap = max(
        layout.vmem_block_elements(s.program, t, bytes_per_scalar=4)
        for s in cfd_chain.stages
    )
    blk = layout.largest_divisor_leq(plan.batch_elements, max_cap)
    assert 2 * blk >= min(max_cap, plan.batch_elements)


def test_chain_placement_no_conflicts(cfd_chain):
    """No channel double-booked within a replica set; shared operands
    placed exactly once chain-wide."""
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=256
    )
    names = [b.name for b in plan.buffers]
    assert len(names) == len(set(names))
    for b in plan.buffers:
        assert len(b.channels) == len(set(b.channels))
    shared = [b for b in plan.buffers if b.role == "shared"]
    assert sorted(b.name for b in shared) == ["A", "Dx", "Dy", "Dz", "S"]
    # consecutive buffers round-robin instead of piling on channel 0
    first_channels = [b.channels[0] for b in plan.buffers]
    assert len(set(first_channels)) > 1


def test_chain_plan_determinism_and_report(cfd_chain):
    kw = dict(target=channels.ALVEO_U280, batch_elements=128, n_eq=1024)
    a = mchain.plan_chain(cfd_chain, **kw)
    b = mchain.plan_chain(cfd_chain, **kw)
    assert a == b
    assert a.report() == b.report()
    rep = a.report()
    assert "ChainPlan interp->grad->helmholtz" in rep
    assert "resident" in rep and "stage helmholtz" in rep


def test_chain_infeasible_reported(cfd_chain):
    tiny = channels.ALVEO_U280.with_(hbm_bytes=2 ** 20, n_channels=4)
    plan = mchain.plan_chain(cfd_chain, target=tiny, batch_elements=4096)
    assert not plan.feasible
    assert "exceeds" in plan.infeasible_reason
    assert "NO" in plan.report()


def test_chain_per_stage_depths_and_backends(cfd_chain):
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=64,
        backends=("xla", "staged", "staged"), prefetch_depth=(0, 1, 2),
    )
    assert [sp.backend for sp in plan.stages] == ["xla", "staged", "staged"]
    assert [sp.prefetch_depth for sp in plan.stages] == [0, 1, 2]
    # the staged Helmholtz exposes its group-boundary intermediates (the
    # gradient's groups all end at program outputs, so it has none)
    assert any(
        b.role == "inter" for b in plan.stages[2].buffers
    )


# ---------------------------------------------------------------------------
# run_chain: the whole pipeline off one plan
# ---------------------------------------------------------------------------


def _unchained_per_batch(chain, inputs, shared, E, n_b):
    """Each batch's outputs from the three compiled operators run
    separately, with host round-trips between them."""
    interp, grad, helm = (s.compiled for s in chain.stages)
    ref = {"grad.gy": [], "grad.gz": [], "helmholtz.v": []}
    for b in range(n_b):
        sl = slice(b * E, (b + 1) * E)
        w = np.asarray(interp.batched_fn(
            {"A": shared["A"], "u": inputs["interp.u"][sl]})["w"])
        g = grad.batched_fn({
            "Dx": shared["Dx"], "Dy": shared["Dy"], "Dz": shared["Dz"],
            "w": w,
        })
        ref["grad.gy"].append(np.asarray(g["gy"]))
        ref["grad.gz"].append(np.asarray(g["gz"]))
        hv = helm.batched_fn({
            "S": shared["S"], "D": inputs["helmholtz.D"][sl],
            "gx": np.asarray(g["gx"]),
        })["v"]
        ref["helmholtz.v"].append(np.asarray(hv))
    return ref


def test_run_chain_bitwise_matches_unchained(cfd_chain, rng):
    """Acceptance: chained execution (intermediates resident on device)
    is bitwise-identical at float32 to running the three compiled
    operators separately with host round-trips between them."""
    p, E, n_b = 5, 16, 3
    n = E * n_b
    chain = cfd_chain
    inputs, shared = _chain_inputs(chain, n, p, rng)
    plan = mchain.plan_chain(
        chain, target=channels.CPU_HOST, batch_elements=E, n_eq=n,
        prefetch_depth=2,
    )
    res = simulation.run_chain(
        chain, plan, inputs=inputs, shared=shared, collect_outputs=True
    )
    assert res.batches == n_b and res.elements == n

    ref = _unchained_per_batch(chain, inputs, shared, E, n_b)
    for q in ref:
        want = np.concatenate(ref[q])
        assert want.dtype == res.outputs[q].dtype == np.float32
        assert np.array_equal(want, res.outputs[q]), q


def test_run_chain_stage_pipelined_bitwise_matches_serial(cfd_chain, rng):
    """Acceptance: cross-batch stage pipelining (stage i of batch k
    dispatched with stage i+1 of batch k-1) is bitwise-equal at float32
    to the serial back-to-back schedule on the CFD chain."""
    p, E, n_b = 5, 16, 4
    n = E * n_b
    inputs, shared = _chain_inputs(cfd_chain, n, p, rng)
    plan = mchain.plan_chain(
        cfd_chain, target=channels.CPU_HOST, batch_elements=E, n_eq=n,
        prefetch_depth=(2, 1, 1),
    )
    assert plan.pipeline.pipelined
    assert plan.pipeline.stage_skews == (0, 1, 2)
    assert plan.cost.t_overlapped <= plan.cost.t_back_to_back
    piped = simulation.run_chain(
        cfd_chain, plan, inputs=inputs, shared=shared, collect_outputs=True
    )
    assert piped.pipelined_stages
    serial = simulation.run_chain(
        cfd_chain, plan, inputs=inputs, shared=shared,
        collect_outputs=True, pipeline_stages=False,
    )
    assert not serial.pipelined_stages
    assert piped.outputs.keys() == serial.outputs.keys()
    for q in serial.outputs:
        assert piped.outputs[q].dtype == serial.outputs[q].dtype
        assert np.array_equal(piped.outputs[q], serial.outputs[q]), q
    # the fully serial plan (all K=0) runs the serial schedule by default
    flat = mchain.plan_chain(
        cfd_chain, target=channels.CPU_HOST, batch_elements=E, n_eq=n,
        prefetch_depth=0,
    )
    assert not flat.pipeline.pipelined
    base = simulation.run_chain(
        cfd_chain, flat, inputs=inputs, shared=shared, collect_outputs=True
    )
    assert not base.pipelined_stages
    # forcing the mode on cannot pipeline a plan with no inter-stage
    # rings: execution and the reported flag stay serial
    forced = simulation.run_chain(
        cfd_chain, flat, inputs=inputs, shared=shared,
        max_batches=1, pipeline_stages=True,
    )
    assert not forced.pipelined_stages
    for q in serial.outputs:
        assert np.array_equal(base.outputs[q], serial.outputs[q]), q


def test_run_chain_checksums_invariant_to_prefetch(cfd_chain, rng):
    p, E, n_b = 5, 8, 3
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    sums = {}
    for depth in (0, 2):
        plan = mchain.plan_chain(
            cfd_chain, target=channels.CPU_HOST, batch_elements=E,
            prefetch_depth=depth, n_eq=E * n_b,
        )
        res = simulation.run_chain(
            cfd_chain, plan, inputs=inputs, shared=shared
        )
        sums[depth] = res.checksums
    assert sums[0].keys() == sums[2].keys()
    for q in sums[0]:
        assert sums[0][q] == pytest.approx(sums[2][q], abs=1e-5)


# ---------------------------------------------------------------------------
# collect_outputs: each retired batch copied into the call's outputs on
# worker threads
# ---------------------------------------------------------------------------


class _DelayedExecutor:
    """A thread pool that records every copy task and runs each one
    ``delay_s`` late, so copies are still in flight when the ring ends."""

    def __init__(self, workers: int, delay_s: float = 0.0):
        self.pool = concurrent.futures.ThreadPoolExecutor(workers)
        self.delay_s = delay_s
        self.futures = []

    def submit(self, fn, *args):
        def late():
            time.sleep(self.delay_s)
            return fn(*args)

        f = self.pool.submit(late)
        self.futures.append(f)
        return f


def _collect_plan(chain, E, n, depth=1):
    return mchain.plan_chain(
        chain, target=channels.CPU_HOST, batch_elements=E, n_eq=n,
        prefetch_depth=depth,
    )


@pytest.mark.parametrize("tracer", ["off", "on", "null"])
def test_collected_outputs_bitwise_concatenation(cfd_chain, rng,
                                                 monkeypatch, tracer):
    """Outputs assembled in place from row blocks are bitwise the
    concatenation of the per-batch reference outputs, traced or not, and
    the checksums are the float64 sums of that concatenation."""
    p, E, n_b = 5, 16, 5
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    # 3 rows a copy task: every batch in 6 blocks, the last a remainder
    monkeypatch.setattr(simulation, "_COPY_BLOCK_BYTES", 3 * 4 * p ** 3)
    tr = {"off": None, "on": trace.Tracer(), "null": trace.NULL}[tracer]
    res = simulation.run_chain(
        cfd_chain, _collect_plan(cfd_chain, E, E * n_b), inputs=inputs,
        shared=shared, collect_outputs=True, tracer=tr,
    )
    assert res.batches == n_b
    ref = _unchained_per_batch(cfd_chain, inputs, shared, E, n_b)
    assert sorted(res.outputs) == sorted(ref)
    for q, parts in ref.items():
        want = np.concatenate(parts)
        got = res.outputs[q]
        assert got.dtype == want.dtype and got.shape == want.shape, q
        assert got.tobytes() == want.tobytes(), q
        assert res.checksums[q] == float(np.sum(want, dtype=np.float64)), q


def test_collected_outputs_many_workers_tiny_blocks(cfd_chain, rng,
                                                    monkeypatch):
    """Stress: one row a copy task on more workers than cores, with a
    short switch interval, still assembles every row in its place."""
    p, E, n_b = 5, 16, 4
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    plan = _collect_plan(cfd_chain, E, E * n_b)
    want = simulation.run_chain(cfd_chain, plan, inputs=inputs,
                                shared=shared, collect_outputs=True)
    ex = _DelayedExecutor(2 * (os.cpu_count() or 1) + 1)
    monkeypatch.setattr(simulation, "_copy_executor", lambda: ex)
    monkeypatch.setattr(simulation, "_COPY_BLOCK_BYTES", 1)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        got = simulation.run_chain(cfd_chain, plan, inputs=inputs,
                                   shared=shared, collect_outputs=True)
    finally:
        sys.setswitchinterval(interval)
        ex.pool.shutdown(wait=True)
    assert len(ex.futures) == 3 * n_b * E  # three streams, E rows a batch
    assert got.checksums == want.checksums
    for q in want.outputs:
        assert got.outputs[q].tobytes() == want.outputs[q].tobytes(), q


class Boom(RuntimeError):
    pass


def _fail_on_batch_2(monkeypatch):
    """Make every run_chain call's last stage raise ``Boom`` on batch 2."""
    inner = simulation.chain_stage_fns

    def failing_stage_fns(*args, **kwargs):
        fns = inner(*args, **kwargs)
        last = fns[-1]
        calls = [0]

        def run_stage(staged, carry):
            calls[0] += 1
            if calls[0] == 3:
                raise Boom("batch 2")
            return last(staged, carry)

        return fns[:-1] + [run_stage]

    monkeypatch.setattr(simulation, "chain_stage_fns", failing_stage_fns)


def test_collected_run_failure_waits_for_copies(cfd_chain, rng, monkeypatch):
    """A stage that raises on batch 2 propagates its exception, and only
    after every copy of the batches retired before it has ended."""
    p, E, n_b = 5, 16, 4
    _fail_on_batch_2(monkeypatch)
    ex = _DelayedExecutor(2, delay_s=0.02)
    monkeypatch.setattr(simulation, "_copy_executor", lambda: ex)
    monkeypatch.setattr(simulation, "_COPY_BLOCK_BYTES", 4 * 4 * p ** 3)
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    try:
        with pytest.raises(Boom, match="batch 2"):
            simulation.run_chain(
                cfd_chain, _collect_plan(cfd_chain, E, E * n_b),
                inputs=inputs, shared=shared, collect_outputs=True,
            )
        # batches 0 and 1 retired: 4 blocks of each of three streams
        assert len(ex.futures) == 2 * 3 * 4
        assert all(f.done() for f in ex.futures)
    finally:
        ex.pool.shutdown(wait=True)


# ---------------------------------------------------------------------------
# the output pool: a call writes into arrays an earlier call returned once
# nothing else holds them
# ---------------------------------------------------------------------------


@pytest.fixture
def out_pool(monkeypatch):
    """An empty output pool of the test's own."""
    pool = []
    monkeypatch.setattr(simulation, "_out_pool", pool)
    return pool


def _data_ptrs(arrays):
    return sorted(a.__array_interface__["data"][0] for a in arrays)


def _collect(chain, plan, inputs, shared):
    return simulation.run_chain(chain, plan, inputs=inputs, shared=shared,
                                collect_outputs=True)


@pytest.mark.parametrize("second", ["same_inputs", "other_inputs_nan_filled"])
def test_collected_outputs_reuse_dropped_arrays(cfd_chain, rng, monkeypatch,
                                                out_pool, second):
    """Once the first result is dropped, a second call of the same shape
    writes into the first call's arrays, and into every row of them: its
    outputs and checksums are bitwise the concatenated reference's, also
    where the reused arrays held NaN and the inputs changed."""
    p, E, n_b = 5, 16, 5
    # 3 rows a copy task: every batch in 6 blocks, the last a remainder
    monkeypatch.setattr(simulation, "_COPY_BLOCK_BYTES", 3 * 4 * p ** 3)
    plan = _collect_plan(cfd_chain, E, E * n_b)
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    first = _collect(cfd_chain, plan, inputs, shared)
    ptrs = _data_ptrs(first.outputs.values())
    refs = [weakref.ref(v) for v in first.outputs.values()]
    del first
    if second == "other_inputs_nan_filled":
        for i in range(len(out_pool)):
            out_pool[i].fill(np.nan)
        inputs = _chain_inputs(cfd_chain, E * n_b, p, rng)[0]
    res = _collect(cfd_chain, plan, inputs, shared)
    assert _data_ptrs(res.outputs.values()) == ptrs
    assert sorted(id(r()) for r in refs) == sorted(map(id,
                                                       res.outputs.values()))
    ref = _unchained_per_batch(cfd_chain, inputs, shared, E, n_b)
    for q, parts in ref.items():
        want = np.concatenate(parts)
        assert res.outputs[q].tobytes() == want.tobytes(), q
        assert res.checksums[q] == float(np.sum(want, dtype=np.float64)), q


@pytest.mark.parametrize("keep", ["result", "slice"])
def test_collected_outputs_kept_by_the_caller_are_not_reused(cfd_chain, rng,
                                                             out_pool, keep):
    """A caller that keeps the first result, or only a slice of one of
    its outputs, keeps those bytes: the next call writes elsewhere, and
    into the arrays nobody kept."""
    p, E, n_b = 5, 16, 3
    plan = _collect_plan(cfd_chain, E, E * n_b)
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    first = _collect(cfd_chain, plan, inputs, shared)
    before = {q: v.tobytes() for q, v in first.outputs.items()}
    ptrs = set(_data_ptrs(first.outputs.values()))
    q0 = sorted(first.outputs)[0]
    if keep == "result":
        kept = dict(first.outputs)
    else:
        kept = {q0: first.outputs[q0][3:7]}
        before = {q0: first.outputs[q0][3:7].tobytes()}
    del first
    res = _collect(cfd_chain, plan,
                   _chain_inputs(cfd_chain, E * n_b, p, rng)[0], shared)
    for q, v in kept.items():
        assert v.tobytes() == before[q], q
        assert not any(np.shares_memory(v, w) for w in res.outputs.values())
    reused = ptrs & set(_data_ptrs(res.outputs.values()))
    assert len(reused) == (0 if keep == "result" else 2)


@pytest.mark.parametrize("change", ["shape", "dtype"])
def test_output_pool_misses_on_shape_or_dtype(out_pool, change):
    """Another shape or dtype allocates new arrays; the pool then holds
    the newest call's arrays and nothing else."""
    E = 4

    def assemble(n, dtype):
        asm = simulation._OutputAssembly(n, E)
        for k in range(n):
            asm.put(k, {"s.a": np.full((E, 3), k, dtype),
                        "s.b": np.full((E, 2, 2), -k, dtype)})
        return asm.join(), asm.reused_bytes

    first, reused = assemble(2, np.float32)
    assert reused == {"s.a": 0.0, "s.b": 0.0}
    old = set(_data_ptrs(first.values()))
    del first
    second, reused = assemble(*((3, np.float32) if change == "shape"
                                else (2, np.float64)))
    assert reused == {"s.a": 0.0, "s.b": 0.0}
    assert not old & set(_data_ptrs(second.values()))
    assert sorted(map(id, out_pool)) == sorted(map(id, second.values()))
    n = len(second["s.a"]) // E
    assert np.array_equal(second["s.a"][:, 0], np.repeat(np.arange(n), E))


def test_failed_call_puts_nothing_in_the_output_pool(cfd_chain, rng,
                                                     monkeypatch, out_pool):
    """A call that fails after taking the pooled arrays and writing part
    of them returns none of them to the pool."""
    p, E, n_b = 5, 16, 4
    plan = _collect_plan(cfd_chain, E, E * n_b)
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    first = _collect(cfd_chain, plan, inputs, shared)
    pooled = sorted(map(id, first.outputs.values()))
    del first
    taken = []
    abort = simulation._OutputAssembly.abort

    def recording_abort(self):
        taken.extend(map(id, self.outputs.values()))
        abort(self)

    monkeypatch.setattr(simulation._OutputAssembly, "abort", recording_abort)
    _fail_on_batch_2(monkeypatch)
    with pytest.raises(Boom, match="batch 2"):
        _collect(cfd_chain, plan, inputs, shared)
    assert sorted(taken) == pooled  # batches 0 and 1 went into them
    assert out_pool == []


def test_concurrent_calls_never_share_an_output_array(cfd_chain, rng,
                                                      out_pool):
    """Two calls in flight at once, with one call's worth of arrays in
    the pool, each get arrays of their own and the right bytes."""
    p, E, n_b = 5, 16, 3
    plan = _collect_plan(cfd_chain, E, E * n_b)
    inputs, shared = _chain_inputs(cfd_chain, E * n_b, p, rng)
    first = _collect(cfd_chain, plan, inputs, shared)
    want = {q: v.tobytes() for q, v in first.outputs.items()}
    del first
    with concurrent.futures.ThreadPoolExecutor(2) as ex:
        futures = [ex.submit(_collect, cfd_chain, plan, inputs, shared)
                   for _ in range(2)]
        results = [f.result(timeout=300) for f in futures]
    arrays = [v for res in results for v in res.outputs.values()]
    assert len(set(_data_ptrs(arrays))) == len(arrays) == 6
    for i, a in enumerate(arrays):
        assert not any(np.shares_memory(a, b) for b in arrays[i + 1:])
    for res in results:
        for q, v in res.outputs.items():
            assert v.tobytes() == want[q], q


def test_pooled_array_is_taken_once(out_pool):
    """Stress: more threads than cores race to take from the pool, with a
    short switch interval; none gets an array another got."""
    threads = 2 * (os.cpu_count() or 1) + 1
    out_pool.extend(np.empty((8, 3), np.float32) for _ in range(4 * threads))
    barrier = threading.Barrier(threads)
    keep = []  # every taken array stays alive, so no id is used twice

    def take_all():
        barrier.wait(timeout=60)
        got = []
        while (a := simulation._take_pooled((8, 3), np.float32)) is not None:
            got.append(id(a))
            keep.append(a)
        return got

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with concurrent.futures.ThreadPoolExecutor(threads) as ex:
            futures = [ex.submit(take_all) for _ in range(threads)]
            ids = [i for f in futures for i in f.result(timeout=60)]
    finally:
        sys.setswitchinterval(interval)
    assert len(ids) == len(set(ids)) == 4 * threads and out_pool == []


def test_run_chain_warns_on_backend_mismatch(cfd_chain, rng):
    """A plan for backends the chain was not compiled with still runs
    (numerically identical programs) but flags the misattribution."""
    p, E = 5, 8
    inputs, shared = _chain_inputs(cfd_chain, E, p, rng)
    plan = mchain.plan_chain(
        cfd_chain, target=channels.CPU_HOST, batch_elements=E,
        backends=("xla", "staged", "xla"), n_eq=E,
    )
    with pytest.warns(RuntimeWarning, match="differ from the compiled"):
        simulation.run_chain(cfd_chain, plan, inputs=inputs, shared=shared)


def test_run_chain_tolerates_plan_with_different_stage_count(cfd_chain, rng):
    """Regression: a pipelined plan from a differently-staged compile
    (stage count != the chain's) still executes the compiled chain as
    the mismatch warning promises, spreading the plan's deepest K."""
    p, E = 5, 8
    inputs, shared = _chain_inputs(cfd_chain, E * 2, p, rng)
    two = mchain.ProgramChain(cfd_chain.stages[:2])  # interp -> grad
    plan = mchain.plan_chain(
        two, target=channels.CPU_HOST, batch_elements=E,
        prefetch_depth=1, n_eq=E * 2,
    )
    assert plan.pipeline.pipelined and len(plan.stages) == 2
    with pytest.warns(RuntimeWarning, match="differ from the compiled"):
        res = simulation.run_chain(
            cfd_chain, plan, inputs=inputs, shared=shared, n_eq=E * 2
        )
    assert res.batches == 2 and res.pipelined_stages
    assert all(np.isfinite(v) for v in res.checksums.values())


def test_run_chain_auto_plans_when_missing(cfd_chain):
    res = simulation.run_chain(cfd_chain, n_eq=64, max_batches=2)
    assert res.plan is not None
    assert res.plan.batch_elements >= 1
    assert set(res.checksums) == {"grad.gy", "grad.gz", "helmholtz.v"}
    assert all(np.isfinite(v) for v in res.checksums.values())


def test_run_chain_auto_e_bounded_by_inputs(cfd_chain, rng):
    """Regression: with inputs but no n_eq, the auto-sized E is capped
    by the data so the element accounting is honest."""
    p, n = 5, 48
    inputs, shared = _chain_inputs(cfd_chain, n, p, rng)
    res = simulation.run_chain(cfd_chain, inputs=inputs, shared=shared)
    assert res.plan.batch_elements <= n
    assert res.elements == res.batches * res.plan.batch_elements <= n
    # an explicitly oversized plan is rejected rather than silently
    # computing on fewer elements than it reports
    big = mchain.plan_chain(
        cfd_chain, target=channels.CPU_HOST, batch_elements=4 * n
    )
    with pytest.raises(ValueError, match="exceeds the provided input"):
        simulation.run_chain(cfd_chain, big, inputs=inputs, shared=shared)
    # an oversized n_eq is clamped to the data instead of running empty
    # batches past the arrays' end
    small = mchain.plan_chain(
        cfd_chain, target=channels.CPU_HOST, batch_elements=16, n_eq=n
    )
    res = simulation.run_chain(
        cfd_chain, small, inputs=inputs, shared=shared, n_eq=16 * n
    )
    assert res.elements <= n


# ---------------------------------------------------------------------------
# run_chain on inputs that already live on the device
# ---------------------------------------------------------------------------


def _resident_case(case, rng):
    """(chain, plan, host inputs, shared, the streams to put on the
    device) for one chain shape."""
    from repro import flow
    from test_cfd import _bp3_source

    if case == "reblocked":
        chain, E, n = operators.build_cfd_chain(5), 16, 48
        inputs, shared = _chain_inputs(chain, n, 5, rng)
        plan = mchain.plan_chain(
            chain, target=channels.CPU_HOST, batch_elements=E, n_eq=n,
            prefetch_depth=1, stage_batch_elements=[E // 2, E, E // 4],
        )
        assert plan.stage_e(0) < E and plan.stage_e(2) < E
        return chain, plan, inputs, shared, set(inputs)
    if case == "bp3":
        n, q, E, n_b = 4, 5, 8, 3
        system = flow.compile(_bp3_source(n, q), name="bp3", max_stages=1,
                              target=channels.CPU_HOST, batch_elements=E,
                              n_eq=E * n_b)
        shared = {v: rng.uniform(-1, 1, (q, n)).astype(np.float32)
                  for v in ("B", "Bd")}
    else:  # the paper's 13-stage pipeline
        E, n_b = 4, 3
        system = flow.compile(operators.CFD_PIPELINE_SRC.format(p=3),
                              target=channels.CPU_HOST, batch_elements=E,
                              n_eq=E * n_b)
        shared = {name: rng.uniform(-1, 1, node.shape).astype(np.float32)
                  for name, node in system.chain.shared_operands().items()}
    chain = system.chain
    inputs = {
        f"{s.name}.{v}": rng.uniform(
            -1, 1, (E * n_b,) + tuple(node.shape)).astype(np.float32)
        for i, s in enumerate(chain.stages)
        for v, node in chain.host_element_inputs(i)
    }
    on_device = set(inputs)
    if case == "mixed":
        on_device = {f"{chain.stages[0].name}.u"}
        assert on_device < set(inputs)
    return chain, system.plan, inputs, shared, on_device


@pytest.mark.parametrize("case", ["bp3", "pipeline13", "mixed", "reblocked"])
def test_resident_inputs_match_host_inputs(case, rng):
    """A run whose element inputs are single-device ``jax.Array``s, read
    in place by the stage programs, gives bitwise the outputs and the
    checksums (device sums and float64 sums of the outputs) of the same
    data streamed from numpy arrays."""
    import jax

    chain, plan, host, shared, on_device = _resident_case(case, rng)
    assert len(chain.stages) == {"bp3": 1, "reblocked": 3}.get(case, 13)
    device = {q: jax.device_put(a) if q in on_device else a
              for q, a in host.items()}
    for collect in (True, False):
        tracer = trace.Tracer()
        want, got = (simulation.run_chain(chain, plan, inputs=ins,
                                          shared=shared, tracer=tr,
                                          collect_outputs=collect)
                     for ins, tr in ((host, None), (device, tracer)))
        assert got.batches == want.batches > 1
        assert tracer.totals("resident_in_place") == {
            q: float(got.batches) for q in on_device}
        assert got.checksums == want.checksums, (case, collect)
        if collect:
            assert sorted(got.outputs) == sorted(want.outputs)
            for q, a in want.outputs.items():
                assert got.outputs[q].tobytes() == a.tobytes(), (case, q)


def test_resident_inputs_are_not_donated_and_sweeps_repeat(rng):
    """The whole arrays read in place survive a call unchanged, and a
    second call over the same field gives bitwise the first's
    checksums."""
    import jax

    chain, plan, host, shared, _ = _resident_case("bp3", rng)
    field = {q: jax.device_put(a) for q, a in host.items()}
    first = simulation.run_chain(chain, plan, inputs=field, shared=shared)
    for q, a in field.items():
        assert not a.is_deleted(), q
        assert np.asarray(a).tobytes() == host[q].tobytes(), q
    again = simulation.run_chain(chain, plan, inputs=field, shared=shared)
    assert again.checksums == first.checksums
    assert all(np.isfinite(v) for v in first.checksums.values())


def test_plan_infeasible_when_block_floor_exceeds_vmem():
    """Even the BE=1 block must fit on-chip, or the plan says so."""
    tiny = channels.ALVEO_U280.with_(vmem_bytes=8192)
    plan = mchain.plan_chain(
        helmholtz_chain(11), target=tiny, batch_elements=64
    )
    assert not plan.feasible
    assert "block working set" in plan.infeasible_reason
    chain_plan = mchain.plan_chain(
        operators.build_cfd_chain(11), target=tiny, batch_elements=64
    )
    assert not chain_plan.feasible
    assert "block working set" in chain_plan.infeasible_reason


# ---------------------------------------------------------------------------
# VMEM-budgeted Pallas block
# ---------------------------------------------------------------------------


def test_plan_block_elements_fits_vmem():
    """Acceptance: the plan-chosen Pallas block's working set fits the
    target's VMEM, divides E, and shows up in the report."""
    t = channels.TPU_V5E
    plan = mchain.plan_chain(
        helmholtz_chain(11), target=t, backends=("pallas",),
        batch_elements=4096,
    )
    sp = plan.stages[0]
    assert sp.block_elements > 1
    assert plan.batch_elements % sp.block_elements == 0
    assert sp.block_working_set_bytes <= t.vmem_bytes
    assert f"BE={sp.block_elements} (vmem ws" in plan.report()
    # maximal: the next power of two would blow the reserve budget
    from repro.kernels.helmholtz import ops as hops
    assert hops.block_working_set_bytes(
        11, sp.block_elements, t
    ) == sp.block_working_set_bytes
    bigger = min(sp.block_elements * 2, plan.batch_elements)
    if bigger > sp.block_elements:
        assert hops.block_working_set_bytes(
            11, bigger, t
        ) > layout.vmem_budget_bytes(t)


def test_tpu_block_working_set_is_padded_and_double_buffered():
    """On a v5e the block is sized as the kernel lays it out: element
    windows (p,p,p, rows, 128) padded to whole (8, 128) tiles and double-
    buffered, plus two 8-row chunks of the largest intermediate -- and
    that working set fits the vmem_limit_bytes the kernel passes."""
    from repro.kernels.helmholtz import ops as hops

    t = channels.TPU_V5E
    untiled = t.with_(tiled_vmem=False)

    def hops_ws(be, *, tiled):
        return hops.block_working_set_bytes(11, be, t if tiled else untiled)

    plan = mchain.plan_chain(
        helmholtz_chain(11), target=t, backends=("pallas",)
    )
    sp = plan.stages[0]
    blk, p3 = sp.block_elements, 11 ** 3
    rows = -(-(-(-blk // 128)) // 8) * 8          # whole sublane tiles
    windows = 2 * 3 * p3 * rows * 128 * 4         # u, D, v; two buffers
    scratch = 2 * p3 * 8 * 128 * 4                # t/r, one chunk each
    assert sp.block_working_set_bytes == windows + scratch + 11 * 11 * 4
    assert sp.block_working_set_bytes <= layout.vmem_budget_bytes(t)
    assert blk >= 1024 and plan.batch_elements % blk == 0
    # padding is real: the tiled count is never below the unpadded one,
    # and a block under one tile of rows costs a whole tile
    for be in (1, 64, blk):
        padded = hops_ws(be, tiled=True)
        assert padded >= hops_ws(be, tiled=False)
    assert hops_ws(1, tiled=True) == hops_ws(1024, tiled=True)


def test_pallas_backend_runs_with_plan_block(rng):
    """The plan-driven block produces correct results through the
    compiled pallas path (interpret mode on CPU)."""
    p, E = 5, 8
    plan = mchain.plan_chain(
        helmholtz_chain(p), target=channels.CPU_HOST, backends=("pallas",),
        batch_elements=E,
    )
    blk = plan.stages[0].block_elements
    assert blk >= 1
    assert E % blk == 0
    from repro.kernels.helmholtz import ops as hops
    impl = hops.make_pallas_impl(impl="interpret", block_elements=blk)
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    D = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    got = np.asarray(impl({"S": S, "D": D, "u": u})["v"])
    ref = operators.build_inverse_helmholtz(p)
    want = np.asarray(ref.batched_fn({"S": S, "D": D, "u": u})["v"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


#: (plan-only chain, planned backends, rebuild with the plan's block)
#: for the composed CFD chain and the Helmholtz operator alone
_PALLAS_REBUILDS = {
    "cfd": (
        operators.build_cfd_chain,
        ("xla", "xla", "pallas"),
        lambda p, plan: operators.build_cfd_chain(
            p, backends=("xla", "xla", "pallas"), chain_plan=plan
        ),
    ),
    "helmholtz": (
        helmholtz_chain,
        ("pallas",),
        lambda p, plan: operators.build_inverse_helmholtz(
            p, backend="pallas",
            block_elements=operators.chain_stage_block_elements(
                plan, "helmholtz"
            ),
        ),
    ),
}


@pytest.mark.parametrize("name", sorted(_PALLAS_REBUILDS))
def test_chain_plan_block_reaches_pallas_stage(monkeypatch, name):
    """Regression: rebuilding with the ChainPlan threads the plan's
    per-stage VMEM block into the Pallas Helmholtz kernel (the
    compiled-before-planning chain cannot know it)."""
    build, backends, rebuild = _PALLAS_REBUILDS[name]
    p, E = 5, 192  # not divisible by the kernel default of 128
    plan_only = build(p)
    plan = mchain.plan_chain(
        plan_only, target=channels.TPU_V5E, batch_elements=E,
        backends=backends,
    )
    blk = operators.chain_stage_block_elements(plan, "helmholtz")
    assert blk == plan.stages[-1].block_elements
    assert blk is not None and E % blk == 0
    assert operators.chain_stage_block_elements(plan, "nosuch") is None
    assert operators.chain_stage_block_elements(None, "helmholtz") is None

    seen = {}
    from repro.kernels.helmholtz import ops as hops
    real = hops.make_pallas_impl

    def spy(impl="auto", block_elements=hops.DEFAULT_BLOCK_ELEMENTS, **kw):
        seen["block_elements"] = block_elements
        return real(impl=impl, block_elements=block_elements, **kw)

    monkeypatch.setattr(
        "repro.cfd.operators.helmholtz_ops.make_pallas_impl", spy
    )
    rebuild(p, plan)
    assert seen["block_elements"] == blk


def test_layout_vmem_block_matches_kernel_formula():
    """memory.layout's generic block working set agrees with the
    Helmholtz kernel's closed form, so the plan and the kernel can
    never disagree about what fits."""
    from repro.core import dsl, rewrite
    from repro.kernels.helmholtz import ops as hops

    for p in (5, 7, 11):
        prog = rewrite.optimize(dsl.inverse_helmholtz_program(p))
        for be in (1, 8, 64, 2048):
            for t in (channels.ALVEO_U280, channels.TPU_V5E):
                assert layout.block_working_set_bytes(
                    prog, be, t, bytes_per_scalar=4
                ) == hops.block_working_set_bytes(p, be, t)


# ---------------------------------------------------------------------------
# measured contention: profile-store stage samples re-price steady state
# ---------------------------------------------------------------------------

def test_contention_fit_round_trip(cfd_chain, tmp_path):
    """Record synthetic per-stage measurements into a ProfileStore, plan
    with profile=, and the fitted multipliers invert the steady-state
    model exactly: max(t_host, k*dev) + t_overhead == measured."""
    from repro.trace.profile import ProfileStore

    kw = dict(target=channels.ALVEO_U280, batch_elements=128, n_eq=1024,
              prefetch_depth=1)
    plan = mchain.plan_chain(cfd_chain, **kw)
    assert plan.cost.pipelined_stages and plan.cost.contention

    store = ProfileStore(path=str(tmp_path / "p.json"), fingerprint="fp")
    k_true = {}
    samples = []
    for i, sp in enumerate(plan.stages):
        c = plan.cost.stages[i]
        dev = max(c.t_compute, c.t_hbm)
        # device-bound evidence: the device part must clear the host link
        k = max(2.0, 1.5 * c.t_host / dev) + 0.5 * i
        k_true[sp.name] = k
        samples.append({
            "scope": f"stage:{sp.name}",
            "predicted_s": c.t_pipelined,
            "measured_s": c.t_overhead + k * dev,
            "bottleneck": c.bottleneck,
        })
    # chain-level sample: the fit must ignore non-stage scopes
    samples.append({"scope": "chain", "predicted_s": 1.0,
                    "measured_s": 2.0, "bottleneck": "compute"})
    assert store.record(plan.target.name, plan.signature,
                        samples) == len(samples)

    fitted = mchain.plan_chain(cfd_chain, profile=store, **kw)
    assert len(fitted.cost.contention_fit) == len(fitted.stages)
    for i, sp in enumerate(fitted.stages):
        assert fitted.cost.contention_fit[i] == pytest.approx(
            k_true[sp.name])
    expect = tuple(
        max(c.t_host, k_true[sp.name] * max(c.t_compute, c.t_hbm))
        + c.t_overhead
        for sp, c in zip(fitted.stages, fitted.cost.stages)
    )
    assert fitted.cost.stage_steady_times == pytest.approx(expect)
    assert "contention fitted from profile" in fitted.report()
    # everything but the cost fit is the structural plan
    assert fitted.stages == plan.stages
    assert fitted.placement == plan.placement


def test_contention_fit_keeps_structural_without_evidence(cfd_chain,
                                                          tmp_path):
    """Host-bound samples say nothing about device sharing: the fit
    falls back to the placement's structural count per stage, and a
    store with no usable samples leaves the plan untouched."""
    from repro.trace.profile import ProfileStore

    kw = dict(target=channels.ALVEO_U280, batch_elements=128, n_eq=1024,
              prefetch_depth=1)
    plan = mchain.plan_chain(cfd_chain, **kw)
    store = ProfileStore(path=str(tmp_path / "p.json"), fingerprint="fp")
    host_bound = [{
        # measured below t_host: the link hides the device terms
        "scope": f"stage:{sp.name}",
        "predicted_s": 1.0,
        "measured_s": c.t_overhead + 0.5 * c.t_host if c.t_host else 1e-12,
        "bottleneck": "host-link",
    } for sp, c in zip(plan.stages, plan.cost.stages)]
    fit = mchain.fit_contention(
        plan.cost, [sp.name for sp in plan.stages], host_bound)
    assert fit == ()
    same = mchain.apply_profile_contention(plan, store)
    assert same == plan  # cold store: unchanged
    # one device-bound sample for one stage: the others keep structural
    c0 = plan.cost.stages[0]
    dev0 = max(c0.t_compute, c0.t_hbm)
    k0 = max(2.0, 2.0 * c0.t_host / dev0)
    partial = mchain.fit_contention(
        plan.cost, [sp.name for sp in plan.stages],
        [{"scope": f"stage:{plan.stages[0].name}",
          "predicted_s": 1.0, "measured_s": c0.t_overhead + k0 * dev0,
          "bottleneck": "compute"}])
    assert partial[0] == pytest.approx(k0)
    assert all(k == 0.0 for k in partial[1:])
    import dataclasses as _dc
    cost = _dc.replace(plan.cost, contention_fit=partial)
    # unfitted stages price with the structural count, fitted with k0
    assert cost.stage_steady_times[1:] == plan.cost.stage_steady_times[1:]
    assert cost.stage_steady_times[0] == pytest.approx(
        max(c0.t_host, k0 * dev0) + c0.t_overhead)


def test_ring_releases_streams_after_their_last_reader():
    """Each batch in the skewed ring keeps only what a later stage or
    the host still reads: a host stream leaves ``staged`` once read, a
    handoff leaves the carry after its last consumer.  (Keeping every
    stream until retire ran a 13-stage p=11 ring out of a v5e's HBM.)"""
    import jax.numpy as jnp

    from repro import flow

    system = flow.compile(
        operators.CFD_PIPELINE_SRC.format(p=3), target=channels.CPU_HOST,
        batch_elements=4, n_eq=8,
    )
    chain = system.chain
    n = len(chain.stages)
    assert n > 3
    out_names = [f"{s.name}.{o}" for i, s in enumerate(chain.stages)
                 for o, _ in chain.chain_outputs(i)]
    shared = {name: jnp.ones(v.shape, jnp.float32)
              for name, v in chain.shared_operands().items()}
    staged = {f"{s.name}.{o}": jnp.ones((4,) + v.shape, jnp.float32)
              for i, s in enumerate(chain.stages)
              for o, v in chain.host_element_inputs(i)}
    fns = simulation.chain_stage_fns(chain, [shared] * n, out_names)
    carry, produced = None, 0
    for i, fn in enumerate(fns):
        carry = fn(staged, carry)
        produced += len(chain.stages[i].program.outputs)
        later = {f"{chain.stages[p].name}.{o}"
                 for j in range(i + 1, n)
                 for p, o in chain.resolved[j].values()}
        assert set(carry) <= later | set(out_names), i
        assert not any(q.startswith(f"{chain.stages[i].name}.")
                       for q in staged), i
    assert set(carry) == set(out_names) and not staged
    assert produced > len(carry)
