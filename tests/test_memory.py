"""repro.memory: planner determinism, paper batch sizing, DSE cost-model
monotonicity, and end-to-end prefetch-pipeline equivalence.  A single
operator is planned, explored and run as a one-stage chain."""
import warnings

import jax
import numpy as np
import pytest

from conftest import helmholtz_chain
from repro.cfd import operators, simulation
from repro.core import dsl, emit, rewrite, schedule
from repro.memory import channels, dse, layout
from repro.memory import pipeline as mempipe
from repro.memory.chain import plan_chain


def _helmholtz_prog(p):
    return rewrite.optimize(
        dsl.parse(
            dsl.INVERSE_HELMHOLTZ_SRC.format(p=p),
            element_vars=("u", "D", "v"),
        )
    )


# ---------------------------------------------------------------------------
# plan construction
# ---------------------------------------------------------------------------


def test_plan_determinism():
    """Same inputs -> identical plan (buffers, channels, cost, report)."""
    a = plan_chain(helmholtz_chain(11), target=channels.ALVEO_U280)
    b = plan_chain(helmholtz_chain(11), target=channels.ALVEO_U280)
    assert a == b
    assert a.report() == b.report()


def test_auto_batch_matches_paper_channel_sizing():
    """The planner's E is the paper's: the elements whose u, D and v
    fit one 256 MB pseudo-channel (Alveo U280: 8 GiB HBM2 / 32
    channels), up to the block padding that keeps E a VMEM-block
    multiple (prime-ish channel quotients must never force the Pallas
    block divisor tiny)."""
    t = channels.ALVEO_U280
    assert t.channel_bytes == 256 * 2 ** 20
    for p in (7, 11):
        plan = plan_chain(helmholtz_chain(p), target=t, policy="float32")
        blk = plan.stages[0].block_elements
        base = plan.batch_elements - plan.batch_pad_elements
        assert base == t.channel_bytes // (3 * p ** 3 * 4)
        # padding did its job: E is block-composite, never block-starved
        assert plan.batch_elements % blk == 0
        assert blk * 2 >= layout.vmem_block_elements(
            rewrite.optimize(dsl.inverse_helmholtz_program(p)), t,
            bytes_per_scalar=4,
        )
        if plan.batch_pad_elements:
            assert "E auto-padded" in plan.report()


def test_auto_batch_capped_by_problem_size():
    plan = plan_chain(
        helmholtz_chain(11), target=channels.ALVEO_U280, n_eq=1000
    )
    assert plan.batch_elements == 1000


def test_pad_batch_for_block():
    """The E auto-padding rule: prime-ish batches round up to a block
    multiple, composite-enough batches are left alone, and a problem-
    size limit snaps down instead of padding past the data."""
    assert layout.pad_batch_for_block(1021, 128) == (1024, 3)   # prime
    assert layout.pad_batch_for_block(1000, 512) == (1000, 0)   # 500 | E
    assert layout.pad_batch_for_block(100, 128) == (100, 0)     # E <= cap
    assert layout.pad_batch_for_block(7, 1) == (7, 0)
    assert layout.pad_batch_for_block(1021, 128, limit=1023) == (896, -125)
    # chain form: E composite for the largest cap can still starve a
    # smaller-cap stage (1018 = 2 * 509: fine for 512, block 2 for 256)
    assert layout.pad_batch_for_block(1018, 512) == (1018, 0)
    assert layout.pad_batch_for_block(
        1018, 512, caps=(512, 256)
    ) == (1024, 6)


def test_plan_buffers_and_channels():
    chain = helmholtz_chain(11)
    plan = plan_chain(chain, target=channels.ALVEO_U280, prefetch_depth=1)
    roles = {b.name: b.role for b in plan.buffers}
    assert roles == {
        "helmholtz.D": "in", "helmholtz.u": "in", "helmholtz.v": "out",
        "S": "shared",
    }
    ins = [b for b in plan.buffers if b.role == "in"]
    # K=1 prefetch: ping/pong pair + the retiring batch JAX frees only
    # after its async compute completes = 3 resident replicas
    assert all(b.replicas == 3 for b in ins)
    serial = plan_chain(
        chain, target=channels.ALVEO_U280, prefetch_depth=0
    )
    assert all(
        b.replicas == 1 for b in serial.buffers if b.role == "in"
    )
    # burst packing: padded to the 64 B AXI quantum, never smaller
    for b in plan.buffers:
        assert b.padded_bytes >= b.element_bytes
        assert b.padded_bytes % channels.ALVEO_U280.burst_bytes == 0
    assert 0 < plan.channels_used <= channels.ALVEO_U280.n_channels
    assert plan.feasible


def test_staged_plan_has_intermediate_buffers():
    plan = plan_chain(
        helmholtz_chain(11), target=channels.ALVEO_U280,
        backends=("staged",),
    )
    inters = [b for b in plan.buffers if b.role == "inter"]
    assert inters, "staged backend must expose group-boundary streams"
    # intermediates cross HBM twice (write + read back)
    assert plan.hbm_stream_bytes > plan.host_stream_bytes


def test_infeasible_plan_reported_not_raised():
    tiny = channels.ALVEO_U280.with_(hbm_bytes=2 ** 20, n_channels=4)
    plan = plan_chain(helmholtz_chain(11), target=tiny, batch_elements=4096)
    assert not plan.feasible
    assert "exceeds" in plan.infeasible_reason
    assert "NO" in plan.report()


# ---------------------------------------------------------------------------
# DSE cost model
# ---------------------------------------------------------------------------


def test_cost_monotone_in_bandwidth():
    """More bandwidth must never predict a slower plan."""
    base_t = channels.ALVEO_U280
    chain = helmholtz_chain(11)
    points = [
        dict(backends=("xla",), prefetch_depth=0),
        dict(backends=("xla",), prefetch_depth=1),
        dict(backends=("xla",), prefetch_depth=4, cu_count=4),
        dict(backends=("staged",), prefetch_depth=1),
        dict(backends=("staged",), prefetch_depth=2, policy="bfloat16"),
    ]
    for kw in points:
        prev = plan_chain(chain, target=base_t, n_eq=1 << 16, **kw)
        for scale in (2.0, 4.0, 16.0):
            t = base_t.with_(
                hbm_bw=base_t.hbm_bw * scale,
                host_link_bw=base_t.host_link_bw * scale,
            )
            cur = plan_chain(chain, target=t, n_eq=1 << 16, **kw)
            assert cur.cost.t_pipelined <= prev.cost.t_pipelined * (1 + 1e-12)
            assert cur.cost.t_serial <= prev.cost.t_serial * (1 + 1e-12)
            prev = cur


def test_cost_overlap_never_slower_than_serial():
    chain = helmholtz_chain(11)
    for depth in (1, 2, 4):
        plan = plan_chain(
            chain, target=channels.ALVEO_U280, prefetch_depth=depth,
            n_eq=1 << 20,
        )
        assert plan.cost.t_pipelined <= plan.cost.t_serial * (1 + 1e-12)
        assert plan.cost.overlap_speedup >= 1.0 - 1e-12


def test_explore_returns_ranked_set():
    # the paper's evaluation grid: both backends and policies, E at the
    # full channel and its halves, K up to 4, up to 4 CUs
    space = dse.ChainDesignSpace(
        backends=("xla", "staged"), policies=("float32", "bfloat16"),
        batch_divisors=(1, 2, 4), prefetch_depths=(0, 1, 2, 4),
        cu_counts=(1, 2, 4),
    )
    cands = dse.explore_chain(
        helmholtz_chain(11), target=channels.ALVEO_U280, n_eq=1 << 16,
        space=space,
    )
    assert len(cands) > 20
    feas = [c for c in cands if c.plan.feasible]
    assert feas, "the paper's operating point must be feasible"
    # ranked: feasible first, then by predicted time per element
    pred = [c.predicted_s_per_element for c in feas]
    assert pred == sorted(pred)
    assert all(c.plan.feasible for c in cands[: len(feas)])
    front = dse.pareto_front(cands)
    assert front
    assert all(c.plan.feasible for c in front)
    assert set(id(c) for c in front) <= set(id(c) for c in cands)


# ---------------------------------------------------------------------------
# transfer pipeline
# ---------------------------------------------------------------------------


def test_prefetch_depth_semantics():
    staged_log = []
    consumed = []

    def stage(x):
        staged_log.append(x)
        return x

    def consume(x, carry):
        # when item k is consumed, items up to k+2 are already staged
        consumed.append(x)
        assert len(staged_log) >= min(5, len(consumed) + 2)
        return x

    out = mempipe.run_stage_pipelined(
        [consume], range(5), stage_fn=stage, depths=2
    )
    assert consumed == out == list(range(5))
    with pytest.raises(ValueError):
        mempipe.run_stage_pipelined(
            [consume], range(3), stage_fn=stage, depths=-1
        )


def test_pipelined_run_bitwise_matches_serial(rng):
    """K-deep prefetch + deferred sync must be bit-identical to the
    serial baseline (paper Fig. 14a: ping/pong changes nothing)."""
    p, E = 5, 16
    c = operators.build_inverse_helmholtz(p)
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    batches = [
        {
            "D": rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32),
            "u": rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32),
        }
        for _ in range(4)
    ]

    def compute(staged, carry):
        return c.batched_fn({"S": S, **staged})["v"]

    stage = lambda b: {k: jax.device_put(v) for k, v in b.items()}
    serial = mempipe.run_stage_pipelined(
        [compute], batches, stage_fn=stage, depths=0
    )
    deep = mempipe.run_stage_pipelined(
        [compute], batches, stage_fn=stage, depths=2
    )
    assert len(serial) == len(deep) == 4
    for a, b in zip(serial, deep):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _record_stage(log, name, transform):
    """A stage fn that logs its dispatch order and applies a pure
    transform to the carry (stage 0 receives carry=None)."""

    def fn(staged, carry):
        log.append((name, staged))
        return transform(staged if carry is None else carry)

    return fn


def test_stage_pipelined_depth0_matches_serial_composition():
    """All-zero depths degrade to the back-to-back schedule: results and
    dispatch order are exactly the serial composition's."""
    log = []
    fns = [
        _record_stage(log, "s0", lambda x: x * 10),
        _record_stage(log, "s1", lambda x: x + 1),
    ]
    out = mempipe.run_stage_pipelined(fns, range(3), depths=0)
    assert out == [1, 11, 21]
    assert log == [
        ("s0", 0), ("s1", 0), ("s0", 1), ("s1", 1), ("s0", 2), ("s1", 2),
    ]


def test_stage_pipelined_skews_dispatch_order():
    """With inter-stage ring depth 1, stage 1 of batch k-1 is dispatched
    in the same tick as stage 0 of batch k -- the tentpole's software-
    pipelined interleaving -- and results still come back in batch
    order."""
    log = []
    fns = [
        _record_stage(log, "s0", lambda x: x * 10),
        _record_stage(log, "s1", lambda x: x + 1),
    ]
    out = mempipe.run_stage_pipelined(fns, range(4), depths=(1, 1))
    assert out == [1, 11, 21, 31]
    assert log == [
        ("s0", 0),
        ("s0", 1), ("s1", 0),
        ("s0", 2), ("s1", 1),
        ("s0", 3), ("s1", 2),
        ("s1", 3),
    ]


def test_stage_pipelined_fill_drain_with_fewer_batches_than_depth():
    """n_batches < total skew: every batch still flows through every
    stage exactly once, in order, and the drain retires them in batch
    order."""
    log = []
    fns = [
        _record_stage(log, "s0", lambda x: x + 1),
        _record_stage(log, "s1", lambda x: x * 2),
        _record_stage(log, "s2", lambda x: x - 3),
    ]
    out = mempipe.run_stage_pipelined(fns, range(2), depths=(4, 3, 3))
    assert out == [(0 + 1) * 2 - 3, (1 + 1) * 2 - 3]
    for k in range(2):
        assert [n for n, s in log if s == k] == ["s0", "s1", "s2"]
    assert out == mempipe.run_stage_pipelined(fns, range(2), depths=0)
    # an empty batch source is a no-op at any depth
    assert mempipe.run_stage_pipelined(fns, [], depths=(4, 3, 3)) == []


def test_stage_pipelined_reduce_and_defer_sync():
    """reduce_fn maps the last stage's carry before any sync; deferred
    sync holds exactly one realized value back until the next batch."""
    events = []

    def reduce_fn(x):
        events.append(("reduce", x.v))
        return x

    class Traced:
        """Quacks enough like a device value to observe device_get."""

        def __init__(self, v):
            self.v = v

        def __array__(self, *a, **kw):  # jax.device_get realizes via this
            events.append(("sync", self.v))
            return np.asarray(self.v)

    fns = [lambda staged, carry: Traced(staged * 10)]
    out = mempipe.run_stage_pipelined(
        fns, range(3), depths=1, reduce_fn=reduce_fn, defer_sync=True
    )
    assert [int(x) for x in out] == [0, 10, 20]
    # deferred: batch k's sync happens only after batch k+1 was reduced
    # (the dispatch queue never drains mid-run)
    assert events == [
        ("reduce", 0), ("reduce", 10), ("sync", 0),
        ("reduce", 20), ("sync", 10), ("sync", 20),
    ]
    # defer_sync=False realizes each batch immediately after its reduce
    events.clear()
    out = mempipe.run_stage_pipelined(
        fns, range(2), depths=0, reduce_fn=reduce_fn
    )
    assert [int(x) for x in out] == [0, 10]
    assert events == [
        ("reduce", 0), ("sync", 0), ("reduce", 10), ("sync", 10),
    ]


def test_stage_pipelined_validates_arguments():
    fns = [lambda s, c: s]
    with pytest.raises(ValueError, match="at least one stage"):
        mempipe.run_stage_pipelined([], range(2))
    with pytest.raises(ValueError, match=">= 0"):
        mempipe.run_stage_pipelined(fns, range(2), depths=-1)
    with pytest.raises(ValueError, match="stage depths"):
        mempipe.run_stage_pipelined(fns, range(2), depths=(1, 1))


def test_stage_pipelined_bitwise_matches_serial_on_device(rng):
    """The skewed schedule changes dispatch order only: device results
    are bit-identical to the serial composition (paper Fig. 14a
    generalized across stages)."""
    p, E = 5, 8
    c = operators.build_inverse_helmholtz(p)
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    batches = [
        {
            "D": rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32),
            "u": rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32),
        }
        for _ in range(4)
    ]
    fns = [
        lambda staged, carry: c.batched_fn({"S": S, **staged})["v"],
        lambda staged, carry: carry * 2.0,
    ]
    stage = lambda b: {k: jax.device_put(v) for k, v in b.items()}
    serial = mempipe.run_stage_pipelined(
        fns, batches, stage_fn=stage, depths=0
    )
    skewed = mempipe.run_stage_pipelined(
        fns, batches, stage_fn=stage, depths=(2, 1)
    )
    assert len(serial) == len(skewed) == 4
    for a, b in zip(serial, skewed):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_simulation_driver_plan_resolves_batch():
    """No hardcoded E: the planner sizes the batch from the channel model
    and the executor runs the batches the plan sized."""
    chain = helmholtz_chain(5)
    plan = plan_chain(chain, n_eq=512)  # batch_elements unset
    assert plan.batch_elements >= 1
    res = simulation.run_chain(chain, plan, n_eq=512, max_batches=2)
    assert res.plan is plan
    assert res.elements == res.batches * plan.batch_elements
    assert res.checksums and all(
        np.isfinite(v) for v in res.checksums.values()
    )


def test_simulation_checksum_invariant_to_prefetch_depth():
    chain = helmholtz_chain(5)
    res = {}
    for depth in (0, 1, 3):
        plan = plan_chain(
            chain, batch_elements=64, prefetch_depth=depth, n_eq=256
        )
        res[depth] = simulation.run_chain(
            chain, plan, n_eq=256, max_batches=4
        ).checksums["helmholtz.v"]
    assert res[0] == pytest.approx(res[1], abs=1e-6)
    assert res[0] == pytest.approx(res[3], abs=1e-6)


# ---------------------------------------------------------------------------
# wiring: schedule stream bytes, emit donation, roofline constants
# ---------------------------------------------------------------------------


def test_schedule_exposes_stream_bytes():
    sch = schedule.schedule(_helmholtz_prog(7), bytes_per_scalar=4)
    io = sch.stream_io_bytes(4)
    assert set(io) == {g.name for g in sch.groups}
    for g in sch.groups:
        ins, outs = io[g.name]
        assert ins == g.in_stream_bytes(4) > 0
        assert outs == g.out_stream_bytes(4) > 0
    # the last group streams the program output: p^3 scalars
    assert sch.groups[-1].out_stream_bytes(4) >= 7 ** 3 * 4
    assert sch.stream_bytes(4)[sch.groups[-1].name] >= 7 ** 3 * 4


def test_emit_accepts_donation_hints(rng):
    p, E = 5, 8
    prog = _helmholtz_prog(p)
    plain = emit.compile_program(prog)
    with warnings.catch_warnings():
        # CPU backends may ignore donation with a warning; the hint must
        # never change results
        warnings.simplefilter("ignore")
        donated = emit.compile_program(prog, donate_args=("D", "u"))
        assert donated.donate_args == ("D", "u")
        S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
        env = {
            "S": S,
            "D": rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32),
            "u": rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32),
        }
        want = np.asarray(plain.batched_fn(dict(env))["v"])
        got = np.asarray(
            donated.batched_fn(
                {k: jax.device_put(v) for k, v in env.items()}
            )["v"]
        )
    assert np.array_equal(want, got)
    with pytest.raises(ValueError):
        emit.compile_program(prog, donate_args=("nope",))


def test_roofline_shares_channel_constants():
    from repro.analysis import roofline

    assert roofline.PEAK_FLOPS_BF16 == channels.TPU_V5E.peak_flops
    assert roofline.HBM_BW == channels.TPU_V5E.hbm_bw
    assert roofline.ICI_LINK_BW == channels.TPU_V5E.ici_bw


def test_layout_stream_bytes_match_simconfig_model():
    """u, D in and v out: three p^3 records per element (S is shared)."""
    prog = _helmholtz_prog(11)
    assert layout.stream_bytes_per_element(prog, 4) == 3 * 11 ** 3 * 4
