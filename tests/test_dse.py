"""DSE coverage: predict_cost monotonicity, pareto_front semantics, the
measured-feedback CostCorrection, and explore-with-measurement smokes
(a one-stage chain and the CFD chain)."""
import dataclasses

import pytest

from conftest import helmholtz_chain
from repro.cfd import operators
from repro.memory import channels, dse
from repro.memory.chain import plan_chain
from repro.memory.plan import predict_cost

#: the paper's evaluation grid: both backends and policies, E at the
#: full channel and its halves, K up to 4, up to 4 CUs
PAPER_SPACE = dse.ChainDesignSpace(
    backends=("xla", "staged"), policies=("float32", "bfloat16"),
    batch_divisors=(1, 2, 4), prefetch_depths=(0, 1, 2, 4),
    cu_counts=(1, 2, 4),
)

#: the chains the explorer smokes run on: the inverse Helmholtz operator
#: alone and the composed CFD application, both at p=5
CHAINS = {
    "helmholtz": lambda: helmholtz_chain(5),
    "cfd": lambda: operators.build_cfd_chain(5),
}


BASE = dict(
    policy="float32", batch_elements=1024, flops_per_element=20_000,
    host_bytes=8 << 20, hbm_bytes=8 << 20, channels_used=4,
    prefetch_depth=1, cu_count=1,
)


def _cost(**over):
    kw = {**BASE, **over}
    return predict_cost(channels.ALVEO_U280, **kw)


# ---------------------------------------------------------------------------
# predict_cost monotonicity (the model's core guarantee)
# ---------------------------------------------------------------------------


def test_predict_cost_monotone_in_channels():
    """More assigned pseudo-channels never predicts slower (the paper's
    point: unmapped channels are wasted bandwidth)."""
    t = channels.ALVEO_U280
    prev = None
    for ch in range(1, t.n_channels + 1):
        c = _cost(channels_used=ch)
        if prev is not None:
            assert c.t_hbm <= prev.t_hbm * (1 + 1e-12)
            assert c.t_pipelined <= prev.t_pipelined * (1 + 1e-12)
            assert c.t_serial <= prev.t_serial * (1 + 1e-12)
        prev = c
    # beyond the physical channel count, bandwidth stops improving
    assert _cost(channels_used=t.n_channels + 8).t_hbm == pytest.approx(
        _cost(channels_used=t.n_channels).t_hbm
    )


def test_predict_cost_monotone_in_prefetch_depth():
    """Deeper K never predicts slower under the steady-state model (no
    n_batches => no pipeline-fill term)."""
    prev = None
    for k in (0, 1, 2, 4, 8):
        c = _cost(prefetch_depth=k)
        if prev is not None:
            assert c.t_pipelined <= prev.t_pipelined * (1 + 1e-12)
        prev = c


def test_predict_cost_fill_term_bounded():
    """With a finite batch count the K-deep fill cost is charged, but
    never exceeds the available batches (K >= n_batches saturates)."""
    deep = _cost(prefetch_depth=16, n_batches=4)
    deeper = _cost(prefetch_depth=64, n_batches=4)
    assert deep.t_pipelined == pytest.approx(deeper.t_pipelined)
    nofill = _cost(prefetch_depth=1)
    assert _cost(prefetch_depth=1, n_batches=4).t_pipelined >= (
        nofill.t_pipelined
    )


# ---------------------------------------------------------------------------
# pareto_front
# ---------------------------------------------------------------------------


def test_pareto_front_on_explored_candidates():
    cands = dse.explore_chain(
        helmholtz_chain(7), target=channels.ALVEO_U280, n_eq=1 << 14,
        space=PAPER_SPACE,
    )
    front = dse.pareto_front(cands)
    assert front
    feas = [c for c in cands if c.plan.feasible]
    # the top-ranked feasible candidate is never dominated
    assert any(f is feas[0] for f in front)
    # no member dominates another
    for a in front:
        for b in front:
            if a is b:
                continue
            dominates = (
                a.predicted_s_per_element <= b.predicted_s_per_element
                and a.plan.resident_bytes <= b.plan.resident_bytes
                and (a.predicted_s_per_element < b.predicted_s_per_element
                     or a.plan.resident_bytes < b.plan.resident_bytes)
            )
            assert not dominates
    # every non-front feasible candidate is dominated by some front member
    front_ids = {id(c) for c in front}
    for c in feas:
        if id(c) in front_ids:
            continue
        assert any(
            f.predicted_s_per_element <= c.predicted_s_per_element
            and f.plan.resident_bytes <= c.plan.resident_bytes
            for f in front
        )


def test_pareto_front_excludes_infeasible():
    cands = dse.explore_chain(
        helmholtz_chain(11),
        target=channels.ALVEO_U280.with_(hbm_bytes=2 ** 26, n_channels=4),
        n_eq=1 << 16, space=PAPER_SPACE,
    )
    front = dse.pareto_front(cands)
    assert all(c.plan.feasible for c in front)


# ---------------------------------------------------------------------------
# measured-feedback correction
# ---------------------------------------------------------------------------


def _plan():
    return plan_chain(
        helmholtz_chain(5), target=channels.ALVEO_U280, batch_elements=64
    )


def _measured_cand(pred, meas, feasible=True):
    plan = _plan()
    if not feasible:
        plan = dataclasses.replace(plan, feasible=False)
    return dse.Candidate(
        plan=plan, predicted_s_per_element=pred,
        measured_s_per_element=meas,
    )


def test_fit_correction_geometric_mean():
    cands = [
        _measured_cand(1e-6, 2e-6),   # ratio 2
        _measured_cand(1e-6, 8e-6),   # ratio 8
        _measured_cand(1e-6, None),   # unmeasured: ignored
    ]
    corr = dse.fit_correction(cands)
    assert corr.n_samples == 2
    assert corr.factor == pytest.approx(4.0)  # sqrt(2 * 8)
    assert corr.corrected(1e-6) == pytest.approx(4e-6)


def test_fit_correction_identity_without_measurements():
    corr = dse.fit_correction([_measured_cand(1e-6, None)])
    assert corr.factor == 1.0 and corr.n_samples == 0
    assert corr.corrected(3.0) == 3.0


def _cand_with_terms(pred, meas, t_compute, t_hbm, t_host):
    plan = _plan()
    stage = dataclasses.replace(
        plan.cost.stages[0], t_compute=t_compute, t_hbm=t_hbm, t_host=t_host
    )
    cost = dataclasses.replace(plan.cost, stages=(stage,))
    return dse.Candidate(
        plan=dataclasses.replace(plan, cost=cost),
        predicted_s_per_element=pred, measured_s_per_element=meas,
    )


def test_fit_correction_learns_per_term_factors():
    """Ratios are attributed to the measured run's bottleneck term:
    host-bound ladders calibrate the host factor, compute-bound ladders
    the compute factor; unobserved terms fall back to the overall
    geometric mean."""
    host = _cand_with_terms(1e-6, 2e-6, 0.1, 0.2, 1.0)   # ratio 2
    comp = _cand_with_terms(1e-6, 8e-6, 1.0, 0.2, 0.1)   # ratio 8
    corr = dse.fit_correction([host, comp])
    assert corr.n_samples == 2
    assert corr.host_factor == pytest.approx(2.0)
    assert corr.compute_factor == pytest.approx(8.0)
    assert corr.hbm_factor is None
    assert corr.factor == pytest.approx(4.0)
    assert corr.factor_for("host-link") == pytest.approx(2.0)
    assert corr.factor_for("compute") == pytest.approx(8.0)
    assert corr.factor_for("hbm") == pytest.approx(4.0)    # fallback
    assert corr.factor_for(None) == pytest.approx(4.0)
    assert corr.corrected(1e-6, "compute") == pytest.approx(8e-6)
    # apply_correction scales each candidate by its own bottleneck term
    fast = dse.Candidate(plan=host.plan, predicted_s_per_element=2e-6)
    dse.apply_correction([host, comp, fast], corr)
    assert fast.corrected_s_per_element == pytest.approx(4e-6)
    assert comp.corrected_s_per_element == pytest.approx(8e-6)


def test_apply_correction_reranks():
    slow = _measured_cand(1e-6, 5e-6)       # measured: actually slow
    fast = _measured_cand(2e-6, None)       # predicted-only
    ranked = dse.apply_correction([slow, fast], dse.fit_correction([slow]))
    # correction factor 5: fast's corrected prediction = 1e-5 > slow's
    # measured 5e-6, so the measured candidate wins the re-rank
    assert ranked[0] is slow
    assert fast.corrected_s_per_element == pytest.approx(1e-5)


# ---------------------------------------------------------------------------
# chain exploration
# ---------------------------------------------------------------------------


def test_explore_chain_ranked_and_pareto():
    chain = operators.build_cfd_chain(5)
    space = dse.ChainDesignSpace(
        backends=("xla", "staged"), batch_divisors=(1, 2),
        prefetch_depths=(0, 1),
    )
    cands = dse.explore_chain(
        chain, target=channels.ALVEO_U280, n_eq=1 << 14, space=space
    )
    # every (backends, E) point contributes at least the chain-wide
    # uniform (cu, depth) grid (8 combos x 2 E x 2 K) plus the joint
    # per-stage placement frontier, deduplicated
    assert len(cands) >= 32
    assert len(
        {(tuple(sp.backend for sp in c.plan.stages),
          c.plan.batch_elements,
          tuple(sp.prefetch_depth for sp in c.plan.stages),
          c.plan.cu_counts)
         for c in cands}
    ) == len(cands)
    feas = [c for c in cands if c.plan.feasible]
    assert feas
    pred = [c.predicted_s_per_element for c in feas]
    assert pred == sorted(pred)
    assert all(c.plan.feasible for c in cands[: len(feas)])
    front = dse.pareto_front(cands)
    assert front and all(c.plan.feasible for c in front)
    # per-stage backends really vary across the sweep
    combos = {tuple(sp.backend for sp in c.plan.stages) for c in cands}
    assert len(combos) == 8
    # ... and so do the per-stage depth vectors (the joint search emits
    # non-uniform placements, not just the chain-wide sweep)
    depth_vecs = {
        tuple(sp.prefetch_depth for sp in c.plan.stages) for c in cands
    }
    assert any(len(set(v)) > 1 for v in depth_vecs)


def test_chain_cost_overlap_term():
    """The cross-batch overlap term: a pipelined chain is priced by its
    slowest stage plus amortized fill/drain, never worse than the
    back-to-back schedule, and n_batches=1 degenerates to it exactly."""
    from repro.memory import chain as mchain

    chain = operators.build_cfd_chain(5)
    piped = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, batch_elements=256,
        prefetch_depth=1, n_eq=1 << 12,
    )
    flat = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, batch_elements=256,
        prefetch_depth=(1, 0, 0), n_eq=1 << 12,
    )
    assert piped.cost.pipelined_stages and not flat.cost.pipelined_stages
    # the steady state is the slowest *contended* stage: on the default
    # single-device topology all three stages time-slice one device
    assert piped.cost.contention == (3, 3, 3)
    assert piped.cost.t_steady == max(piped.cost.stage_steady_times)
    assert piped.cost.t_steady >= max(
        max(c.t_host, c.t_compute, c.t_hbm) + c.t_overhead
        for c in piped.cost.stages
    )
    # a disjoint placement (one device per stage) removes the contention
    # and can only speed the steady state up
    from repro.memory.placement import DeviceTopology

    disjoint = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, batch_elements=256,
        prefetch_depth=1, n_eq=1 << 12,
        topology=DeviceTopology.homogeneous(3),
    )
    assert disjoint.placement.contention == (1, 1, 1)
    assert disjoint.cost.t_steady <= piped.cost.t_steady * (1 + 1e-12)
    assert piped.cost.t_pipelined == pytest.approx(
        min(piped.cost.t_back_to_back,
            piped.cost.t_steady + piped.cost.t_fill)
    )
    assert piped.cost.t_pipelined <= flat.cost.t_pipelined * (1 + 1e-12)
    assert piped.cost.stage_overlap_speedup >= 1.0 - 1e-12
    assert flat.cost.t_pipelined == pytest.approx(flat.cost.t_back_to_back)
    # the correction hook: a chain's bottleneck is its bottleneck
    # stage's dominating term
    idx = piped.cost.bottleneck_stage
    assert piped.cost.bottleneck == piped.cost.stages[idx].bottleneck
    one = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, batch_elements=256,
        prefetch_depth=1, n_eq=256,
    )
    assert one.cost.t_overlapped == pytest.approx(one.cost.t_back_to_back)


@pytest.mark.parametrize("name", sorted(CHAINS))
def test_explore_chain_calibrate_requires_measurement(name):
    chain = CHAINS[name]()
    with pytest.raises(ValueError, match="measure_top"):
        dse.explore_chain(
            chain, target=channels.CPU_HOST, n_eq=64, calibrate=True
        )


@pytest.mark.slow
@pytest.mark.parametrize(
    "name,n_eq,divisors",
    [("helmholtz", 128, (1, 2)), ("cfd", 64, (1,))],
    ids=["helmholtz", "cfd"],
)
def test_explore_chain_calibrate_smoke(name, n_eq, divisors):
    """Measure-then-calibrate on the real chain driver: every candidate
    gains a corrected prediction, feasible candidates stay ranked
    first."""
    chain = CHAINS[name]()
    space = dse.ChainDesignSpace(
        backends=("xla",), batch_divisors=divisors, prefetch_depths=(0, 1),
    )
    cands = dse.explore_chain(
        chain, target=channels.CPU_HOST, n_eq=n_eq, space=space,
        measure_top=1, measure_batches=2, calibrate=True,
    )
    assert any(c.verified for c in cands)
    assert all(c.corrected_s_per_element is not None for c in cands)
    feas = [c.plan.feasible for c in cands]
    assert feas == sorted(feas, reverse=True)


@pytest.mark.slow
@pytest.mark.parametrize(
    "name,n_eq", [("helmholtz", 256), ("cfd", 64)],
    ids=["helmholtz", "cfd"],
)
def test_explore_chain_measures_matching_candidates(name, n_eq):
    """measure_top verifies the best candidates whose planned backends
    match how the chain was compiled, through the real run_chain."""
    chain = CHAINS[name]()
    space = dse.ChainDesignSpace(
        backends=("xla",), batch_divisors=(1,), prefetch_depths=(0, 1),
    )
    cands = dse.explore_chain(
        chain, target=channels.CPU_HOST, n_eq=n_eq, space=space,
        measure_top=1, measure_batches=2,
    )
    assert any(c.verified for c in cands)
    best = next(c for c in cands if c.verified)
    assert best.measured_s_per_element > 0
    # a plan whose backends differ from the compiled chain is refused
    staged_plan = dse.explore_chain(
        chain, target=channels.CPU_HOST, n_eq=n_eq,
        space=dse.ChainDesignSpace(
            backends=("staged",), batch_divisors=(1,),
            prefetch_depths=(0,),
        ),
    )[0].plan
    assert dse.measure_chain_plan(chain, staged_plan) is None
