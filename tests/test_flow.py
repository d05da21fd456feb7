"""repro.flow: the end-to-end tool flow.  Acceptance: the flow-compiled
Fig. 2 program is bitwise-equal at float32 to the directly compiled
operator (and matches the float64 oracle), the flow-compiled pipeline
subsumes the hand stage cuts bitwise, the CLI's system report is
golden-checked, and every derived ProgramChain validates (hypothesis).
"""
import os
import pathlib

import numpy as np
import pytest

from repro import flow
from repro.cfd import operators, reference, simulation
from repro.core import dsl, liveness
from repro.memory import chain as mchain
from repro.memory import channels, dse, layout

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
EXAMPLES = pathlib.Path(__file__).parent.parent / "examples"


def _chain_run(system, inputs_by_var, shared, **kw):
    """Run a system, routing full input arrays to whichever stage hosts
    each element stream (stage names differ between auto/named cuts)."""
    ch = system.chain
    inputs = {}
    for i, s in enumerate(ch.stages):
        for n, _ in ch.host_element_inputs(i):
            inputs[f"{s.name}.{n}"] = inputs_by_var[n]
    return system.run(
        inputs=inputs, shared=shared, collect_outputs=True, **kw
    )


# ---------------------------------------------------------------------------
# acceptance: Fig. 2 end-to-end, zero hand-written operator code
# ---------------------------------------------------------------------------


def test_flow_fig2_bitwise_and_oracle(rng):
    """flow.compile on the paper's Fig. 2 source yields a ChainPlan plus
    an executable bitwise-identical at float32 to the directly compiled
    operator, and numerically matching the float64 reference oracle."""
    p, E, n_b = 5, 8, 3
    n = E * n_b
    src = dsl.INVERSE_HELMHOLTZ_SRC.format(p=p)
    system = flow.compile(
        src, name="fig2", element_vars=("u", "D", "v"),
        target=channels.CPU_HOST, batch_elements=E, n_eq=n,
    )
    assert system.plan.feasible
    assert len(system.chain.stages) == len(system.plan.stages)
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    D = rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32)
    res = _chain_run(system, {"u": u, "D": D}, {"S": S})
    (vq,) = [q for q in res.outputs if q.endswith(".v")]
    got = res.outputs[vq]

    hand = operators.build_inverse_helmholtz(p)
    want = np.asarray(hand.batched_fn({"S": S, "D": D, "u": u})["v"])
    assert got.dtype == want.dtype == np.float32
    assert np.array_equal(got, want)

    oracle = reference.inverse_helmholtz_batch(
        S.astype(np.float64), D.astype(np.float64), u.astype(np.float64)
    )
    np.testing.assert_allclose(got, oracle, rtol=3e-4, atol=3e-4)


def test_flow_pipeline_auto_stages_subsume_hand_cuts(rng):
    """The fully automatic (schedule-derived) pipeline and the named
    hand-granularity cuts produce bitwise-identical outputs."""
    p, E, n_b = 5, 16, 2
    n = E * n_b
    src = operators.CFD_PIPELINE_SRC.format(p=p)
    auto = flow.compile(
        src, target=channels.CPU_HOST, batch_elements=E, n_eq=n
    )
    assert len(auto.chain.stages) > 3  # finer than the hand cuts
    u = rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32)
    D = rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32)
    shared = {
        name: rng.uniform(-1, 1, (p, p)).astype(np.float32)
        for name in ("A", "Dx", "Dy", "Dz", "S")
    }
    got = _chain_run(auto, {"u": u, "D": D}, shared)

    hand = operators.build_cfd_chain(p)
    plan = mchain.plan_chain(
        hand, target=channels.CPU_HOST, batch_elements=E, n_eq=n
    )
    want = simulation.run_chain(
        hand, plan,
        inputs={"interp.u": u, "helmholtz.D": D},
        shared=shared, collect_outputs=True,
    )
    for out_var in ("gy", "gz", "v"):
        (gq,) = [q for q in got.outputs if q.endswith("." + out_var)]
        (wq,) = [q for q in want.outputs if q.endswith("." + out_var)]
        assert np.array_equal(got.outputs[gq], want.outputs[wq]), out_var


def test_flow_named_cuts_match_hand_structure():
    """The named-stage pipeline reproduces the paper's operator
    granularity, with both bound streams HBM-resident and the Pallas
    Helmholtz stage dispatched by structural match."""
    system = operators.compile_cfd_pipeline(
        5, backends=("xla", "xla", "pallas"), target=channels.ALVEO_U280
    )
    assert system.stage_names == ("interp", "grad", "helmholtz")
    assert system.backends == ("xla", "xla", "pallas")
    resident = {
        s.name: s.klass for s in system.streams
        if s.klass == liveness.STREAM_RESIDENT
    }
    assert sorted(resident) == ["gx", "w"]
    rep = system.report()
    assert "repro.flow system" in rep
    assert "ChainPlan interp->grad->helmholtz" in rep


def test_flow_pallas_covers_interp_and_grad_stages():
    """The tiled GEMM-chain kernel class covers the interpolation and
    gradient stages, so 'pallas' no longer falls back to xla there."""
    system = flow.compile(
        operators.CFD_PIPELINE_SRC.format(p=5),
        stages=operators.CFD_PIPELINE_STAGES,
        backends=("pallas", "pallas", "pallas"),
        target=channels.ALVEO_U280,
    )
    assert system.backends == ("pallas", "pallas", "pallas")


def test_flow_pallas_fallback_when_no_kernel_matches():
    """A 'pallas' stage with no matching hand-tiled kernel is compiled
    with xla under a warning that names it -- never silently.  An
    element-tensor x element-tensor product with a contraction is outside
    every kernel class (the GEMM chain needs a shared (p,p) matrix)."""
    src = (
        "var input elem a : [4 4]\n"
        "var input elem b : [4 4]\n"
        "var output elem y : [4 4]\n"
        "y = a # b . [[1 2]]\n"
    )
    with pytest.warns(RuntimeWarning, match="no Pallas kernel matches"):
        system = flow.compile(
            src, backend="pallas", target=channels.CPU_HOST,
            batch_elements=4, n_eq=8,
        )
    assert system.backends == ("xla",)


def test_flow_output_consumed_downstream_reaches_host(rng):
    """A program output that later stages also consume is classified
    'both': exported once for the host and once (under a _res alias)
    for the resident consumer -- the host still receives it."""
    src = (
        "var input M : [3 3]\n"
        "var input elem x : [3 3]\n"
        "var output elem y : [3 3]\n"
        "var output elem z : [3 3]\n"
        "y = M # x . [[1 2]]\n"
        "z = y * x\n"
    )
    system = flow.compile(
        src, target=channels.CPU_HOST, batch_elements=4, n_eq=8
    )
    classes = {s.name: s.klass for s in system.streams}
    assert classes == {
        "y": liveness.STREAM_BOTH, "z": liveness.STREAM_HOST,
    }
    M = rng.uniform(-1, 1, (3, 3)).astype(np.float32)
    x = rng.uniform(-1, 1, (8, 3, 3)).astype(np.float32)
    res = _chain_run(system, {"x": x}, {"M": M})
    assert sorted(q.split(".")[1] for q in res.outputs) == ["y", "z"]
    want_y = np.einsum("ab,ebc->eac", M, x).astype(np.float32)
    (yq,) = [q for q in res.outputs if q.endswith(".y")]
    (zq,) = [q for q in res.outputs if q.endswith(".z")]
    np.testing.assert_allclose(res.outputs[yq], want_y, atol=1e-6)
    np.testing.assert_allclose(
        res.outputs[zq], want_y * x, atol=1e-6
    )


def test_flow_shared_precompute_consumed_by_two_stages(rng):
    """Regression (PR-4 review gap a): a shared precomputed operand
    (element-free q = M * M) consumed by two auto-derived stages used to
    make flow.compile reject the program ('does not depend on any
    element input'); the partitioner now duplicates the element-free
    nodes into every consumer stage."""
    src = (
        "var input M : [4 4]\n"
        "var input elem x : [4 4]\n"
        "var input elem y : [4 4]\n"
        "var output elem u : [4 4]\n"
        "var output elem v : [4 4]\n"
        "var q : [4 4]\n"
        "q = M * M\n"
        "u = q # x . [[1 2]]\n"
        "v = q * y\n"
    )
    system = flow.compile(
        src, target=channels.CPU_HOST, batch_elements=4, n_eq=8
    )
    assert len(system.chain.stages) == 2
    # both stages recompute q from the shared M; nothing element-free
    # crosses a stage boundary
    for s in system.chain.stages:
        assert "M" in s.program.inputs
    assert all(s.klass != liveness.STREAM_RESIDENT or s.name != "q"
               for s in system.streams)
    M = rng.uniform(-1, 1, (4, 4)).astype(np.float32)
    x = rng.uniform(-1, 1, (8, 4, 4)).astype(np.float32)
    y = rng.uniform(-1, 1, (8, 4, 4)).astype(np.float32)
    res = _chain_run(system, {"x": x, "y": y}, {"M": M})
    q = M * M
    (uq,) = [k for k in res.outputs if k.endswith(".u")]
    (vq,) = [k for k in res.outputs if k.endswith(".v")]
    np.testing.assert_allclose(
        res.outputs[uq], np.einsum("ab,ebc->eac", q, x), atol=1e-5
    )
    np.testing.assert_allclose(res.outputs[vq], q[None] * y, atol=1e-5)


def test_flow_rejects_degenerate_programs():
    with pytest.raises(dsl.ParseError, match="empty program"):
        flow.compile("// comment only\n")
    with pytest.raises(flow.FlowError, match="no outputs"):
        flow.compile("var input elem x : [2 2]")
    with pytest.raises(flow.FlowError, match="element"):
        flow.compile(
            "var input a : [2 2]\nvar output b : [2 2]\nb = a * a"
        )
    # an output computed purely from shared operands cannot stream
    with pytest.raises(flow.FlowError, match="does not depend"):
        flow.compile(
            "var input a : [2 2]\nvar input elem x : [2 2]\n"
            "var output y : [2 2]\nvar output elem z : [2 2]\n"
            "y = a * a\nz = x * x"
        )
    with pytest.raises(flow.FlowError, match="unknown target"):
        flow.compile(
            dsl.INVERSE_HELMHOLTZ_SRC.format(p=3),
            element_vars=("u", "D", "v"), target="nosuch",
        )


def test_flow_stage_cut_validation():
    src = operators.CFD_PIPELINE_SRC.format(p=3)
    with pytest.raises(flow.FlowError, match="unknown value"):
        flow.compile(src, stages=[("a", ("nosuch",))])
    with pytest.raises(flow.FlowError, match="cover output"):
        flow.compile(src, stages=[("a", ("w",))])
    with pytest.raises(flow.FlowError, match="duplicate stage"):
        flow.compile(src, stages=[
            ("a", ("w",)), ("a", ("gx", "gy", "gz", "v")),
        ])
    # cutting against the dataflow leaves a later stage empty
    with pytest.raises(flow.FlowError, match="empty"):
        flow.compile(src, stages=[
            ("a", ("gy", "gz", "v")), ("b", ("w",)),
        ])


def test_flow_dse_adopts_feasible_plan():
    system = flow.compile(
        operators.CFD_PIPELINE_SRC.format(p=5),
        stages=operators.CFD_PIPELINE_STAGES,
        target=channels.ALVEO_U280, n_eq=1 << 12,
        dse=True,
        dse_space=dse.ChainDesignSpace(
            backends=("xla", "staged"), batch_divisors=(1, 2),
            prefetch_depths=(0, 1), max_backend_combos=4,
        ),
    )
    assert system.candidates
    best = next(c for c in system.candidates if c.plan.feasible)
    assert system.plan == best.plan
    # the executable chain was rebuilt to match the winning backends
    assert tuple(s.backend for s in system.chain.stages) == tuple(
        sp.backend for sp in system.plan.stages
    )


def test_flow_dse_recompiles_pallas_block_on_e_change(monkeypatch):
    """Regression (PR-4 review gap b): a DSE winner with the *same*
    backends+policy but a different E/block used to skip the recompile,
    leaving the Pallas kernel's baked block out of sync with the plan's
    block_elements.  The winner's block must reach the kernel."""
    from repro.kernels.helmholtz import ops as hops

    seen, limits = [], set()
    real = hops.make_pallas_impl

    def spy(impl="auto", block_elements=hops.DEFAULT_BLOCK_ELEMENTS,
            vmem_limit_bytes=None):
        seen.append(block_elements)
        limits.add(vmem_limit_bytes)
        return real(impl=impl, block_elements=block_elements,
                    vmem_limit_bytes=vmem_limit_bytes)

    monkeypatch.setattr(
        "repro.flow.patterns.helmholtz_ops.make_pallas_impl", spy
    )
    system = flow.compile(
        dsl.INVERSE_HELMHOLTZ_SRC.format(p=5),
        element_vars=("u", "D", "v"), backend="pallas", max_stages=1,
        target=channels.ALVEO_U280, n_eq=1 << 12, dse=True,
        dse_space=dse.ChainDesignSpace(
            backends=("pallas",), batch_divisors=(2,),
            prefetch_depths=(1,), max_backend_combos=1,
        ),
    )
    assert system.backends == ("pallas",)
    blk = system.plan.stages[0].block_elements
    assert blk > 0
    # first call: the pre-DSE compile at the kernel default; second: the
    # adoption recompile threading the winning plan's VMEM block
    assert len(seen) == 2
    assert seen[0] == hops.DEFAULT_BLOCK_ELEMENTS
    assert seen[-1] == blk
    assert system.plan.batch_elements % blk == 0
    assert limits == {layout.vmem_budget_bytes(channels.ALVEO_U280)}


@pytest.mark.parametrize("target", ["alveo-u280", "tpu-v5e", "cpu-host"])
def test_pallas_stage_kernel_gets_the_target_vmem_budget(target, monkeypatch):
    """Every Pallas kernel the flow builds is compiled with the VMEM
    budget of the target its block was sized against -- never a
    constant of one chip."""
    from repro.kernels.helmholtz import ops as hops

    limits = []
    real = hops.make_pallas_impl

    def spy(impl="auto", block_elements=hops.DEFAULT_BLOCK_ELEMENTS,
            vmem_limit_bytes=None):
        limits.append(vmem_limit_bytes)
        return real(impl=impl, block_elements=block_elements,
                    vmem_limit_bytes=vmem_limit_bytes)

    monkeypatch.setattr(
        "repro.flow.patterns.helmholtz_ops.make_pallas_impl", spy
    )
    system = flow.compile(
        dsl.INVERSE_HELMHOLTZ_SRC.format(p=5),
        element_vars=("u", "D", "v"), backend="pallas", max_stages=1,
        target=target, n_eq=1 << 12,
    )
    assert system.backends == ("pallas",)
    budget = layout.vmem_budget_bytes(system.target)
    assert limits and set(limits) == {budget}
    assert system.plan.stages[0].block_working_set_bytes <= budget


def test_flow_tune_blocks_measures_and_records(tmp_path):
    """flow.compile(tune_blocks=True) times the candidate VMEM blocks of
    each Pallas stage, adopts a winner consistent with the plan, and
    deposits the measured sample in the profile store keyed by the plan
    signature."""
    from repro.trace.profile import ProfileStore

    prof = str(tmp_path / "prof.json")
    system = flow.compile(
        dsl.INVERSE_HELMHOLTZ_SRC.format(p=5),
        element_vars=("u", "D", "v"), backend="pallas", max_stages=1,
        target=channels.CPU_HOST, batch_elements=8, n_eq=16,
        tune_blocks=True, profile=prof,
    )
    assert system.backends == ("pallas",)
    blk = system.plan.stages[0].block_elements
    assert blk in (1, 2, 4, 8)
    assert system.plan.batch_elements % blk == 0
    store = ProfileStore(path=prof)
    got = store.samples(channels.CPU_HOST.name, system.plan.signature)
    tuned = [s for s in got if s.get("scope") == "tune"]
    assert tuned and tuned[0]["block_elements"] == blk
    assert tuned[0]["measured_s"] > 0


def test_flow_dse_replans_when_winner_backend_unrealizable():
    """A winning backend combo that no kernel can realize (pallas on an
    element-by-element contraction, outside every kernel class) is
    re-planned at the winner's design point with the backends that
    actually compiled -- plan and executable always agree, so run_chain
    never warns about a mismatch."""
    src = (
        "var input elem a : [4 4]\n"
        "var input elem b : [4 4]\n"
        "var input M : [4 4]\n"
        "var output elem z : [4 4]\n"
        "var y : [4 4]\n"
        "y = a # b . [[1 2]]\n"
        "z = M # y . [[1 2]]\n"
    )
    system = flow.compile(
        src,
        stages=[("mix", ["y"]), ("proj", ["z"])],
        target=channels.ALVEO_U280, n_eq=1 << 12, dse=True,
        dse_space=dse.ChainDesignSpace(
            backends=("pallas",), batch_divisors=(1,),
            prefetch_depths=(1,), max_backend_combos=1,
        ),
    )
    planned = tuple(sp.backend for sp in system.plan.stages)
    compiled = tuple(s.backend for s in system.chain.stages)
    assert planned == compiled == system.backends
    assert planned == ("xla", "pallas")


# ---------------------------------------------------------------------------
# golden system reports (the CLI's output, checked like plan goldens)
# ---------------------------------------------------------------------------


def _check_golden(name: str, rendered: str) -> None:
    path = GOLDEN_DIR / name
    if os.environ.get("REGEN_GOLDENS"):
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_text(rendered)
        pytest.skip(f"regenerated {name}")
    assert path.exists(), (
        f"golden file {name} missing -- run with REGEN_GOLDENS=1"
    )
    assert rendered == path.read_text(), (
        f"{name} drifted from the checked-in golden.  If intentional, "
        "regenerate with REGEN_GOLDENS=1 and review the diff."
    )


@pytest.mark.parametrize("example", ["inverse_helmholtz", "cfd_pipeline"])
def test_flow_cli_report_golden(example, capsys):
    """The CLI on examples/*.cfd emits the golden-checked architecture
    report (the same invocation CI's flow smoke job diffs)."""
    rc = flow.cli.main([
        str(EXAMPLES / f"{example}.cfd"), "--target", "alveo-u280",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    _check_golden(f"flow_{example}.txt", out)


def test_flow_cli_report_golden_ceed_bp3(capsys):
    """The CLI's report of the BP3 example, whose default staging needs
    stages in dataflow order: 20 stages, the three gradients' values
    each read by three later stages."""
    rc = flow.cli.main([
        str(EXAMPLES / "ceed_bp3.cfd"), "--target", "alveo-u280",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    _check_golden("flow_ceed_bp3.txt", out)


def test_example_sources_match_library_constants():
    """The checked-in .cfd examples compute the library's source
    constants at p=11 (same structure, names, and element streams), so
    the CLI goldens and the in-library tests validate the same
    programs."""
    from repro.flow.patterns import program_signature

    pairs = [
        ((EXAMPLES / "cfd_pipeline.cfd").read_text(), (),
         operators.CFD_PIPELINE_SRC.format(p=11), ()),
        ((EXAMPLES / "inverse_helmholtz.cfd").read_text(), (),
         dsl.INVERSE_HELMHOLTZ_SRC.format(p=11), ("u", "D", "v")),
    ]
    for src_a, ev_a, src_b, ev_b in pairs:
        a = dsl.parse(src_a, element_vars=ev_a)
        b = dsl.parse(src_b, element_vars=ev_b)
        assert program_signature(a) == program_signature(b)
        assert sorted(a.inputs) == sorted(b.inputs)
        assert sorted(a.outputs) == sorted(b.outputs)
        assert set(a.element_vars) == set(b.element_vars)


def test_target_normalization_dash_underscore_identical(capsys):
    """CI passes --target alveo-u280, the Python API historically used
    alveo_u280: both spellings (any case, stray whitespace) must resolve
    to the same datasheet, in the library and through the CLI."""
    for name in ("alveo-u280", "alveo_u280", "ALVEO_U280", " Alveo-U280 "):
        assert channels.resolve_target(name) is channels.ALVEO_U280
        assert flow.build.resolve_target(name) is channels.ALVEO_U280
    assert channels.resolve_target(None) is channels.detect_target()
    assert channels.resolve_target(channels.TPU_V5E) is channels.TPU_V5E
    src = str(EXAMPLES / "inverse_helmholtz.cfd")
    outs = []
    for spelling in ("alveo-u280", "alveo_u280"):
        assert flow.cli.main([src, "--target", spelling]) == 0
        outs.append(capsys.readouterr().out)
    assert outs[0] == outs[1]


def test_target_typo_lists_known_targets():
    with pytest.raises(
        channels.UnknownTargetError, match="alveo-u280.*cpu-host.*tpu-v5e"
    ):
        channels.resolve_target("alveo-u28")
    with pytest.raises(flow.FlowError, match="known targets"):
        flow.build.resolve_target("alveo-u28")
    # UnknownTargetError is a ValueError: existing CLI/compile callers
    # that catch ValueError keep working
    assert issubclass(channels.UnknownTargetError, ValueError)
    # near misses get a did-you-mean hint; garbage does not
    with pytest.raises(
        channels.UnknownTargetError, match="did you mean 'tpu-v5e'"
    ):
        channels.resolve_target("tpu_v5x")
    try:
        channels.resolve_target("qqqqqq")
    except channels.UnknownTargetError as e:
        assert "did you mean" not in str(e)


def test_flow_cli_target_typo_exits_2_with_suggestion(capsys):
    rc = flow.cli.main([
        str(EXAMPLES / "inverse_helmholtz.cfd"), "--target", "tpu_v5x",
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "unknown target" in err and "did you mean 'tpu-v5e'" in err


def test_flow_cli_error_paths(tmp_path, capsys):
    empty = tmp_path / "empty.cfd"
    empty.write_text("// nothing here\n")
    assert flow.cli.main([str(empty)]) == 2
    assert "empty program" in capsys.readouterr().err
    assert flow.cli.main([str(tmp_path / "missing.cfd")]) == 2
    bad = tmp_path / "bad.cfd"
    bad.write_text(
        "var input elem x : [2 2]\nvar output elem y : [2 2]\ny = - x\n"
    )
    assert flow.cli.main([str(bad)]) == 2
    assert "binary operator" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# hypothesis: every derived ProgramChain validates
# ---------------------------------------------------------------------------


def _random_pipeline_source(k: int, steps) -> str:
    """A random CFDlang pipeline: a chain of matrix applications and
    Hadamard products over (k, k) element streams."""
    lines = [f"var input elem x0 : [{k} {k}]"]
    n_mats = sum(1 for s in steps if s == "mat")
    n_elem = sum(1 for s in steps if s == "had")
    for i in range(n_mats):
        lines.append(f"var input M{i} : [{k} {k}]")
    for i in range(n_elem):
        lines.append(f"var input elem e{i} : [{k} {k}]")
    for i in range(len(steps) - 1):
        lines.append(f"var y{i} : [{k} {k}]")
    lines.append(f"var output elem z : [{k} {k}]")
    prev, mi, ei = "x0", 0, 0
    for i, s in enumerate(steps):
        dst = "z" if i == len(steps) - 1 else f"y{i}"
        if s == "mat":
            lines.append(f"{dst} = M{mi} # {prev} . [[1 2]]")
            mi += 1
        else:
            lines.append(f"{dst} = {prev} * e{ei}")
            ei += 1
        prev = dst
    return "\n".join(lines) + "\n"


def _check_derived_chain_validates(k, steps, e):
    """Property body: for a random pipeline, the flow-derived
    ProgramChain constructs without dangling bindings, its plan is
    deterministic, and HBM-resident streams strictly reduce host-link
    bytes versus planning every stage standalone (equal only when
    nothing is resident)."""
    src = _random_pipeline_source(k, steps)
    t = channels.ALVEO_U280
    system = flow.compile(src, target=t, batch_elements=e)
    chain = system.chain  # ProgramChain.__init__ validates bindings
    assert system.plan == flow.compile(
        src, target=t, batch_elements=e
    ).plan
    n_resident = sum(
        1 for s in system.streams
        if s.klass in (liveness.STREAM_RESIDENT, liveness.STREAM_BOTH)
    )
    assert n_resident == len(chain.stages) - 1  # a linear pipeline
    standalone = sum(
        mchain.plan_chain(
            mchain.ProgramChain([(s.name, s.compiled)]), target=t,
            batch_elements=e,
        ).host_stream_bytes
        for s in chain.stages
    )
    if n_resident:
        assert system.plan.host_stream_bytes < standalone
    else:
        assert system.plan.host_stream_bytes == standalone


try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @given(
        k=st.integers(2, 5),
        steps=st.lists(
            st.sampled_from(["mat", "had"]), min_size=1, max_size=5
        ),
        e=st.integers(1, 512),
    )
    @settings(max_examples=40, deadline=None)
    def test_flow_derived_chains_validate(k, steps, e):
        _check_derived_chain_validates(k, steps, e)

else:  # deterministic fallback so the property still runs everywhere

    @pytest.mark.parametrize("k,steps,e", [
        (2, ("mat",), 1),
        (3, ("mat", "had"), 17),
        (4, ("had", "mat", "mat"), 509),   # prime-ish explicit E
        (5, ("mat", "had", "mat", "had", "mat"), 512),
    ])
    def test_flow_derived_chains_validate(k, steps, e):
        _check_derived_chain_validates(k, list(steps), e)
