"""Operator scheduling (dataflow groups) + Mnemosyne-style liveness."""
import pathlib

import numpy as np
import pytest

from repro.core import dsl, ir, liveness, rewrite, schedule


def _helmholtz(p=7):
    return rewrite.optimize(dsl.inverse_helmholtz_program(p))


def test_default_schedule_is_seven_stages():
    """The paper's 7-loop-nest structure: most aggressive partition keeps
    7 singleton groups (3 GEMM + Hadamard + 3 GEMM)."""
    sch = schedule.schedule(_helmholtz(), bytes_per_scalar=8)
    assert len(sch.groups) == 7
    assert all(len(g.nodes) == 1 for g in sch.groups)


@pytest.mark.parametrize("target", [1, 2, 3])
def test_max_groups_collapse(target):
    """The paper's Dataflow 1/2/3-compute variants via max_groups."""
    sch = schedule.schedule(
        _helmholtz(), bytes_per_scalar=8, max_groups=target
    )
    assert len(sch.groups) <= max(target, 1) + 1


def test_groups_topologically_ordered():
    sch = schedule.schedule(_helmholtz(), bytes_per_scalar=8)
    seen = set()
    for g in sch.groups:
        for n in g.nodes:
            for op in n.operands():
                if not isinstance(op, ir.Input):
                    assert op.uid in seen or any(
                        op.uid == m.uid for m in g.nodes
                    )
            seen.add(n.uid)


def test_critical_flops_bounds_throughput():
    sch = schedule.schedule(_helmholtz(11), bytes_per_scalar=8)
    assert sch.critical_flops == max(g.flops for g in sch.groups)
    # paper: each contraction stage costs 2p^4
    assert sch.critical_flops == 2 * 11 ** 4


def test_working_set_respects_budget():
    budget = 10 ** 6
    sch = schedule.schedule(
        _helmholtz(11), vmem_budget=budget, bytes_per_scalar=8
    )
    for g in sch.groups:
        assert g.working_set(8) <= budget


def test_liveness_sharing_on_collapsed_group():
    """Collapsed single group: the t/r intermediates have disjoint
    lifetimes with later stages -> sharing saves memory (paper
    'Mem Sharing' row: only applies to the 1-compute variant)."""
    sch1 = schedule.schedule(
        _helmholtz(11), bytes_per_scalar=8, max_groups=1
    )
    plans = liveness.plan_program(sch1.groups, 8)
    total_savings = sum(p.naive_bytes - p.shared_bytes for p in plans.values())
    assert total_savings > 0

    # singleton groups: no internal temporaries -> nothing to share
    # (matches the paper: sharing "cannot be applied" to 7-compute)
    sch7 = schedule.schedule(_helmholtz(11), bytes_per_scalar=8)
    plans7 = liveness.plan_program(sch7.groups, 8)
    assert all(p.naive_bytes == 0 for p in plans7.values())


def test_stream_bytes_accounting():
    sch = schedule.schedule(_helmholtz(7), bytes_per_scalar=8)
    for g in sch.groups[:-1]:
        assert len(g.out_streams) >= 1
    # last group streams the program output
    assert sch.groups[-1].out_streams[0].shape == (7, 7, 7)


def test_stream_bytes_default_follows_policy_width():
    """Regression: byte-count methods default to the scalar width the
    schedule was built for, instead of a silent 4-byte assumption that
    disagreed with low-precision policies."""
    prog = _helmholtz(7)
    for bps in (2, 4, 8):
        sch = schedule.schedule(prog, bytes_per_scalar=bps)
        assert sch.bytes_per_scalar == bps
        assert sch.stream_bytes() == sch.stream_bytes(bps)
        assert sch.stream_io_bytes() == sch.stream_io_bytes(bps)
        for g in sch.groups:
            assert g.bytes_per_scalar == bps
            assert g.in_stream_bytes() == g.in_stream_bytes(bps)
            assert g.out_stream_bytes() == g.out_stream_bytes(bps)
            assert g.working_set() == g.working_set(bps)
            # explicit widths still override the default
            assert g.out_stream_bytes(1) * bps == g.out_stream_bytes(bps)
    bf16 = schedule.schedule(prog, bytes_per_scalar=2)
    f32 = schedule.schedule(prog, bytes_per_scalar=4)
    assert all(
        bf16.stream_bytes()[k] * 2 == f32.stream_bytes()[k]
        for k in bf16.stream_bytes()
    )
    assert bf16.summary() != f32.summary()


def test_stage_partition_duplicates_element_free_group_into_all_consumers():
    """Regression (PR-4 review gap a): an element-free group consumed by
    two element-dependent stages is duplicated into *both*, so no stage
    reads an element-free cross-stage stream."""
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
    prog = rewrite.optimize(dsl.parse(src))
    sch = schedule.schedule(prog, bytes_per_scalar=4)
    parts = schedule.stage_partition(sch)
    elem_dep = prog.element_dependent_uids()
    q_uid = prog.temps["q"].uid
    assert q_uid not in elem_dep
    holders = [
        i for i, nodes in enumerate(parts)
        if any(n.uid == q_uid for n in nodes)
    ]
    assert len(holders) == 2  # one copy per consumer stage
    # every stage still streams elements, and no stage's boundary input
    # is an element-free value produced by another stage
    classes = liveness.classify_boundary_streams(prog, parts)
    assert q_uid not in classes
    for nodes in parts:
        assert any(n.uid in elem_dep for n in nodes)


# ---------------------------------------------------------------------------
# branching programs: the group graph stays acyclic, stages come in order
# ---------------------------------------------------------------------------

EXAMPLES = pathlib.Path(__file__).parent.parent / "examples"


def _bp3_source(n=4, q=5):
    src = (EXAMPLES / "ceed_bp3.cfd").read_text()
    return (src.replace("[12 11]", f"[{q} {n}]")
            .replace("[12 12 12]", f"[{q} {q} {q}]")
            .replace("[11 11 11]", f"[{n} {n} {n}]"))


def _random_branching_program(seed, n=3):
    """A CFDlang program whose values fan out to, and fan in from, random
    earlier values -- contractions, products and sums of two or three
    terms -- with its outputs computed in float64 numpy beside it."""
    rng = np.random.default_rng(seed)
    mats = {"S": rng.uniform(-1, 1, (n, n)), "T": rng.uniform(-1, 1, (n, n))}
    decl = [f"var input  {m} : [{n} {n}]" for m in mats]
    decl += [f"var input elem {v} : [{n} {n} {n}]" for v in "ab"]
    decl += [f"var output elem {v} : [{n} {n} {n}]" for v in "yz"]
    vals, stmts, ops = ["a", "b"], [], []
    for i in range(int(rng.integers(6, 11))):
        picked = [vals[j] for j in rng.choice(len(vals), 3)]
        kind = int(rng.integers(3))
        if kind == 0:
            m = "ST"[rng.integers(2)]
            rhs = f"{m} # {m} # {m} # {picked[0]} . [[1 6][3 7][5 8]]"
            ops.append(("contract", m, picked[:1]))
        elif kind == 1:
            rhs = " * ".join(picked[:2])
            ops.append(("mul", None, picked[:2]))
        else:
            terms = picked[:int(rng.integers(2, 4))]
            rhs = " + ".join(terms)
            ops.append(("add", None, terms))
        decl.append(f"var x{i} : [{n} {n} {n}]")
        stmts.append(f"x{i} = {rhs}")
        vals.append(f"x{i}")
    last, mid = vals[-3:], vals[len(vals) // 2]
    stmts.append(f"y = {' + '.join(last)}")
    stmts.append(f"z = S # S # S # {mid} . [[0 6][2 7][4 8]]")

    def outputs(a, b):
        env = {"a": a, "b": b}
        for i, (kind, m, args) in enumerate(ops):
            xs = [env[v] for v in args]
            if kind == "contract":
                M = mats[m]
                env[f"x{i}"] = np.einsum("il,jm,kn,elmn->eijk", M, M, M,
                                         xs[0])
            elif kind == "mul":
                env[f"x{i}"] = xs[0] * xs[1]
            else:
                env[f"x{i}"] = sum(xs)
        S = mats["S"]
        return {"y": sum(env[v] for v in last),
                "z": np.einsum("li,mj,nk,elmn->eijk", S, S, S, env[mid])}

    return "\n".join(decl + stmts) + "\n", mats, outputs


def _assert_stages_in_dataflow_order(system):
    """Every group's operands come from itself or an earlier group, and
    every stage's bound inputs from an earlier stage."""
    seen = set()
    for g in system.schedule.groups:
        mine = {n.uid for n in g.nodes}
        for n in g.nodes:
            for op in n.operands():
                assert isinstance(op, ir.Input) or op.uid in seen | mine
        seen |= mine
    chain = system.chain
    for i in range(len(chain.stages)):
        for producer, _ in chain.resolved[i].values():
            assert producer < i


STAGINGS = [None, 1, 2, 3, 4, 5, 6, 7, 8]


@pytest.mark.parametrize("program", ["bp3"] + [f"random-{s}"
                                                for s in range(12)])
def test_branching_stage_graph_is_acyclic(program):
    from repro import flow
    from repro.memory import channels

    if program == "bp3":
        src, run_with = _bp3_source(), None
    else:
        src, mats, outputs = _random_branching_program(
            int(program.split("-")[1]))
        run_with = (mats, outputs)
    for max_stages in STAGINGS:
        system = flow.compile(src, max_stages=max_stages,
                              target=channels.CPU_HOST, batch_elements=4,
                              n_eq=8)
        _assert_stages_in_dataflow_order(system)
        if run_with is None and max_stages is not None:
            # BP3 reaches every cap; a value that feeds two sibling
            # groups keeps them apart, so a random program may not
            assert len(system.chain.stages) <= max_stages
        if run_with is None or max_stages not in (None, 2):
            continue
        mats, outputs = run_with
        rng = np.random.default_rng(7)
        ab = {v: rng.uniform(-1, 1, (8, 3, 3, 3)).astype(np.float32)
              for v in "ab"}
        chain = system.chain
        inputs = {f"{s.name}.{v}": ab[v]
                  for i, s in enumerate(chain.stages)
                  for v, _ in chain.host_element_inputs(i)}
        res = system.run(inputs=inputs, collect_outputs=True,
                         shared={m: a.astype(np.float32)
                                 for m, a in mats.items()})
        want = outputs(*(ab[v].astype(np.float64) for v in "ab"))
        for name, w in want.items():
            (got,) = [a for q, a in res.outputs.items()
                      if q.endswith("." + name)]
            assert np.max(np.abs(got - w)) <= 1e-5 * np.max(np.abs(w))


#: the stage split the paper's programs have always had: each stage's
#: host or bound inputs and its outputs
PAPER_SPLITS = {
    ("cfd_pipeline", None): [
        (["u"], ["t0"]), (["s0.t0"], ["t1"]), (["s1.t1"], ["w"]),
        (["s2.w"], ["gy"]), (["s2.w"], ["gz"]), (["s2.w"], ["gx"]),
        (["s5.gx"], ["t2"]), (["s6.t2"], ["t3"]), (["s7.t3"], ["t"]),
        (["D", "s8.t"], ["r"]), (["s9.r"], ["t4"]), (["s10.t4"], ["t5"]),
        (["s11.t5"], ["v"]),
    ],
    ("inverse_helmholtz", None): [
        (["u"], ["t0"]), (["s0.t0"], ["t1"]), (["s1.t1"], ["t"]),
        (["D", "s2.t"], ["r"]), (["s3.r"], ["t2"]), (["s4.t2"], ["t3"]),
        (["s5.t3"], ["v"]),
    ],
    ("inverse_helmholtz", 2): [(["D", "u"], ["r"]), (["s0.r"], ["v"])],
    ("inverse_helmholtz", 1): [(["D", "u"], ["v"])],
}


@pytest.mark.parametrize("example,max_stages", sorted(
    PAPER_SPLITS, key=str), ids=lambda x: str(x))
def test_paper_programs_keep_their_stage_split(example, max_stages):
    from repro import flow

    system = flow.compile((EXAMPLES / f"{example}.cfd").read_text(),
                          max_stages=max_stages, target="tpu-v5e",
                          n_eq=1 << 16)
    chain = system.chain
    got = [
        (sorted(v for v, _ in chain.host_element_inputs(i))
         + sorted(f"{chain.stages[p].name}.{o}"
                  for p, o in chain.resolved[i].values()),
         sorted(s.program.outputs))
        for i, s in enumerate(chain.stages)
    ]
    assert got == PAPER_SPLITS[(example, max_stages)]


def test_topological_order_keeps_an_order_that_already_holds():
    assert schedule.topological_order(4, [(0, 1), (1, 3), (2, 3)]) == [
        0, 1, 2, 3]
    assert schedule.topological_order(3, [(2, 0), (2, 1)]) == [2, 0, 1]
    with pytest.raises(ValueError):
        schedule.topological_order(2, [(0, 1), (1, 0)])
