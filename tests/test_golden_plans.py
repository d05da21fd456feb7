"""Golden-plan regression tests: rendered plan reports are checked in
under tests/golden/ so any cost-model, layout, padding, or report drift
shows up as a reviewable diff.  Regenerate intentionally with

    REGEN_GOLDENS=1 PYTHONPATH=src python -m pytest tests/test_golden_plans.py

All golden plans target the paper's fixed Alveo U280 datasheet, so they
are machine-independent (pure-python planning, no jax numerics).
"""
import os
import pathlib

import pytest

from conftest import helmholtz_chain
from repro.cfd import operators
from repro.memory import chain as mchain
from repro.memory import channels

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"


def _check(name: str, rendered: str) -> None:
    path = GOLDEN_DIR / name
    if os.environ.get("REGEN_GOLDENS"):
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_text(rendered + "\n")
        pytest.skip(f"regenerated {name}")
    assert path.exists(), (
        f"golden file {name} missing -- run with REGEN_GOLDENS=1"
    )
    want = path.read_text().rstrip("\n")
    got = rendered.rstrip("\n")
    assert got == want, (
        f"{name} drifted from the checked-in golden.\n"
        "If the change is intentional, regenerate with REGEN_GOLDENS=1 "
        "and review the diff.\n"
        f"--- golden ---\n{want}\n--- current ---\n{got}"
    )


def test_golden_single_op_plan():
    """A single operator is a one-stage chain."""
    plan = mchain.plan_chain(
        helmholtz_chain(7), target=channels.ALVEO_U280, policy="float32",
        prefetch_depth=1, n_eq=1 << 16,
    )
    _check("plan_helmholtz_p7_alveo.txt", plan.report())


def test_golden_staged_plan():
    plan = mchain.plan_chain(
        helmholtz_chain(7, backend="staged"), target=channels.ALVEO_U280,
        policy="float32", prefetch_depth=2, n_eq=1 << 16,
    )
    _check("plan_helmholtz_p7_staged_alveo.txt", plan.report())


def test_golden_bf16_plan():
    """Locks the policy-width threading: a bfloat16 plan's byte counts
    are half the float32 plan's."""
    plan = mchain.plan_chain(
        helmholtz_chain(7), target=channels.ALVEO_U280, policy="bfloat16",
        prefetch_depth=1, n_eq=1 << 16,
    )
    _check("plan_helmholtz_p7_bf16_alveo.txt", plan.report())


def test_golden_chain_plan():
    chain = operators.build_cfd_chain(5)
    plan = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, policy="float32",
        batch_elements=512, prefetch_depth=1, n_eq=1 << 12,
    )
    _check("chain_cfd_p5_alveo.txt", plan.report())


def test_golden_chain_mixed_backends():
    chain = operators.build_cfd_chain(5)
    plan = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, policy="float32",
        backends=("xla", "xla", "staged"), batch_elements=256,
        prefetch_depth=(1, 1, 2), n_eq=1 << 12,
    )
    _check("chain_cfd_p5_mixed_alveo.txt", plan.report())


def test_golden_chain_sharded_placement():
    """Locks the placement layer's report: per-stage CU groups over an
    explicit topology, the contention vector, and the contention-aware
    overlap pricing (stage groups wrap on a 2-device topology, so the
    middle stage time-slices with both neighbors)."""
    from repro.memory.placement import DeviceTopology

    chain = operators.build_cfd_chain(5)
    plan = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, policy="float32",
        batch_elements=256, prefetch_depth=(2, 1, 1),
        cu_count=(1, 2, 1), topology=DeviceTopology.homogeneous(2),
        n_eq=1 << 12,
    )
    _check("chain_cfd_p5_sharded_alveo.txt", plan.report())


def test_golden_chain_hetero_placement():
    """Locks the heterogeneous report: per-stage (kind, E, channels)
    lines in the placement section, per-group channel-id bases (cpu-host
    ids before the alveo block), and the re-block handoff line for the
    E- and kind-crossing 0->1 boundary."""
    from repro.memory.placement import DeviceTopology

    chain = operators.build_cfd_chain(5)
    plan = mchain.plan_chain(
        chain, target=channels.ALVEO_U280, policy="float32",
        batch_elements=256, prefetch_depth=(2, 1, 1),
        cu_count=(1, 2, 1),
        topology=DeviceTopology.parse("cpu:1,alveo:2"),
        stage_groups=(0, 1, 1), stage_batch_elements=(64, 256, 256),
        n_eq=1 << 12,
    )
    _check("chain_cfd_p5_hetero_alveo.txt", plan.report())
