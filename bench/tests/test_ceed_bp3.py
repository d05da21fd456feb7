"""The BP3 configuration's yardstick: its work counts, its reference
against the repository's oracle and the program, its limits against the
control, a broken timed path in its cell, and its three readers."""
import contextlib
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import check, control, faults, reference, run, work
from bench.programs import ceed_bp3
from bench.spans import Spans
from bench.traffic import Window

ROOT = Path(__file__).resolve().parents[2]
CELL = "ceed_bp3_p11.resident_qdata"
CFG = json.loads((ROOT / "bench" / "configs" / "ceed_bp3_p11.json")
                 .read_text())
V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _data(n, p, seed=7, dtype=np.float32):
    q = ceed_bp3.quadrature_points(p)
    rng = np.random.default_rng(seed)
    shared = {k: rng.uniform(-1, 1, (q, p)).astype(dtype)
              for k in ceed_bp3.SHARED}
    ins = {k: rng.uniform(-1, 1, (n,) + ((p,) * 3 if k == "u" else (q,) * 3)
                          ).astype(dtype)
           for k in ceed_bp3.ELEMENT_INPUTS}
    return shared, ins


def test_the_configuration_runs_the_example_source():
    assert CFG["cfdlang"] == (ROOT / "examples" / "ceed_bp3.cfd").read_text()


def test_counts_at_p11():
    # 6 tensor products of 104,808, the geometric factor, the final adds
    assert ceed_bp3.tensor_product_flops(11) == 104_808
    assert ceed_bp3.flops_per_element(11) == (
        6 * 104_808 + 15 * 12 ** 3 + 2 * 11 ** 3) == 657_430
    assert ceed_bp3.bytes_per_element(11) == 4 * (2 * 1331 + 6 * 1728) == (
        52_120)


def test_bound_by_hbm_on_a_v5e():
    f, b = ceed_bp3.flops_per_element(11), ceed_bp3.bytes_per_element(11)
    assert work.roofline_seconds(f, b, V5E) == b / V5E["bytes_per_s"]


def test_matches_the_repositorys_float64_oracle():
    from repro.cfd import reference as oracle

    shared, ins = _data(5, 4, dtype=np.float64)
    got = ceed_bp3.outputs(reference, shared, ins)["y"]
    want = oracle.ceed_bp3(shared["B"], shared["Bd"],
                           [ins[k] for k in ceed_bp3.ELEMENT_INPUTS[1:]],
                           ins["u"])
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_matches_the_program_at_small_p():
    from repro import flow

    p, q = 4, 5
    src = (CFG["cfdlang"].replace("[12 11]", f"[{q} {p}]")
           .replace("[12 12 12]", f"[{q} {q} {q}]")
           .replace("[11 11 11]", f"[{p} {p} {p}]"))
    system = flow.compile(src, target="tpu-v5e", n_eq=256, profile=None,
                          batch_elements=128, **CFG["compile"])
    chain = system.chain
    shared, ins = _data(256, p, seed=1)
    inputs = {f"{s.name}.{n}": ins[n]
              for i, s in enumerate(chain.stages)
              for n, _ in chain.host_element_inputs(i)}
    res = system.run(inputs=inputs, shared=shared, collect_outputs=True)
    (y,) = res.outputs.values()
    want = check.reference_outputs(ceed_bp3, shared, ins)
    assert check.rel_err(y, want["y"]) < 1e-6


def test_the_control_fails_every_limit():
    shared, ins = _data(64, 11)
    outs = check.reference_outputs(ceed_bp3, shared, ins, ops=control)
    checks = check.compare(ceed_bp3, shared, ins, outs, CFG["limits"])
    for name, c in checks.items():
        assert c["value"] > 3 * c["limit"], (name, c)


def test_float32_with_full_products_passes_every_limit():
    shared, ins = _data(64, 11)
    outs = {q: v.astype(np.float32) for q, v in check.reference_outputs(
        ceed_bp3, shared, ins).items()}
    assert check.passed(check.compare(ceed_bp3, shared, ins, outs,
                                      CFG["limits"]))


def small_cell():
    """The cell at p = 4, q = 5 and 2,048 elements, for the CPU."""
    cell = run.load_cell(CELL)
    cfg = dict(cell.cfg, p=4, n_eq=2048)
    cfg["cfdlang"] = (cfg["cfdlang"].replace("[12 11]", "[5 4]")
                      .replace("[12 12 12]", "[5 5 5]")
                      .replace("[11 11 11]", "[4 4 4]"))
    cfg["compile"] = dict(cfg["compile"], batch_elements=256)
    cell.cfg = cfg
    return cell


@pytest.mark.parametrize("fault", [None] + sorted(faults.FAULTS))
def test_a_broken_timed_path_is_not_correct(fault):
    plant = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with plant:
        res = run.run_cell(small_cell(), 2 ** 33 + 3, 0.3, False,
                           time.perf_counter(), target="tpu-v5e")
    assert res["correct"] is (fault is None), res["checks"]
    if fault and fault.startswith("device_sum"):
        # the outputs brought back are sound: only the window's own
        # answers, its checksums, show the fault
        assert res["checks"]["y_err"]["value"] <= (
            res["checks"]["y_err"]["limit"])


def test_the_kind_makes_each_input_at_its_streams_shape():
    from bench import traffic

    cell = small_cell()
    system = run.compile_system(cell, "tpu-v5e")
    kind = traffic.make(system, ceed_bp3, cell.cfg, cell.mix, 5)
    kind.make_inputs()
    shapes = {q: a.shape for q, a in kind.field.items()}
    assert shapes == {"s0.u": (2048, 4, 4, 4),
                      **{f"s0.{g}": (2048, 5, 5, 5)
                         for g in ceed_bp3.ELEMENT_INPUTS[1:]}}


# -- the readers ------------------------------------------------------------

def record(counter=None, busy_by_chip=None):
    spans = Spans(mirror=False)
    if counter:
        spans.bump("resident_in_bytes", counter)
    return run.Record(
        window=Window(wall_s=10.0, calls=2, batches=24, elements=200_000),
        spans=spans,
        trace={"window_s": 10.0, "busy_s": 9.0,
               "busy_s_by_chip": busy_by_chip or {0: 9.0}},
        chips=1, p=11, plan_s_per_batch=0.02, flops_per_element=0,
        bytes_per_element=0, peaks=V5E)


def test_device_idle_pct_bp3():
    assert run.load_reader("device_idle_pct.bp3")(record()) == (
        pytest.approx(10.0))


def test_bp3_roofline():
    # 200,000 elements x 52,120 B at 819 GB/s over 9 s busy
    assert run.load_reader("bp3_roofline")(record()) == pytest.approx(
        100 * 200_000 * 52_120 / 819e9 / 9.0)
    assert run.load_reader("bp3_roofline")(record(busy_by_chip={0: 0.0})) is (
        None)


def test_resident_gbps_bp3():
    read = run.load_reader("resident_gbps.bp3")
    assert read(record({"s0.u": 3e9, "s0.Grr": 6e9})) == pytest.approx(1.0)
    # a program without the counter, as before it existed, reads nothing
    assert read(record()) is None
    assert read(record({"s0.u": 3e9}, busy_by_chip={0: 0.0})) is None
