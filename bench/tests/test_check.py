"""The comparison that decides ``correct``: its limits sit between the
program and the control, and a broken timed path comes out not correct.
"""
import contextlib
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import check, control, faults, run
from bench.programs import cfd_pipeline, inverse_helmholtz

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((BENCH.parent / c["file"]).read_text())
           for c in SPEC["configs"]}
PROGRAMS = {"cfd_pipeline": cfd_pipeline,
            "inverse_helmholtz": inverse_helmholtz}


def _data(program, n, p=11, seed=7):
    rng = np.random.default_rng(seed)
    shared = {k: rng.uniform(-1, 1, (p, p)).astype(np.float32)
              for k in program.SHARED}
    ins = {k: rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32)
           for k in program.ELEMENT_INPUTS}
    return shared, ins


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_the_control_fails_every_limit(config):
    cfg = CONFIGS[config]
    program = PROGRAMS[cfg["program"]]
    shared, ins = _data(program, 64)
    outs = check.reference_outputs(program, shared, ins, ops=control)
    checks = check.compare(program, shared, ins, outs, cfg["limits"])
    assert not check.passed(checks)
    for name, c in checks.items():
        assert c["value"] > 3 * c["limit"], (name, c)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_float32_with_full_products_passes_every_limit(config):
    cfg = CONFIGS[config]
    program = PROGRAMS[cfg["program"]]
    shared, ins = _data(program, 64)
    ins32 = {k: v.astype(np.float64) for k, v in ins.items()}
    outs = {q: v.astype(np.float32) for q, v in check.reference_outputs(
        program, shared, ins32).items()}
    checks = check.compare(program, shared, ins, outs, cfg["limits"])
    assert check.passed(checks), checks


def test_non_finite_outputs_read_inf():
    assert check.rel_err(np.array([1.0, np.nan]), np.ones(2)) == np.inf


# -- a whole run on the CPU at p = 5, with the timed path broken ----------

SMALL = {"p": 5, "n_eq": 2048, "batch_elements": 256}


def small_cell(name):
    cell = run.load_cell(name)
    cfg = dict(cell.cfg)
    cfg["p"], cfg["n_eq"] = SMALL["p"], SMALL["n_eq"]
    cfg["cfdlang"] = cfg["cfdlang"].replace("11", str(SMALL["p"]))
    cfg["compile"] = dict(cfg["compile"],
                          batch_elements=SMALL["batch_elements"])
    cfg["compile"].pop("cu_count", None)
    cfg["compile"].pop("devices", None)
    cell.cfg, cell.chips = cfg, 1
    return cell


CELLS = [w["name"] for w in SPEC["workloads"]]
#: faults every cell can have, and those of a kind's own path
FAULTS = ["answer_altered", "half_the_batch_left_out"]
FAULTS_OF_KIND = {"hbm_resident": ["device_sum_altered", "device_sum_half",
                                   "device_sum_drifts"]}
CASES = [(c, f) for c in CELLS
         for f in [None] + FAULTS + FAULTS_OF_KIND.get(
             run.load_cell(c).mix["kind"], [])]


@pytest.mark.parametrize("cell_name,fault", CASES)
def test_a_broken_timed_path_is_not_correct(cell_name, fault):
    plant = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with plant:
        res = run.run_cell(small_cell(cell_name), 2 ** 33 + 1, 0.3, False,
                           time.perf_counter(), target="tpu-v5e")
    assert res["correct"] is (fault is None), res["checks"]
    if fault and fault.startswith("device_sum"):
        # the outputs brought back are sound: only the window's own
        # answers, its checksums, show the fault
        assert res["checks"]["v_err"]["value"] <= (
            res["checks"]["v_err"]["limit"])
