import pytest

from bench import work
from bench.programs import cfd_pipeline, inverse_helmholtz

V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_inverse_helmholtz_counts_are_the_papers():
    assert inverse_helmholtz.flops_per_element(11) == 177_023
    assert inverse_helmholtz.flops_per_element(11) == (12 * 11 + 1) * 11 ** 3
    assert inverse_helmholtz.bytes_per_element(11) == 3 * 4 * 11 ** 3 == 15_972


def test_pipeline_counts():
    assert cfd_pipeline.flops_per_element(11) == 352_715
    assert cfd_pipeline.bytes_per_element(11) == 5 * 4 * 11 ** 3 == 26_620


@pytest.mark.parametrize("program", [cfd_pipeline, inverse_helmholtz])
def test_both_programs_are_bound_by_hbm_on_a_v5e(program):
    f, b = program.flops_per_element(11), program.bytes_per_element(11)
    assert work.roofline_seconds(f, b, V5E) == b / V5E["bytes_per_s"]
    assert f / V5E["flops_per_s"] < b / V5E["bytes_per_s"]


def test_roofline_seconds_takes_the_larger_bound():
    assert work.roofline_seconds(2e12, 1.0, {"flops_per_s": 1e12,
                                             "bytes_per_s": 1.0}) == 2.0
    assert work.roofline_seconds(1.0, 3e9, {"flops_per_s": 1e12,
                                            "bytes_per_s": 1e9}) == 3.0
