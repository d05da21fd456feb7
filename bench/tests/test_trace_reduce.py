"""``trace_reduce`` on a hand-built trace whose numbers are known, and on
a trace recorded on a TPU v5e (``data/``)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"
CHIP_TRACE = DATA / "pipeline_p11.host_stream.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile(device_ops, host_spans, chips=1):
    planes = [NS(name=f"/device:TPU:{c}", lines=[
        NS(name="XLA Modules", events=[ev("jit_x", 0, 10 ** 9)]),
        NS(name="XLA Ops", events=device_ops.get(c, []))])
        for c in range(chips)]
    planes.append(NS(name="/host:CPU", lines=[
        NS(name="python3", events=host_spans
           + [ev("PjitFunction(f)", 0, 5)])]))
    return NS(planes=planes)


# window 0..1000 ns; ops at [100, 300) and [200, 400) overlap, [600, 700)
OPS = [ev("%fusion.1 = f32[8] fusion(...)", 100, 200),
       ev("%copy.2 = f32[8] copy(...)", 200, 200),
       ev("%fusion.1 = f32[8] fusion(...)", 600, 100),
       ev("%late = f32[8] copy(...)", 1500, 100)]
SPANS = [ev(trace_reduce.WINDOW, 0, 1000),
         ev("@bench.call:call", 0, 900),
         ev("@sync:sync b0", 450, 100),
         ev("@stage-host:stage b1", 750, 100)]


def test_busy_is_the_union_of_ops_inside_the_window():
    r = trace_reduce.reduce_trace(profile({0: OPS}, SPANS), chips=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)      # [100,400) + [600,700)
    assert r["busy_s_by_chip"] == {0: pytest.approx(400e-9)}


def test_device_ops_by_instruction_name_longest_first():
    r = trace_reduce.reduce_trace(profile({0: OPS}, SPANS), chips=1)
    assert r["op_seconds"] == {"%fusion.1": pytest.approx(300e-9),
                               "%copy.2": pytest.approx(200e-9)}
    assert r["device_ops"] == [["%fusion.1", pytest.approx(300e-9)],
                               ["%copy.2", pytest.approx(200e-9)]]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    r = trace_reduce.reduce_trace(profile({0: OPS}, SPANS), chips=1)
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # holes [0,100) [400,600) [700,1000): midpoints 50, 500, 850
    assert gaps == {"bench.call": pytest.approx(100e-9),
                    "sync": pytest.approx(200e-9),
                    "stage-host": pytest.approx(300e-9)}


def test_busy_is_averaged_over_the_cells_chips_idle_ones_included():
    r = trace_reduce.reduce_trace(profile({0: OPS}, SPANS, chips=2),
                                  chips=2)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["busy_s_by_chip"][1] == 0.0


def test_a_trace_without_the_window_or_device_work_is_refused():
    with pytest.raises(trace_reduce.TraceError, match="annotation"):
        trace_reduce.reduce_trace(profile({0: OPS}, SPANS[1:]), chips=1)
    with pytest.raises(trace_reduce.TraceError, match="no device operation"):
        trace_reduce.reduce_trace(profile({0: OPS[3:]}, SPANS), chips=1)


def test_recorded_chip_trace():
    """One call of ``pipeline_p11.host_stream`` (8 batches) on one v5e,
    recorded with ``BENCH_KEEP_TRACE`` by a ``--trace 1`` run."""
    r = trace_reduce.reduce_file(str(CHIP_TRACE), chips=1)
    assert r["window_s"] == pytest.approx(7.005581028)
    assert r["busy_s"] == pytest.approx(0.154852268)
    assert r["device_ops"][0] == ["%convolution_bitcast_fusion",
                                  pytest.approx(0.06118706)]
    assert all(" = " not in n for n, _ in r["device_ops"])
    assert [n for n, _ in r["idle_gaps"]] == ["bench.call", "run",
                                              "dispatch"]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
