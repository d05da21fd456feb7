"""Every per-layer reader in ``metrics/`` on a record whose numbers are
known, and on a trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import run, work
from bench.spans import Span, Spans
from bench.traffic import Window

BENCH = Path(__file__).resolve().parents[1]
PEAKS = {"flops_per_s": 200e12, "bytes_per_s": 800e9}

#: one ``run`` span of 100 ms and what the driver does inside it
SPANS = [
    ("run", 0.000, 0.100),
    ("stage-host", 0.000, 0.002),
    ("slot", 0.010, 0.030), ("dispatch", 0.012, 0.028),
    ("stage-host", 0.050, 0.054),
    ("slot", 0.060, 0.070), ("dispatch", 0.061, 0.069),
    ("handoff", 0.065, 0.080),
    ("sync", 0.090, 0.095),
    ("stage-host", 0.200, 0.210),            # after the run: not inside it
]


def spans(items=SPANS):
    s = Spans(mirror=False)
    for cat, t0, t1 in items:
        s.spans.append(Span(name="x", cat=cat, t0=t0, t1=t1))
    return s


def record(**kw):
    base = dict(
        window=Window(wall_s=10.0, calls=2, batches=8, elements=8000),
        spans=spans(),
        trace={"window_s": 10.0, "busy_s": 2.5,
               "busy_s_by_chip": {0: 2.0, 1: 3.0},
               "op_seconds": {"%inverse_helmholtz.1": 0.5,
                              "%inverse_helmholtz": 0.3,
                              "%inverse_helmholtz_tiles": 1.0,
                              "%copy.1": 1.0}},
        chips=2, p=11, plan_s_per_batch=0.25, flops_per_element=1000,
        bytes_per_element=8000, peaks=PEAKS)
    base.update(kw)
    return run.Record(**base)


EXPECTED = {
    "host_stage_ms.solve": (2 + 4 + 10) / 3,
    # inside the run, 100 ms less 2 + 20 + 4 + 20 covered, over 8 batches
    "host_sync_ms.solve": 54 / 8,
    "dispatch_ms.solve": (16 + 8) / 8,
    "plan_error_pct.solve": 80.0,              # |1.25 - 0.25| / 1.25
    "device_idle_pct.solve": 75.0,
    # 8000 elements x 8000 B / 800 GB/s = 80 us over 5 s busy
    "chain_roofline": 100 * 8e-5 / 5.0,
    # 8000 elements x 15,972 B / 800 GB/s over the kernel's 0.8 s
    "helmholtz_roofline": 100 * 8000 * 15_972 / 800e9 / 0.8,
    # 8e6 flops over 10 s x 2 chips x 200 TF/s
    "step_mfu": 100 * 8e6 / (10.0 * 2 * 200e12),
}


def test_every_reader_file_has_an_expected_value():
    assert {p.name[:-3] for p in (BENCH / "metrics").glob("*.py")} == set(
        EXPECTED)


def test_every_per_layer_metric_has_a_reader():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader(name):
    assert run.load_reader(name)(record()) == pytest.approx(EXPECTED[name])


def test_the_kernel_roofline_counts_the_kernels_own_work():
    assert work.inverse_helmholtz_flops(11) / PEAKS["flops_per_s"] < (
        work.stream_bytes(11, 3) / PEAKS["bytes_per_s"])


@pytest.mark.parametrize("name", ["host_stage_ms.solve", "host_sync_ms.solve",
                                  "dispatch_ms.solve"])
def test_span_readers_find_nothing_without_spans(name):
    assert run.load_reader(name)(record(spans=spans([]))) is None


def test_the_kernel_roofline_is_silent_where_the_kernel_did_not_run():
    rec = record(trace=dict(record().trace, op_seconds={"%copy.1": 1.0}))
    assert run.load_reader("helmholtz_roofline")(rec) is None


def test_readers_on_the_recorded_chip_trace():
    """The readers give back, from the committed v5e trace alone (spans
    rebuilt from its mirrored annotations), what that run reported; the
    retire time they read lies between the device's idle time under the
    ``run`` span and that span's whole length."""
    from jax.profiler import ProfileData

    from bench import trace_reduce

    path = BENCH / "tests" / "data" / "pipeline_p11.host_stream.xplane.pb"
    data = ProfileData.from_file(str(path))
    rebuilt = Spans(mirror=False)
    for plane in data.planes:
        for line in plane.lines if plane.name.startswith("/host:") else ():
            for e in line.events:
                if (e.name.startswith(trace_reduce.SPAN_PREFIX)
                        and e.name != trace_reduce.WINDOW):
                    rebuilt.spans.append(Span(
                        name=e.name, cat=e.name[1:].split(":", 1)[0],
                        t0=e.start_ns * 1e-9,
                        t1=(e.start_ns + e.duration_ns) * 1e-9))
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    summary = trace_reduce.reduce_trace(data, chips=1)
    rec = record(
        window=Window(wall_s=7.006, calls=1, batches=8, elements=278_528),
        spans=rebuilt, trace=summary, chips=1, p=11,
        plan_s_per_batch=0.043163, flops_per_element=352_715,
        bytes_per_element=26_620, peaks=peaks)
    reported = {  # the --trace 1 run that recorded the trace
        "host_stage_ms.solve": 6.061186, "dispatch_ms.solve": 41.296499,
        "plan_error_pct.solve": 95.071043,
        "device_idle_pct.solve": 97.789587, "chain_roofline": 5.846224,
        "step_mfu": 0.0071184,
        # read from the trace after the run
        "host_sync_ms.solve": 153.5597}
    for name, want in reported.items():
        assert run.load_reader(name)(rec) == pytest.approx(
            want, rel=0.02, abs=0.01), name
    assert run.load_reader("helmholtz_roofline")(rec) is None
    retire_s = run.load_reader("host_sync_ms.solve")(rec) * 8 / 1e3
    idle_in_run = dict(summary["idle_gaps"])["run"]
    (run_span,) = rebuilt.closed("run")
    assert idle_in_run < retire_s < run_span.duration
