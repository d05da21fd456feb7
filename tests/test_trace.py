"""repro.trace: span/counter tracing, Chrome export, schema invariants,
pred-vs-measured attribution, and the persistent profile store.

The schema tests pin the executor's tracing contract: spans nest and
never overlap within a track, per-channel byte counters sum *exactly*
to the plan's host_stream_bytes, disabling the tracer changes nothing
bitwise, and the Chrome JSON round-trips ``json.loads``.  The golden
test locks the deterministic (non-timing) fields of the ``measured:``
report section.
"""
import concurrent.futures
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro import trace
from repro.cfd import operators, simulation
from repro.cfd.simulation import run_chain
from repro.memory import chain as mchain
from repro.memory import channels, dse
from repro.runtime.monitor import StepMonitor
from repro.trace.attribution import (
    CAT_CHECKSUM, CAT_CONCAT, CAT_DISPATCH, CAT_PREPARE, CAT_RETIRE,
    CAT_SLICE, CAT_SLOT, CAT_SYNC, CAT_TRANSFER, CAT_WAIT,
    COUNTER_CHANNEL_BYTES, COUNTER_HOST_OUT_BYTES,
    COUNTER_HOST_OUT_HIDDEN_BYTES, COUNTER_HOST_OUT_REUSED_BYTES,
    COUNTER_OCCUPANCY,
    COUNTER_RESIDENT_IN_BYTES, COUNTER_RESIDENT_IN_PLACE, host_channel_bytes,
)

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"

P, E, N_B = 5, 128, 3


def _golden_check(name: str, rendered: str) -> None:
    path = GOLDEN_DIR / name
    if os.environ.get("REGEN_GOLDENS"):
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_text(rendered + "\n")
        pytest.skip(f"regenerated {name}")
    assert path.exists(), (
        f"golden file {name} missing -- run with REGEN_GOLDENS=1"
    )
    want = path.read_text().rstrip("\n")
    assert rendered.rstrip("\n") == want, (
        f"{name} drifted from the checked-in golden.\n"
        "If the change is intentional, regenerate with REGEN_GOLDENS=1 "
        "and review the diff.\n"
        f"--- golden ---\n{want}\n--- current ---\n{rendered}"
    )


@pytest.fixture(scope="module")
def cfd_chain():
    return operators.build_cfd_chain(P)


def _chain_data(chain, n, rng):
    inputs = {
        "interp.u": rng.uniform(-1, 1, (n, P, P, P)).astype(np.float32),
        "helmholtz.D": rng.uniform(-1, 1, (n, P, P, P)).astype(np.float32),
    }
    shared = {
        name: rng.uniform(-1, 1, node.shape).astype(np.float32)
        for name, node in sorted(chain.shared_operands().items())
    }
    return inputs, shared


@pytest.fixture(scope="module")
def traced_run(cfd_chain):
    """One stage-pipelined 3-batch run with tracing on, reused by the
    schema/attribution/profile tests below."""
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=E,
        prefetch_depth=1, n_eq=E * N_B,
    )
    rng = np.random.default_rng(3)
    inputs, shared = _chain_data(cfd_chain, E * N_B, rng)
    tracer = trace.Tracer()
    res = run_chain(
        cfd_chain, plan, inputs=inputs, shared=shared, n_eq=E * N_B,
        max_batches=N_B, pipeline_stages=True, tracer=tracer,
    )
    return plan, tracer, res


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_tracer_spans_nest_lifo():
    tr = trace.Tracer()
    outer = tr.begin("outer", "run", 0)
    inner = tr.begin("inner", "slot", 0)
    tr.end(inner)
    tr.end(outer)
    assert not outer.open and not inner.open
    assert inner.t0 >= outer.t0 and inner.t1 <= outer.t1


def test_tracer_rejects_out_of_order_end():
    tr = trace.Tracer()
    outer = tr.begin("outer", "run", 0)
    tr.begin("inner", "slot", 0)
    with pytest.raises(trace.TraceError):
        tr.end(outer)  # inner is still open on the same track


def test_tracer_rejects_end_without_begin():
    tr = trace.Tracer()
    sp = tr.begin("a", "run", 0)
    tr.end(sp)
    with pytest.raises(trace.TraceError):
        tr.end(sp)


def test_null_tracer_is_falsy_noop():
    assert not trace.NULL
    assert not trace.NullTracer()
    with trace.NULL.span("x", "run", 0) as sp:
        assert sp is None
    trace.NULL.bump("c", {"a": 1.0})  # must not raise


def test_counter_totals_accumulate():
    tr = trace.Tracer()
    tr.bump("bytes", {"0": 10.0, "1": 5.0})
    tr.bump("bytes", {"0": 10.0})
    assert tr.totals("bytes") == {"0": 20.0, "1": 5.0}


# ---------------------------------------------------------------------------
# traced chain run: schema invariants
# ---------------------------------------------------------------------------


def test_traced_chain_schema_valid(traced_run, tmp_path):
    _, tracer, _ = traced_run
    doc = trace.to_chrome(tracer)
    assert trace.validate(doc) == []
    # Chrome JSON round-trips json.loads, via the actual file writer
    path = tmp_path / "trace.json"
    trace.write_chrome(tracer, str(path))
    loaded = json.loads(path.read_text())
    assert trace.validate(loaded) == []
    assert {e["ph"] for e in loaded["traceEvents"]} >= {"X", "C", "M"}


def test_traced_chain_no_open_spans(traced_run):
    _, tracer, _ = traced_run
    assert tracer.open_spans() == []


def test_channel_counters_sum_exactly_to_plan(traced_run):
    plan, tracer, res = traced_run
    per_ch = tracer.totals(COUNTER_CHANNEL_BYTES)
    assert per_ch, "no channel_bytes counters recorded"
    assert sum(per_ch.values()) == res.batches * plan.host_stream_bytes
    # and the per-channel split helper is exact on its own
    split = host_channel_bytes(plan.buffers)
    assert sum(split.values()) == plan.host_stream_bytes


def test_occupancy_counter_matches_plan(traced_run):
    plan, tracer, _ = traced_run
    occ = tracer.totals(COUNTER_OCCUPANCY)
    assert occ == {
        sp.name: float(sp.cu_count) for sp in plan.stages
    }


@pytest.mark.parametrize("collect_outputs", [False, True],
                         ids=["checksums", "outputs"])
def test_tracer_off_is_bitwise_identical(cfd_chain, collect_outputs):
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=64,
        prefetch_depth=1, n_eq=128,
    )
    rng = np.random.default_rng(5)
    inputs, shared = _chain_data(cfd_chain, 128, rng)
    kw = dict(inputs=inputs, shared=shared, n_eq=128, max_batches=2,
              pipeline_stages=True, collect_outputs=collect_outputs)
    plain = run_chain(cfd_chain, plan, **kw)
    traced = run_chain(cfd_chain, plan, tracer=trace.Tracer(), **kw)
    nulled = run_chain(cfd_chain, plan, tracer=trace.NULL, **kw)
    assert plain.checksums == traced.checksums == nulled.checksums
    if collect_outputs:
        for q, want in plain.outputs.items():
            for res in (traced, nulled):
                assert res.outputs[q].dtype == want.dtype
                assert res.outputs[q].tobytes() == want.tobytes(), q
    else:
        assert plain.outputs is traced.outputs is nulled.outputs is None


# ---------------------------------------------------------------------------
# run_chain's own host work: prepare, slice, retire, concat, checksum
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def collected_run(cfd_chain):
    """A traced 3-batch run that brings every output back to the host."""
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=E,
        prefetch_depth=1, n_eq=E * N_B,
    )
    rng = np.random.default_rng(4)
    inputs, shared = _chain_data(cfd_chain, E * N_B, rng)
    tracer = trace.Tracer()
    res = run_chain(
        cfd_chain, plan, inputs=inputs, shared=shared, n_eq=E * N_B,
        max_batches=N_B, pipeline_stages=True, tracer=tracer,
        collect_outputs=True,
    )
    return tracer, res


def _count(tracer, cat):
    return len(tracer.spans_by(cat=cat, track=trace.HOST_TRACK))


@pytest.mark.parametrize("cat,per_batch", [
    (CAT_RETIRE, True), (CAT_WAIT, True), (CAT_TRANSFER, True),
    (CAT_SLICE, True), (CAT_PREPARE, False), (CAT_CONCAT, False),
    (CAT_CHECKSUM, False),
])
def test_collected_run_host_spans(collected_run, cat, per_batch):
    tracer, res = collected_run
    assert _count(tracer, cat) == (res.batches if per_batch else 1)


def test_collected_run_retire_holds_wait_then_transfer(collected_run):
    tracer, _ = collected_run
    retires = tracer.spans_by(cat=CAT_RETIRE)
    for cat in (CAT_WAIT, CAT_TRANSFER):
        for sp in tracer.spans_by(cat=cat):
            (outer,) = [r for r in retires
                        if r.args["batch"] == sp.args["batch"]]
            assert outer.t0 <= sp.t0 and sp.t1 <= outer.t1
    for w, t in zip(tracer.spans_by(cat=CAT_WAIT),
                    tracer.spans_by(cat=CAT_TRANSFER)):
        assert w.args["batch"] == t.args["batch"] and w.t1 <= t.t0
    # prepare closes before the root run span, concat and checksum
    # follow it, in that order
    (prep,), (root,) = (tracer.spans_by(cat=CAT_PREPARE),
                        tracer.spans_by(cat="run"))
    (concat,), (checksum,) = (tracer.spans_by(cat=CAT_CONCAT),
                              tracer.spans_by(cat=CAT_CHECKSUM))
    assert prep.t1 <= root.t0 and root.t1 <= concat.t0
    assert concat.t1 <= checksum.t0
    # concat covers the join: the hidden-bytes sample taken as it begins
    (hidden,) = [c for c in tracer.counters
                 if c.name == COUNTER_HOST_OUT_HIDDEN_BYTES]
    assert concat.t0 <= hidden.t <= concat.t1


def test_host_out_bytes_are_the_returned_outputs(collected_run):
    tracer, res = collected_run
    assert tracer.totals(COUNTER_HOST_OUT_BYTES) == {
        q: float(v.nbytes) for q, v in res.outputs.items()
    }


def test_host_out_hidden_bytes_within_host_out_bytes(collected_run):
    tracer, res = collected_run
    out = tracer.totals(COUNTER_HOST_OUT_BYTES)
    hidden = tracer.totals(COUNTER_HOST_OUT_HIDDEN_BYTES)
    assert sorted(hidden) == sorted(out) == sorted(res.outputs)
    for q, nbytes in out.items():
        assert 0 <= hidden[q] <= nbytes, q


class _InlineExecutor:
    """Runs each copy as it is submitted."""

    def submit(self, fn, *args):
        f = concurrent.futures.Future()
        f.set_result(fn(*args))
        return f


class _GatedExecutor:
    """Holds every copy until the join opens the gate."""

    def __init__(self):
        self.gate = threading.Event()
        self.pool = concurrent.futures.ThreadPoolExecutor(2)

    def submit(self, fn, *args):
        def gated():
            assert self.gate.wait(timeout=60)
            return fn(*args)

        return self.pool.submit(gated)


@pytest.mark.parametrize("copies", ["inline", "gated"])
def test_host_out_hidden_bytes_counts_finished_copies(cfd_chain, monkeypatch,
                                                      copies):
    """Copies all done before the join count every byte as hidden;
    copies all held until the join count none."""
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=64,
        prefetch_depth=1, n_eq=256,
    )
    inputs, shared = _chain_data(cfd_chain, 256, np.random.default_rng(8))
    if copies == "inline":
        ex = _InlineExecutor()
    else:
        ex = _GatedExecutor()
        join = simulation._OutputAssembly.join

        def open_then_join(self):
            ex.gate.set()
            return join(self)

        monkeypatch.setattr(simulation._OutputAssembly, "join",
                            open_then_join)
    monkeypatch.setattr(simulation, "_copy_executor", lambda: ex)
    tracer = trace.Tracer()
    try:
        res = run_chain(cfd_chain, plan, inputs=inputs, shared=shared,
                        n_eq=256, max_batches=4, pipeline_stages=True,
                        tracer=tracer, collect_outputs=True)
    finally:
        if copies == "gated":
            ex.gate.set()
            ex.pool.shutdown(wait=True)
    out = tracer.totals(COUNTER_HOST_OUT_BYTES)
    hidden = tracer.totals(COUNTER_HOST_OUT_HIDDEN_BYTES)
    assert res.batches == 4
    want = out if copies == "inline" else {q: 0.0 for q in out}
    assert hidden == want


def test_host_out_reused_bytes_on_a_second_call(cfd_chain, monkeypatch):
    """The first call of a shape reuses nothing; a second one, after the
    first result was dropped, writes every byte it brings back into the
    first call's arrays."""
    monkeypatch.setattr(simulation, "_out_pool", [])
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=64,
        prefetch_depth=1, n_eq=256,
    )
    inputs, shared = _chain_data(cfd_chain, 256, np.random.default_rng(9))
    totals = []
    for _ in range(2):
        tracer = trace.Tracer()
        run_chain(cfd_chain, plan, inputs=inputs, shared=shared, n_eq=256,
                  max_batches=4, pipeline_stages=True, tracer=tracer,
                  collect_outputs=True)
        assert len([c for c in tracer.counters
                    if c.name == COUNTER_HOST_OUT_REUSED_BYTES]) == 1
        totals.append((tracer.totals(COUNTER_HOST_OUT_BYTES),
                       tracer.totals(COUNTER_HOST_OUT_REUSED_BYTES)))
    (_, first), (out, second) = totals
    assert not any(first.values())
    assert second == out and len(out) == 3


def test_collected_run_is_lifo_and_schema_valid(collected_run):
    tracer, _ = collected_run
    assert tracer.open_spans() == []
    assert trace.validate(trace.to_chrome(tracer)) == []


def test_reduced_run_transfers_nothing(traced_run):
    """Without ``collect_outputs`` the retire dispatches a reduction on
    the device: no wait or transfer span, no bytes counted back."""
    _, tracer, res = traced_run
    assert _count(tracer, CAT_WAIT) == _count(tracer, CAT_TRANSFER) == 0
    assert tracer.totals(COUNTER_HOST_OUT_BYTES) == {}
    assert _count(tracer, CAT_RETIRE) == _count(tracer, CAT_SLICE) == (
        res.batches)
    assert _count(tracer, CAT_CHECKSUM) == _count(tracer, CAT_PREPARE) == 1
    assert _count(tracer, CAT_CONCAT) == 0


def _resident_runs(cfd_chain, on_device, collect_outputs=False):
    """The same 3-batch chain run plain and traced, with the inputs named
    in ``on_device`` put on the device first."""
    import jax

    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=E,
        prefetch_depth=1, n_eq=E * N_B,
    )
    rng = np.random.default_rng(6)
    host, shared = _chain_data(cfd_chain, E * N_B, rng)
    inputs = {q: jax.device_put(a) if q in on_device else a
              for q, a in host.items()}
    kw = dict(inputs=inputs, shared=shared, n_eq=E * N_B, max_batches=N_B,
              pipeline_stages=True, collect_outputs=collect_outputs)
    tracer = trace.Tracer()
    return (host, run_chain(cfd_chain, plan, **kw),
            run_chain(cfd_chain, plan, tracer=tracer, **kw), tracer)


@pytest.mark.parametrize("on_device", [("interp.u", "helmholtz.D"),
                                       ("helmholtz.D",)],
                         ids=["all", "one"])
def test_resident_in_bytes_are_each_batch_of_the_device_inputs(cfd_chain,
                                                              on_device):
    host, _, _, tracer = _resident_runs(cfd_chain, on_device)
    assert tracer.totals(COUNTER_RESIDENT_IN_BYTES) == {
        q: float(sum(host[q][b * E:(b + 1) * E].nbytes for b in range(N_B)))
        for q in on_device
    }
    assert trace.validate(trace.to_chrome(tracer)) == []


def test_host_inputs_count_no_resident_bytes(traced_run):
    _, tracer, _ = traced_run
    assert tracer.totals(COUNTER_RESIDENT_IN_BYTES) == {}
    assert not [c for c in tracer.counters
                if c.name == COUNTER_RESIDENT_IN_BYTES]


@pytest.mark.parametrize("on_device", [("interp.u", "helmholtz.D"),
                                       ("helmholtz.D",)],
                         ids=["all", "one"])
def test_resident_in_place_counts_each_batch_of_the_device_inputs(
        cfd_chain, on_device):
    """Inputs on the stage's own device are read in place, every batch;
    the run matches the same data streamed from numpy arrays."""
    host, plain, traced, tracer = _resident_runs(cfd_chain, on_device)
    assert tracer.totals(COUNTER_RESIDENT_IN_PLACE) == {
        q: float(N_B) for q in on_device}
    assert traced.checksums == plain.checksums
    # the same data as _resident_runs makes, all of it from numpy
    _, shared = _chain_data(cfd_chain, E * N_B, np.random.default_rng(6))
    streamed = run_chain(cfd_chain, plain.plan, inputs=host, shared=shared,
                         n_eq=E * N_B, max_batches=N_B, pipeline_stages=True)
    assert streamed.checksums == plain.checksums


def test_host_inputs_read_nothing_in_place(traced_run):
    _, tracer, _ = traced_run
    assert tracer.totals(COUNTER_RESIDENT_IN_PLACE) == {}
    assert not [c for c in tracer.counters
                if c.name == COUNTER_RESIDENT_IN_PLACE]


OFF_STAGE_SCRIPT = textwrap.dedent("""
    import json
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import trace
    from repro.cfd import operators, simulation
    from repro.memory import chain as mchain
    from repro.memory import channels

    assert jax.device_count() == 2, jax.devices()
    p, E, n_b = 5, 16, 3
    n = E * n_b
    chain = operators.build_cfd_chain(p)
    rng = np.random.default_rng(0)
    host = {
        "interp.u": rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32),
        "helmholtz.D": rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32),
    }
    shared = {
        name: rng.uniform(-1, 1, node.shape).astype(np.float32)
        for name, node in sorted(chain.shared_operands().items())
    }
    both, first = jax.devices(), jax.devices()[:1]
    cases = {
        # sharded over both devices, run on both: a multi-device stage
        "sharded": (both, NamedSharding(simulation.element_mesh(both),
                                        P("elements"))),
        # on the second device, run on the first: another stage's device
        "other_device": (first, jax.devices()[1]),
    }
    out = {}
    for case, (devices, where) in cases.items():
        mesh = simulation.element_mesh(devices)
        plan = mchain.plan_chain(
            chain, target=channels.CPU_HOST, batch_elements=E, n_eq=n,
            prefetch_depth=1, cu_count=len(devices),
        )
        kw = dict(shared=shared, mesh=mesh, collect_outputs=True)
        want = simulation.run_chain(chain, plan, inputs=host, **kw)
        tracer = trace.Tracer()
        got = simulation.run_chain(
            chain, plan, tracer=tracer,
            inputs={q: jax.device_put(a, where) for q, a in host.items()},
            **kw)
        out[case] = {
            "batches": got.batches,
            "equal": got.checksums == want.checksums and all(
                got.outputs[q].tobytes() == a.tobytes()
                for q, a in want.outputs.items()),
            "in_place": [c.name for c in tracer.counters
                         if c.name == "resident_in_place"],
            "resident_bytes": tracer.totals("resident_in_bytes"),
        }
    print(json.dumps(out))
""")


def test_inputs_off_the_stage_device_keep_the_sliced_path():
    """A device array sharded over several devices, or placed on another
    device than its stage's, is sliced and staged as before: nothing is
    read in place, its bytes still count as resident, and the run
    matches the host run bitwise."""
    from conftest import subprocess_env

    res = subprocess.run(
        [sys.executable, "-c", OFF_STAGE_SCRIPT],
        env=subprocess_env(2), capture_output=True, text=True, timeout=420,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert sorted(out) == ["other_device", "sharded"]
    for case, got in out.items():
        assert got["batches"] == N_B, case
        assert got["equal"] is True, case
        assert got["in_place"] == [], case
        assert sorted(got["resident_bytes"]) == ["helmholtz.D",
                                                 "interp.u"], case


@pytest.mark.parametrize("collect_outputs", [False, True],
                         ids=["checksums", "outputs"])
def test_tracer_off_is_bitwise_identical_on_resident_inputs(cfd_chain,
                                                            collect_outputs):
    _, plain, traced, _ = _resident_runs(
        cfd_chain, ("interp.u", "helmholtz.D"), collect_outputs)
    assert plain.checksums == traced.checksums
    if collect_outputs:
        for q, want in plain.outputs.items():
            assert traced.outputs[q].tobytes() == want.tobytes(), q


def test_spans_land_in_the_profiler_trace_nested(cfd_chain, tmp_path):
    """Under ``jax.profiler``, every span of a traced run is an
    ``@<cat>:<name>`` event on the host plane, and two events nest
    exactly where their spans do."""
    import glob

    import jax
    from jax.profiler import ProfileData

    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=64,
        prefetch_depth=1, n_eq=128,
    )
    inputs, shared = _chain_data(cfd_chain, 128, np.random.default_rng(6))
    tracer = trace.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_chain(cfd_chain, plan, inputs=inputs, shared=shared, n_eq=128,
                  max_batches=2, pipeline_stages=True, tracer=tracer,
                  collect_outputs=True)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("@"):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    spans = sorted(tracer.spans, key=lambda s: s.t0)
    by_label = {}
    for s in spans:
        by_label.setdefault(f"@{s.cat}:{s.name}", []).append(s)
    assert sorted(events) == sorted(by_label)
    matched = []
    for label, group in by_label.items():
        assert len(events[label]) == len(group), label
        matched += zip(group, sorted(events[label]))

    def holds(a, b):
        return a[0] <= b[0] and b[1] <= a[1]

    for sa, ea in matched:
        for sb, eb in matched:
            if sa is not sb:
                assert holds((sa.t0, sa.t1), (sb.t0, sb.t1)) == holds(
                    ea, eb), (sa.name, sb.name)


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


def test_attribution_matches_span_sums(traced_run):
    plan, tracer, _ = traced_run
    a = trace.attribute(tracer, plan)
    assert a.n_batches == N_B
    assert len(a.stages) == len(plan.stages)
    for i, s in enumerate(a.stages):
        assert s.name == plan.stages[i].name
        assert s.slots == N_B  # every stage dispatched every batch
        disp = [
            sp for sp in tracer.spans
            if sp.cat == CAT_DISPATCH and int(sp.args["stage"]) == i
        ]
        assert s.measured_s == pytest.approx(
            sum(sp.duration for sp in disp)
        )
        assert s.measured_s > 0
    # per-stage slot spans partition the run: one per (stage, batch)
    slots = [sp for sp in tracer.spans if sp.cat == CAT_SLOT]
    assert len(slots) == N_B * len(plan.stages)
    assert a.wall_s > 0 and a.pred_s_per_batch > 0


def test_attribution_report_renders(traced_run):
    plan, tracer, _ = traced_run
    rep = trace.attribution_report(tracer, plan)
    assert rep.startswith("measured:")
    for sp in plan.stages:
        assert sp.name in rep
    assert "-> ok)" in rep  # counter sum matched the plan


def test_golden_measured_section_stable(traced_run):
    """The deterministic fields of the measured: section (structure,
    predictions, counter sums -- no wall times) are golden-locked."""
    plan, tracer, _ = traced_run
    rep = trace.attribution_report(tracer, plan, stable_only=True)
    _golden_check("trace_measured_cfd_p5_alveo.txt", rep)


# ---------------------------------------------------------------------------
# straggler monitoring -> trace annotations
# ---------------------------------------------------------------------------


def test_monitor_flags_become_span_annotations(cfd_chain):
    plan = mchain.plan_chain(
        cfd_chain, target=channels.ALVEO_U280, batch_elements=64,
        prefetch_depth=1, n_eq=192,
    )
    rng = np.random.default_rng(9)
    inputs, shared = _chain_data(cfd_chain, 192, rng)
    tracer = trace.Tracer()
    # factor 0 flags every post-seed step: deterministic on any machine
    mon = StepMonitor(straggler_factor=0.0, warmup=0)
    res = run_chain(
        cfd_chain, plan, inputs=inputs, shared=shared, n_eq=192,
        max_batches=3, pipeline_stages=True, tracer=tracer, monitor=mon,
    )
    assert res.straggler_batches == (1, 2)
    flagged = sorted(
        int(sp.args["batch"]) for sp in tracer.spans
        if sp.cat == CAT_SYNC and sp.args.get("straggler")
    )
    assert flagged == [1, 2]
    assert trace.attribute(tracer, plan).straggler_batches == (1, 2)


# ---------------------------------------------------------------------------
# profile store
# ---------------------------------------------------------------------------


def test_profile_store_roundtrip(traced_run, tmp_path):
    plan, tracer, _ = traced_run
    path = str(tmp_path / "profile.json")
    store = trace.ProfileStore(path=path, fingerprint="testfp")
    n = store.record_trace(tracer, plan)
    assert n == len(plan.stages) + 1  # per-stage + chain-level samples

    # persists: a fresh store reloads the samples and refits from them
    store2 = trace.ProfileStore(path=path, fingerprint="testfp")
    assert len(store2) == n
    corr = store2.correction(plan.target.name, plan.signature)
    assert corr.n_samples == n
    assert corr.factor > 0 and corr.factor != pytest.approx(1.0)
    assert any(
        f is not None for f in
        (corr.host_factor, corr.hbm_factor, corr.compute_factor)
    )
    # a different machine's fingerprint sees none of it
    other = trace.ProfileStore(path=path, fingerprint="elsewhere")
    assert other.samples(plan.target.name) == []


def test_profile_store_env_override(tmp_path, monkeypatch):
    p = str(tmp_path / "env_profile.json")
    monkeypatch.setenv(trace.PROFILE_ENV, p)
    assert trace.default_profile_path() == p
    store = trace.ProfileStore()
    assert store.path == p


def test_explore_chain_warm_profile_reranks(traced_run, cfd_chain,
                                            tmp_path):
    """The acceptance round-trip: trace -> store -> refit -> the DSE
    ranking is re-priced by the learned per-term corrections."""
    plan, tracer, _ = traced_run
    path = str(tmp_path / "profile.json")
    store = trace.ProfileStore(path=path, fingerprint="testfp")
    assert store.record_trace(tracer, plan) > 0

    space = dse.ChainDesignSpace(
        backends=("xla", "staged"), batch_divisors=(1, 2),
        prefetch_depths=(0, 1), cu_counts=(1,), max_placements=2,
    )
    cold = dse.explore_chain(
        cfd_chain, target=channels.ALVEO_U280, n_eq=1 << 10, space=space,
    )
    warm = dse.explore_chain(
        cfd_chain, target=channels.ALVEO_U280, n_eq=1 << 10, space=space,
        profile=store,
    )
    assert all(c.corrected_s_per_element is None for c in cold)
    feas = [c for c in warm if c.plan.feasible]
    assert feas and all(
        c.corrected_s_per_element is not None for c in feas
    )
    # the correction actually moved the predictions...
    assert any(
        c.corrected_s_per_element != c.predicted_s_per_element
        for c in feas
    )
    # ...and the warm ranking is ordered by the corrected cost
    vals = [c.corrected_s_per_element for c in feas]
    assert vals == sorted(vals)


def test_per_term_correction_can_reorder(cfd_chain):
    """Per-term factors are not a monotone rescale: penalizing the cold
    leader's own bottleneck term demotes it below a candidate bound by a
    different term."""
    space = dse.ChainDesignSpace(
        backends=("xla", "staged"), batch_divisors=(1,),
        prefetch_depths=(0, 1), cu_counts=(1,), max_placements=2,
    )
    # cpu-host is the one datasheet whose chain candidates split between
    # hbm- and compute-bound (ALVEO streaming is always host-link-bound)
    cands = dse.explore_chain(
        cfd_chain, target=channels.CPU_HOST, n_eq=1 << 10, space=space,
    )
    feas = [c for c in cands if c.plan.feasible]
    leader = feas[0]
    term = leader.plan.cost.bottleneck
    if all(c.plan.cost.bottleneck == term for c in feas):
        pytest.skip("design space has a single bottleneck term")
    kw = {
        "host-link": "host_factor", "hbm": "hbm_factor",
        "compute": "compute_factor",
    }[term]
    corr = dse.CostCorrection(factor=1.0, n_samples=1, **{kw: 1e3})
    reranked = dse.apply_correction(list(feas), corr)
    assert reranked[0].plan is not leader.plan


def test_profile_store_fifo_bound(tmp_path):
    from repro.trace.profile import MAX_SAMPLES_PER_KEY

    store = trace.ProfileStore(
        path=str(tmp_path / "p.json"), fingerprint="fp"
    )
    samples = [
        {"predicted_s": 1.0, "measured_s": 2.0, "bottleneck": "hbm"}
        for _ in range(MAX_SAMPLES_PER_KEY + 50)
    ]
    store.record("t", "sig", samples, save=False)
    assert len(store) == MAX_SAMPLES_PER_KEY
