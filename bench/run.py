#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one configuration (``bench/configs/<config>.json``: the
CFDlang source, p, policy and ``flow.compile`` arguments) under one
traffic mix (``bench/traffic/<mix>.json``, driven by the code of its
kind, ``bench/kinds/<kind>.py``, through ``traffic.py``).
The run compiles the configuration through ``flow.compile``, makes the mix's inputs from ``--seed``, warms up
every shape the window uses, and counts all of that as ``setup_s``.  It
then drives the window for ``--seconds`` and compares the sampled
outputs of the timed path with the float64 reference (``check.py``),
with any numbers the traffic kind adds.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records the window under the profiler, with the program's spans
mirrored into it (``spans.py``), and reports the cell's per-layer
metrics, each read by ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (batches of the window), ``metrics``,
``device`` and, traced, ``breakdown``; its last key, ``checks``, holds
each compared number beside its limit, as do the last lines of standard
error.  Without a TPU, with fewer chips than the cell asks for, or on a
``device_kind`` missing from ``peaks.json``, the run exits 2 and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoChip(RuntimeError):
    """No TPU, too few chips, or a device kind without peaks."""


@dataclasses.dataclass
class Cell:
    name: str
    config: str
    cfg: dict
    traffic: str
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclasses.dataclass
class Record:
    """What a per-layer reader reads: the traced window's counts, the
    program's spans (host clock), the reduced profiler trace, the plan's
    prediction and the benchmark's own work counts and peaks."""

    window: Any
    spans: Any
    trace: dict
    chips: int
    p: int
    plan_s_per_batch: float
    flops_per_element: int
    bytes_per_element: int
    peaks: dict


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, config=w["config"], cfg=load_json(ROOT / conf["file"]),
                traffic=w["traffic"],
                mix=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise NoChip(f"device kind {kind!r} has no peaks in bench/peaks.json"
                     f" (known: {sorted(table)})")
    return table[kind]


def check_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} device_kind={d.device_kind!r} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {d.platform!r}; the "
                     "benchmark measures the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    peaks_for(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def enable_compile_cache() -> None:
    """JAX's persistent cache in ``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names another; every program is
    cached, however short its compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_program(name: str):
    return importlib.import_module(f"bench.programs.{name}")


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def compile_system(cell: Cell, target=None):
    from repro import flow

    cfg = cell.cfg
    return flow.compile(cfg["cfdlang"], name=cell.config, target=target,
                        policy=cfg["policy"], n_eq=cfg["n_eq"],
                        profile=None, **cfg["compile"])


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


@contextlib.contextmanager
def gc_pauses():
    """The seconds of each garbage collection inside the block."""
    seconds: List[float] = []
    t0 = [0.0]

    def callback(phase: str, info: dict) -> None:
        if phase == "start":
            t0[0] = time.perf_counter()
        else:
            seconds.append(time.perf_counter() - t0[0])

    gc.callbacks.append(callback)
    try:
        yield seconds
    finally:
        gc.callbacks.remove(callback)


def traced_window(traffic, seconds: float, chips: int):
    """The window under the profiler; returns it, the program's spans
    and the reduced trace."""
    import jax

    from bench import trace_reduce
    from bench.spans import Spans

    tracer = Spans(mirror=True)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    try:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                window = traffic.window(seconds, tracer)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            shutil.copy(files[0], keep)
        summary = trace_reduce.reduce_file(files[0], chips)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return window, tracer, summary


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, target=None, device: Optional[dict] = None
             ) -> Dict[str, Any]:
    from bench import check, traffic as traffic_mod

    program = load_program(cell.cfg["program"])
    system = compile_system(cell, target)
    plan = system.plan
    log(f"[{cell.name}] plan: E={plan.batch_elements} stages="
        f"{len(system.backends)} backends={sorted(set(system.backends))} "
        f"predicted {plan.cost.t_pipelined * 1e3:.3f} ms/batch")
    traffic = traffic_mod.make(system, program, cell.cfg, cell.mix, seed)
    traffic.setup()
    setup_s = time.perf_counter() - t_start
    log(f"[{cell.name}] setup {setup_s:.3f} s")

    with gc_pauses() as pauses:
        if trace:
            window, spans, summary = traced_window(traffic, seconds,
                                                   cell.chips)
        else:
            window, spans, summary = traffic.window(seconds), None, None
    log(f"[{cell.name}] garbage collections in the window: {len(pauses)}, "
        f"longest {max(pauses, default=0):.4f} s, "
        f"total {sum(pauses):.4f} s")
    log(f"[{cell.name}] window: {window.calls} calls, {window.batches} "
        f"batches, {window.elements} elements in {window.wall_s:.3f} s")
    if window.call_s:
        q = sorted(window.call_s)
        log(f"[{cell.name}] call seconds: first {window.call_s[0]:.4f} "
            f"min {q[0]:.4f} median {q[len(q) // 2]:.4f} max {q[-1]:.4f}")
    peak = memory_peak_bytes(cell.chips)
    shared, ins, outs = traffic.samples()
    own = traffic.checks()
    e2e_values = traffic.end_to_end(window)
    traffic.free()
    del traffic, system
    gc.collect()
    checks = check.compare(program, shared, ins, outs, cell.cfg["limits"])
    checks.update(check.limited(own, cell.cfg["limits"]))
    correct = check.passed(checks) and window.failed == 0

    device = dict(device or {})
    device["memory_peak_bytes"] = peak
    metrics: Dict[str, dict] = {}
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": window.attempted,
                              "failed": window.failed}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        peaks = peaks_for(device["kind"]) if "kind" in device else {}
        rec = Record(window=window, spans=spans, trace=summary,
                     chips=cell.chips, p=cell.cfg["p"],
                     plan_s_per_batch=plan.cost.t_pipelined,
                     flops_per_element=program.flops_per_element(cell.cfg["p"]),
                     bytes_per_element=program.bytes_per_element(cell.cfg["p"]),
                     peaks=peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        e2e_values["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e_values[m["name"]]),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    enable_compile_cache()
    try:
        device = check_device(cell.chips)
    except NoChip as e:
        log(f"error: {e}")
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, device=device)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
