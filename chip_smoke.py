#!/usr/bin/env python3
"""Run the paper's CFD application on a TPU through the program's own
entry points, and check what comes out against the float64 reference.

    python chip_smoke.py             # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4   # four chips: phase (f) only
    python chip_smoke.py --one-pass-control   # negative control, see below

Phases:
  (a) device: the first JAX device must be a TPU whose ``device_kind``
      has a datasheet (``memory.channels.detect_target``).
  (b) XLA batch solve: ``flow.compile(examples/cfd_pipeline.cfd)`` at
      the planner's own E, then ``CompiledSystem.run`` over 2^20
      elements, streamed batch by batch from a seed.
  (c) Pallas: the same program with ``backend="pallas"`` (every stage
      must report ``pallas``), and ``examples/inverse_helmholtz.cfd`` as
      one stage, which the hand-fused Helmholtz kernel must match.
  (d) correctness: two batches of each system against
      ``cfd/reference.py`` in float64 on a sample of elements.
  (e) serve: a ``ServeEngine`` over the XLA system answers 8 requests of
      mixed element counts; its outputs must match the batch path's.
  (f) four chips: the pipeline compiled with ``cu_count=4`` runs
      element-sharded over all four chips and must match a one-device
      run of the same batches.

Every line but the last reports; the last is one JSON object with the
device as JAX reports it.  Any failure exits non-zero without that line.
The script starts no child processes: this process holds the chip(s).

``--one-pass-control`` checks the check: it runs one batch of the XLA
pipeline with float32 storage but every contraction at the TPU's default
matmul precision (one bfloat16 pass) through the same float64 comparison,
and exits 1 when :data:`RTOL` rejects it, as it must -- or 3, with the
errors, when the bound is too loose to see a one-pass run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
import warnings

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_EQ = 1 << 20
SEED = 0
#: Relative error bound, max|out - ref| / max|ref| per output, against
#: the float64 reference.  Outputs come from float32 accumulation over
#: chains of length-11 contractions, up to seven deep for v: float32
#: rounding keeps that near 1e-7.  Rounding every contraction's operands
#: to bfloat16 once -- one MXU pass, the TPU's default precision for a
#: float32 matmul -- is off by about 1e-3 or more, since bfloat16 keeps 8
#: significant bits; ``--one-pass-control`` measures that run against
#: this bound (4.3e-3 to 6.6e-3 on a TPU v5e, rejected).
RTOL = 1e-4
#: Elements per batch compared with the host float64 reference.
SAMPLE = 64
#: Element counts of the served requests (they span two waves of E).
REQUEST_FRACTIONS = (1e-5, 2e-4, 0.004, 0.03, 0.1, 0.25, 0.5)


class SmokeError(RuntimeError):
    """A phase found the system wrong, or not on the chip."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def read_example(name: str) -> str:
    with open(os.path.join(ROOT, "examples", name)) as f:
        return f.read()


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def check_device(n_chips: int):
    import jax

    from repro.memory import channels

    devs = jax.devices()
    dev = devs[0]
    require(dev.platform == "tpu",
            f"no TPU: JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); this script measures the chip only")
    require(len(devs) >= n_chips,
            f"{n_chips} chip(s) asked for, {len(devs)} present")
    target = channels.detect_target()
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)}")
    log(f"target: {target.name} (hbm {target.hbm_bytes / 2**30:.0f} GiB, "
        f"vmem {target.vmem_bytes / 2**20:.0f} MiB)")
    return dev, target


# ---------------------------------------------------------------------------
# helpers shared by the phases
# ---------------------------------------------------------------------------


def compile_system(source: str, name: str, target, **kw):
    from repro import flow

    t0 = time.perf_counter()
    system = flow.compile(source, name=name, target=target, n_eq=N_EQ,
                          profile=None, **kw)
    plan_s = time.perf_counter() - t0
    e = system.plan.batch_elements
    log(f"[{name}] plan: E={e} batches={N_EQ // e} "
        f"elements={(N_EQ // e) * e} of n_eq={N_EQ} "
        f"stages={len(system.backends)} plan {plan_s:.2f} s")
    log(f"[{name}] stage backends: "
        + " ".join(f"{s}={b}" for s, b in zip(system.stage_names,
                                               system.backends)))
    blocks = sorted({sp.block_elements for sp in system.plan.stages})
    log(f"[{name}] vmem blocks: {blocks}")
    return system


def timed_run(system, name: str, **kw):
    """First batch (compiles every stage), then the timed run."""
    e = system.plan.batch_elements
    t0 = time.perf_counter()
    system.run(n_eq=e, max_batches=1, seed=SEED, **kw)
    first_s = time.perf_counter() - t0
    res = system.run(n_eq=N_EQ, seed=SEED, **kw)
    require(res.batches == N_EQ // e,
            f"[{name}] ran {res.batches} batches, want {N_EQ // e}")
    bad = [q for q, v in res.checksums.items() if not np.isfinite(v)]
    require(not bad, f"[{name}] non-finite checksums: {bad}")
    log(f"[{name}] first batch (compiles every stage) {first_s:.2f} s")
    log(f"[{name}] timed run: {res.batches} batches, {res.elements} "
        f"elements, wall {res.wall_s:.3f} s, "
        f"{res.elements / res.wall_s:.0f} elements/s, "
        f"pipelined={res.pipelined_stages}")
    return res


def batch_inputs(system, n: int, seed: int):
    """Host inputs for ``n`` elements and the shared operands, by name."""
    rng = np.random.default_rng(seed)
    chain = system.chain
    inputs = {
        f"{s.name}.{name}": rng.uniform(
            -1, 1, (n,) + tuple(v.shape)).astype(np.float32)
        for i, s in enumerate(chain.stages)
        for name, v in chain.host_element_inputs(i)
    }
    shared = {
        name: rng.uniform(-1, 1, v.shape).astype(np.float32)
        for name, v in sorted(chain.shared_operands().items())
    }
    return inputs, shared


def by_var(qualified: dict) -> dict:
    """``{"s3.gy": a}`` -> ``{"gy": a}`` (program-level names)."""
    out = {}
    for q, a in qualified.items():
        var = q.split(".", 1)[1]
        require(var not in out, f"stream {var!r} appears twice")
        out[var] = a
    return out


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(got.astype(np.float64) - want))
                 / np.max(np.abs(want)))


def reference(program: str, ins: dict, shared: dict, idx) -> dict:
    """float64 outputs of the sampled elements (``cfd/reference.py``)."""
    from repro.cfd import reference as ref

    f64 = {k: v.astype(np.float64) for k, v in shared.items()}
    u = ins["u"][idx].astype(np.float64)
    D = ins["D"][idx].astype(np.float64)
    if program == "inverse_helmholtz":
        return {"v": ref.inverse_helmholtz_batch(f64["S"], D, u)}
    w = ref.interpolation_batch(f64["A"], u)
    grads = [ref.gradient(f64["Dx"], f64["Dy"], f64["Dz"], we) for we in w]
    gx, gy, gz = (np.stack(g) for g in zip(*grads))
    return {"gy": gy, "gz": gz,
            "v": ref.inverse_helmholtz_batch(f64["S"], D, gx)}


def check_outputs(name: str, program: str, outs: dict, ins: dict,
                  shared: dict) -> float:
    """Compare a sample of elements with the float64 reference."""
    n = next(iter(outs.values())).shape[0]
    rng = np.random.default_rng(SEED + 7)
    idx = np.unique(np.concatenate(
        [[0, n - 1], rng.choice(n, SAMPLE, replace=False)]))
    want = reference(program, ins, shared, idx)
    require(set(want) == set(outs),
            f"[{name}] outputs {sorted(outs)} != reference {sorted(want)}")
    errs = {}
    for var, w in want.items():
        got = outs[var][idx]
        require(np.all(np.isfinite(got)), f"[{name}] {var}: non-finite")
        errs[var] = rel_err(got, w)
        log(f"[{name}] {var}: max rel err vs float64 {errs[var]:.3e} "
            f"({len(idx)} elements; bound {RTOL:g})")
    bad = {v: e for v, e in errs.items() if e > RTOL}
    require(not bad, f"[{name}] rel err over {RTOL:g}: "
            + ", ".join(f"{v} {e:.3e}" for v, e in bad.items()))
    return max(errs.values())


def collected(system, inputs: dict, shared: dict, **kw) -> dict:
    n = next(iter(inputs.values())).shape[0]
    res = system.run(inputs=inputs, shared=shared, n_eq=n,
                     collect_outputs=True, **kw)
    require(res.elements == n, f"ran {res.elements} of {n} elements")
    return res, by_var(res.outputs)


# ---------------------------------------------------------------------------
# phases (b)-(f)
# ---------------------------------------------------------------------------


def one_chip(target) -> None:
    from repro.flow import patterns

    pipe_src = read_example("cfd_pipeline.cfd")

    # (b) XLA batch solve
    xla = compile_system(pipe_src, "cfd_pipeline/xla", target)
    timed_run(xla, "cfd_pipeline/xla")

    # (c) Pallas: every stage a kernel, and the hand-fused Helmholtz
    pal = compile_system(pipe_src, "cfd_pipeline/pallas", target,
                         backend="pallas")
    require(set(pal.backends) == {"pallas"},
            f"pallas stages fell back: {pal.backends}")
    timed_run(pal, "cfd_pipeline/pallas")
    helm = compile_system(read_example("inverse_helmholtz.cfd"),
                          "inverse_helmholtz/pallas", target,
                          backend="pallas", max_stages=1)
    require(helm.backends == ("pallas",),
            f"helmholtz stage backend {helm.backends}")
    require(patterns.match_inverse_helmholtz(helm.chain.stages[0].program)
            is not None, "the fused Helmholtz kernel did not match")
    timed_run(helm, "inverse_helmholtz/pallas")

    # (d) correctness: two batches of each system vs float64
    e = xla.plan.batch_elements
    pipe_in, pipe_shared = batch_inputs(xla, 2 * e, SEED + 1)
    _, xla_out = collected(xla, pipe_in, pipe_shared)
    pipe_vars = by_var(pipe_in)
    check_outputs("cfd_pipeline/xla", "cfd_pipeline", xla_out, pipe_vars,
                  pipe_shared)
    require(pal.plan.batch_elements == e, "pallas plan E differs")
    _, pal_out = collected(pal, pipe_in, pipe_shared)
    check_outputs("cfd_pipeline/pallas", "cfd_pipeline", pal_out,
                  pipe_vars, pipe_shared)
    he = helm.plan.batch_elements
    helm_in, helm_shared = batch_inputs(helm, 2 * he, SEED + 2)
    _, helm_out = collected(helm, helm_in, helm_shared)
    check_outputs("inverse_helmholtz/pallas", "inverse_helmholtz",
                  helm_out, by_var(helm_in), helm_shared)

    # (e) serve: 8 requests over the same rows as the XLA batch check
    serve(xla, pipe_in, pipe_shared, xla_out)


def serve(system, inputs: dict, shared: dict, batch_out: dict) -> None:
    from repro.serve.engine import ServeEngine

    n = next(iter(inputs.values())).shape[0]
    sizes = [max(1, int(f * n)) for f in REQUEST_FRACTIONS]
    sizes.append(n - sum(sizes))
    require(len(sizes) == 8 and min(sizes) >= 1, f"bad sizes {sizes}")
    engine = ServeEngine(system, shared=shared, seed=SEED)
    t0 = time.perf_counter()
    reqs, start = [], 0
    for k in sizes:
        reqs.append((start, engine.submit(
            {q: a[start:start + k] for q, a in inputs.items()})))
        start += k
    engine.drain()
    wall = time.perf_counter() - t0
    worst, bitwise = 0.0, True
    for start, req in reqs:
        require(req.error is None, f"request r{req.rid} failed: {req.error}")
        got = by_var(req.outputs)
        for var, want in batch_out.items():
            w = want[start:start + req.n_elements].astype(np.float64)
            g = got[var]
            require(g.shape == w.shape, f"r{req.rid} {var}: {g.shape}")
            err = float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
            require(err <= RTOL,
                    f"served r{req.rid} {var}: rel err {err:.3e} vs batch")
            worst = max(worst, err)
            bitwise &= bool(np.array_equal(g, want[start:start
                                                    + req.n_elements]))
    log(f"[serve] {len(reqs)} requests, sizes {sizes}, "
        f"{engine.stats['waves']} waves, wall {wall:.3f} s (compiled)")
    log(f"[serve] outputs vs batch path: max rel err {worst:.3e} "
        f"(bound {RTOL:g}), bitwise equal: {bitwise}")


def four_chips(target) -> None:
    import jax

    from repro.cfd.simulation import element_mesh

    src = read_example("cfd_pipeline.cfd")
    sys4 = compile_system(src, "cfd_pipeline/4chips", target, cu_count=4,
                          devices=0)
    e = sys4.plan.batch_elements
    ins, shared = batch_inputs(sys4, 2 * e, SEED + 3)
    res4, out4 = collected(sys4, ins, shared)
    groups = res4.placement_groups
    require(groups is not None, "the 4-chip run fell back to one mesh")
    require(all(sorted(g) == [0, 1, 2, 3] for g in groups),
            f"placement groups {groups} do not span 4 devices")
    log(f"[4chips] placement groups: {groups}")
    sys1 = compile_system(src, "cfd_pipeline/1chip", target,
                          batch_elements=e)
    res1, out1 = collected(sys1, ins, shared,
                           mesh=element_mesh(jax.devices()[:1]))
    require(res1.placement_groups is None or all(
        len(g) == 1 for g in res1.placement_groups), "1-chip run spread")
    worst, bitwise = 0.0, True
    for var, a in out1.items():
        err = rel_err(out4[var], a.astype(np.float64))
        require(err <= RTOL, f"4 chips vs 1 chip {var}: rel err {err:.3e}")
        worst = max(worst, err)
        bitwise &= bool(np.array_equal(out4[var], a))
    log(f"[4chips] vs one device, {res4.batches} batches of {e}: max rel "
        f"err {worst:.3e} (bound {RTOL:g}), bitwise equal: {bitwise}")
    check_outputs("cfd_pipeline/4chips", "cfd_pipeline", out4, by_var(ins),
                  shared)
    timed_run(sys4, "cfd_pipeline/4chips")


class ControlNotRejected(SmokeError):
    """The one-pass control passed the float64 check."""


def one_pass_control(target) -> None:
    """One batch of the XLA pipeline at the TPU's default matmul
    precision, through the float64 check that must reject it."""
    import jax

    from repro.core.precision import FloatPolicy

    class OnePassFloat32(FloatPolicy):
        """float32 storage, contractions as one bfloat16 pass."""

        @property
        def precision(self):
            return jax.lax.Precision.DEFAULT

    name = "control/one-pass"
    system = compile_system(read_example("cfd_pipeline.cfd"), name, target,
                            policy=OnePassFloat32("float32"))
    inputs, shared = batch_inputs(system, system.plan.batch_elements,
                                  SEED + 1)
    _, outs = collected(system, inputs, shared)
    try:
        check_outputs(name, "cfd_pipeline", outs, by_var(inputs), shared)
    except SmokeError:
        log(f"[{name}] rejected by the float64 check, as it must be")
        raise
    raise ControlNotRejected(
        f"[{name}] passed the float64 check: RTOL {RTOL:g} cannot tell "
        "one bfloat16 pass from float32")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip phase (f)")
    ap.add_argument("--one-pass-control", action="store_true",
                    help="run only the negative control of the float64 "
                         "check; exits 1 when the check rejects it")
    args = ap.parse_args()
    try:
        import jax

        from repro.compile_cache import enable_compile_cache

        cache = enable_compile_cache()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            dev, target = check_device(args.chips)
            log(f"compile cache: {cache}")
            if args.one_pass_control:
                one_pass_control(target)
            elif args.chips == 4:
                four_chips(target)
            else:
                one_chip(target)
        stand_ins = [str(w.message) for w in caught
                     if issubclass(w.category, RuntimeWarning)
                     and str(w.message).startswith(("run_chain:", "flow:"))]
        require(not stand_ins, f"fallback warnings: {stand_ins}")
    except ControlNotRejected:
        traceback.print_exc()
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
