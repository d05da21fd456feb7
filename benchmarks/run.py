"""Benchmark harness -- one entry per paper table/figure.

  tab2_ladder      Fig. 15 / Table 2: the optimization ladder
                   (baseline -> double-buffer -> dataflow 1/2/3/7)
  fig16_precision  Fig. 16 / Table 4: precision x polynomial degree
  fig17_multicu    Fig. 17 / Table 5: CU replication (element-sharding)
  fig19_kernels    Fig. 19: Inverse Helmholtz / Interpolation / Gradient
  memplan_ladder   Figs. 14-15: the same ladder driven by MemoryPlans
                   (repro.memory), plus the machine's DSE winner
  chain_ladder     Sec. 5: the composed interpolation -> gradient ->
                   inverse-Helmholtz application planned as one
                   ProgramChain (inter-stage streams HBM-resident) vs
                   the unchained host-round-trip baseline; also writes
                   chain_ladder.json (CI uploads it as an artifact)
  flow_ladder      the repro.flow acceptance ladder: hand stage cuts vs
                   fully automatic source-to-system compilation; writes
                   flow_ladder.json
  lm_throughput    framework health: LM train/decode throughput (smoke)

Prints ``name,us_per_call,derived`` CSV rows (derived = GFLOPS under the
paper's Eq. (2) op-count model where applicable).  Wall times are those
of the backend it runs on -- the CPU, in the CI ladders -- and are not
chip numbers; ``chip_smoke.py`` at the repo root is the run on a TPU.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.cfd import operators, reference  # noqa: E402
from repro.cfd.simulation import SimConfig, run_simulation  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.precision import POLICIES, enable_x64  # noqa: E402


def _time(fn, *args, warmup=2, iters=5, **kw):
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _pred_fields(pred_s_per_batch, us_per_batch, E):
    """Prediction-accuracy fields for a timed ladder row: per-element
    predicted and measured seconds plus their symmetric ratio
    (``max(pred/meas, meas/pred)``, so over- and under-prediction are
    penalized alike).  CI bounds the ratio via $BENCH_PRED_ERROR_MAX."""
    meas_s = us_per_batch * 1e-6
    fields = {
        "predicted_s_per_element": pred_s_per_batch / E,
        "measured_s_per_element": meas_s / E,
    }
    if pred_s_per_batch > 0 and meas_s > 0:
        fields["prediction_error"] = max(
            pred_s_per_batch / meas_s, meas_s / pred_s_per_batch
        )
    return fields


_PROFILE_STORE = None


def _profile_record(plan, pred_s_per_batch, us_per_batch, scope):
    """Deposit a timed rung into the persistent profile store so later
    ``explore_chain(profile=...)`` runs rank with per-term corrections
    refit from this machine's history.  $BENCH_NO_PROFILE=1 disables;
    $REPRO_PROFILE redirects the store file.  Never fails the bench."""
    global _PROFILE_STORE
    import os

    if os.environ.get("BENCH_NO_PROFILE"):
        return
    try:
        if _PROFILE_STORE is None:
            from repro.trace import ProfileStore

            _PROFILE_STORE = ProfileStore()
        E = plan.batch_elements
        _PROFILE_STORE.record_measurement(
            plan, pred_s_per_batch / E, us_per_batch * 1e-6 / E,
            scope=f"bench:{scope}",
        )
    except Exception as e:
        print(f"# profile store skipped: {e}", file=sys.stderr)


def _helmholtz_data(p, E, rng, dtype=np.float32):
    return (
        rng.uniform(-1, 1, (p, p)).astype(dtype),
        rng.uniform(-1, 1, (E, p, p, p)).astype(dtype),
        rng.uniform(-1, 1, (E, p, p, p)).astype(dtype),
    )


def tab2_ladder() -> None:
    """The paper's cumulative-optimization ladder, CPU analogues:

    naive        literal O(p^6) contraction (pre-rewrite)
    serial_1elem factorized but one element per dispatch (serial CU)
    factorized   teil factorization -> GEMM chain, batched (paper baseline)
    dataflow_K   staged backend with K compute groups (1/2/3/7)
    """
    p, E = 11, 512
    rng = np.random.default_rng(0)
    S, D, u = _helmholtz_data(p, E, rng)
    flops = E * reference.paper_flops_per_element(p)

    # naive: literal program (no factorization) -- tiny E, extrapolate
    naive = operators.build_inverse_helmholtz(p, optimize=False)
    En = 4
    t_n = _time(
        lambda: naive.batched_fn({"S": S, "D": D[:En], "u": u[:En]})["v"],
        warmup=1, iters=2,
    )
    _row("tab2_ladder/naive_literal", t_n / En * E * 1e6,
         f"{flops / (t_n / En * E) / 1e9:.3f}GFLOPS")

    fact = operators.build_inverse_helmholtz(p)
    t1 = _time(
        lambda: [fact.batched_fn(
            {"S": S, "D": D[i:i + 1], "u": u[i:i + 1]})["v"]
            for i in range(32)],
        warmup=1, iters=2,
    )
    _row("tab2_ladder/serial_1elem", t1 / 32 * E * 1e6,
         f"{flops / (t1 / 32 * E) / 1e9:.3f}GFLOPS")

    t = _time(lambda: fact.batched_fn({"S": S, "D": D, "u": u})["v"])
    _row("tab2_ladder/factorized_xla", t * 1e6, f"{flops / t / 1e9:.3f}GFLOPS")

    for k in (1, 2, 3, 7):
        staged = operators.build_inverse_helmholtz(
            p, backend="staged", max_groups=k
        )
        tk = _time(lambda: staged.batched_fn({"S": S, "D": D, "u": u})["v"])
        _row(f"tab2_ladder/dataflow_{k}", tk * 1e6,
             f"{flops / tk / 1e9:.3f}GFLOPS")


def fig16_precision() -> None:
    rng = np.random.default_rng(1)
    for p in (7, 11):
        E = 256
        S, D, u = _helmholtz_data(p, E, rng, np.float64)
        flops = E * reference.paper_flops_per_element(p)
        oracle = reference.inverse_helmholtz_batch(S, D, u)
        for pol_name in ("float32", "bfloat16"):
            c = operators.build_inverse_helmholtz(p, policy=pol_name)
            env = {"S": S.astype(np.float32),
                   "D": D.astype(np.float32), "u": u.astype(np.float32)}
            try:
                t = _time(lambda: c.batched_fn(env)["v"])
                got = np.asarray(
                    c.batched_fn(env)["v"].astype(jnp.float32), np.float64
                )
            except Exception:
                # CPU runtime lacks BF16xBF16=F32 dot execution (the
                # bf16 policy is a TPU-target path; compile-only here)
                _row(f"fig16/{pol_name}_p{p}", 0.0,
                     "unsupported-on-cpu-runtime")
                continue
            mse = float(np.mean((got - oracle) ** 2))
            _row(f"fig16/{pol_name}_p{p}", t * 1e6,
                 f"{flops / t / 1e9:.3f}GFLOPS;mse={mse:.2e}")
        with enable_x64(True):
            for pol_name in ("fixed32_q8.24", "fixed64_q24.40"):
                pol = POLICIES[pol_name]
                c = operators.build_inverse_helmholtz(
                    p, policy=pol
                )
                env = {k: pol.encode(v) for k, v in
                       {"S": S, "D": D, "u": u}.items()}
                t = _time(lambda: c.batched_fn(env)["v"], warmup=1, iters=2)
                got = np.asarray(pol.decode(c.batched_fn(env)["v"]))
                mse = float(np.mean((got - oracle) ** 2))
                _row(f"fig16/{pol_name}_p{p}", t * 1e6,
                     f"{flops / t / 1e9:.3f}GOPS;mse={mse:.2e}")


def fig17_multicu() -> None:
    """CU replication / batching: elements per dispatch (the paper's E)
    and double-buffering on/off.  On this 1-core container replication
    cannot reduce wall time -- the paper's own conclusion when host
    bandwidth is the limit; the accounting structure is the deliverable."""
    for E in (256, 512, 1024):
        cfg = SimConfig(p=11, n_eq=4 * E, batch_elements=E)
        run_simulation(cfg, max_batches=2)  # warm
        res = run_simulation(cfg, max_batches=4)
        flops = res.elements * reference.paper_flops_per_element(11)
        _row(f"fig17/batch_{E}", res.wall_s / res.batches * 1e6,
             f"{flops / res.wall_s / 1e9:.3f}GFLOPS")
    for db in (False, True):
        cfg = SimConfig(p=11, n_eq=2048, batch_elements=512,
                        double_buffer=db)
        run_simulation(cfg, max_batches=2)
        res = run_simulation(cfg, max_batches=4)
        flops = res.elements * reference.paper_flops_per_element(11)
        _row(f"fig17/double_buffer_{db}", res.wall_s / res.batches * 1e6,
             f"{flops / res.wall_s / 1e9:.3f}GFLOPS")


def fig19_kernels() -> None:
    rng = np.random.default_rng(2)
    E = 512
    p = 11
    S, D, u = _helmholtz_data(p, E, rng)
    c = operators.build_inverse_helmholtz(p)
    t = _time(lambda: c.batched_fn({"S": S, "D": D, "u": u})["v"])
    fl = E * reference.paper_flops_per_element(p)
    _row("fig19/inverse_helmholtz", t * 1e6, f"{fl / t / 1e9:.3f}GFLOPS")

    n = m = 11
    A = rng.uniform(-1, 1, (m, n)).astype(np.float32)
    ui = rng.uniform(-1, 1, (E, n, n, n)).astype(np.float32)
    ci = operators.build_interpolation(n, m)
    ti = _time(lambda: ci.batched_fn({"A": A, "u": ui})["v"])
    fl_i = E * 2 * 3 * n ** 4
    _row("fig19/interpolation", ti * 1e6, f"{fl_i / ti / 1e9:.3f}GFLOPS")

    nx, ny, nz = 8, 7, 6
    Dx = rng.uniform(-1, 1, (nx, nx)).astype(np.float32)
    Dy = rng.uniform(-1, 1, (ny, ny)).astype(np.float32)
    Dz = rng.uniform(-1, 1, (nz, nz)).astype(np.float32)
    ug = rng.uniform(-1, 1, (E, nx, ny, nz)).astype(np.float32)
    cg = operators.build_gradient(nx, ny, nz)
    tg = _time(lambda: cg.batched_fn(
        {"Dx": Dx, "Dy": Dy, "Dz": Dz, "u": ug})["gx"])
    fl_g = E * 2 * (nx * nx * ny * nz + ny * ny * nx * nz + nz * nz * nx * ny)
    _row("fig19/gradient", tg * 1e6, f"{fl_g / tg / 1e9:.3f}GFLOPS")


def memplan_ladder() -> None:
    """The paper's baseline -> double-buffer -> dataflow ladder, but every
    rung generated from a MemoryPlan instead of hand-rolled driver knobs.
    Rows report measured us/batch plus the plan's predicted us/batch; the
    last row is the DSE winner for this machine."""
    from repro.memory import channels as mchan, dse

    target = mchan.detect_target()
    E, n_b = 512, 8
    n_eq = E * n_b
    rungs = [
        ("baseline", {"prefetch_depth": 0}),
        ("double_buffer", {"prefetch_depth": 1}),
        ("prefetch_4", {"prefetch_depth": 4}),
        ("dataflow", {"prefetch_depth": 1, "backend": "staged"}),
    ]
    for name, kw in rungs:
        plan = dse.make_plan(
            11, target=target, batch_elements=E, n_eq=n_eq, **kw
        )
        cfg = SimConfig(
            p=11, n_eq=n_eq, batch_elements=E,
            backend=kw.get("backend", "xla"),
            prefetch_depth=kw["prefetch_depth"],
        )
        run_simulation(cfg, plan=plan, max_batches=2)  # warm
        # min over repetitions: robust against CPU frequency/cache drift
        best = min(
            (run_simulation(cfg, plan=plan, max_batches=n_b)
             for _ in range(3)),
            key=lambda r: r.wall_s,
        )
        flops = best.elements * reference.paper_flops_per_element(11)
        _row(
            f"memplan_ladder/{name}", best.wall_s / best.batches * 1e6,
            f"{flops / best.wall_s / 1e9:.3f}GFLOPS;"
            f"pred={plan.cost.t_pipelined * 1e6:.0f}us",
        )
    # "this machine's winner": only CU counts that exist here, and report
    # the candidate that was actually measured (not just predicted)
    space = dse.DesignSpace(cu_counts=(jax.device_count(),))
    ranked = dse.explore(
        11, target=target, n_eq=n_eq, space=space, measure_top=1
    )
    best = next((c for c in ranked if c.verified), ranked[0])
    meas = best.measured_s_per_element
    _row(
        "memplan_ladder/dse_best",
        (meas if meas is not None else best.predicted_s_per_element)
        * best.plan.batch_elements * 1e6,
        f"backend={best.plan.backend};E={best.plan.batch_elements};"
        f"K={best.plan.prefetch_depth};CU={best.plan.cu_count};"
        f"{'measured' if meas is not None else 'predicted-only'};"
        f"pred={best.predicted_s_per_element * 1e6:.4f}us/elem",
    )


def _sharded_worker(p: int, E: int, n_b: int) -> None:
    """Subprocess body for the sharded rungs: runs the 3-stage chain
    under a 2-device placement (gradient stage element-sharded over both
    devices, handoffs resharded between groups) and prints one JSON line
    with the measurement.  Launched with
    ``--xla_force_host_platform_device_count=2`` and ``JAX_PLATFORMS=cpu``
    by the parent ladder: two virtual CPU devices, never the TPU.
    """
    import json

    from repro.cfd.simulation import run_chain
    from repro.memory import chain as mchain
    from repro.memory import channels as mchan
    from repro.memory.placement import DeviceTopology

    assert jax.device_count() == 2, jax.devices()
    n_eq = E * n_b
    target = mchan.detect_target()
    chain = operators.build_cfd_chain(p)
    flops_pe = sum(s.program.total_flops() for s in chain.stages)
    rng = np.random.default_rng(7)
    inputs = {
        "interp.u": rng.uniform(-1, 1, (n_eq, p, p, p)).astype(np.float32),
        "helmholtz.D": rng.uniform(
            -1, 1, (n_eq, p, p, p)
        ).astype(np.float32),
    }
    shared = {
        name: rng.uniform(-1, 1, node.shape).astype(np.float32)
        for name, node in sorted(chain.shared_operands().items())
    }
    plan = mchain.plan_chain(
        chain, target=target, batch_elements=E, prefetch_depth=1,
        cu_count=(1, 2, 1), topology=DeviceTopology.homogeneous(2),
        n_eq=n_eq,
    )
    run_chain(chain, plan, inputs=inputs, shared=shared,
              max_batches=2)  # warm
    best = min(
        (run_chain(chain, plan, inputs=inputs, shared=shared,
                   n_eq=n_eq, max_batches=n_b)
         for _ in range(3)),
        key=lambda r: r.wall_s,
    )
    assert best.placement_groups is not None  # really ran multi-device
    print(json.dumps({
        "us_per_batch": best.wall_s / best.batches * 1e6,
        "gflops": best.elements * flops_pe / best.wall_s / 1e9,
        "groups": [list(g) for g in best.placement_groups],
        "host_stream_bytes": plan.host_stream_bytes,
        "pred_us": plan.cost.t_overlapped * 1e6,
    }))


def _hetero_worker(p: int, E: int, n_b: int) -> None:
    """Subprocess body for the heterogeneous rung: the same chain over a
    *declared* 2-kind topology (cpu-host + alveo-u280, one device each),
    stage 0 placed on the host group at half the chain E so the 0->1
    handoff crosses both an E change and a kind change and exercises the
    re-blocking path.  Both devices are really CPU host devices (forced
    by the parent, which also sets ``JAX_PLATFORMS=cpu`` so the child
    never reaches for the TPU), so the rung tracks the re-block machinery's wall
    cost, not a speedup -- and the declared-kind pricing is meaningless
    on this silicon, so no prediction fields are reported.
    """
    import json

    from repro.cfd.simulation import run_chain
    from repro.memory import chain as mchain
    from repro.memory import channels as mchan
    from repro.memory.placement import DeviceTopology

    assert jax.device_count() == 2, jax.devices()
    n_eq = E * n_b
    chain = operators.build_cfd_chain(p)
    flops_pe = sum(s.program.total_flops() for s in chain.stages)
    rng = np.random.default_rng(7)
    inputs = {
        "interp.u": rng.uniform(-1, 1, (n_eq, p, p, p)).astype(np.float32),
        "helmholtz.D": rng.uniform(
            -1, 1, (n_eq, p, p, p)
        ).astype(np.float32),
    }
    shared = {
        name: rng.uniform(-1, 1, node.shape).astype(np.float32)
        for name, node in sorted(chain.shared_operands().items())
    }
    plan = mchain.plan_chain(
        chain, target=mchan.ALVEO_U280, batch_elements=E,
        prefetch_depth=1,
        topology=DeviceTopology.parse("cpu:1,alveo:1"),
        stage_groups=(0, 1, 1), stage_batch_elements=(E // 2, E, E),
        n_eq=n_eq,
    )
    assert plan.cost.t_reblock and plan.cost.t_reblock[1] > 0
    run_chain(chain, plan, inputs=inputs, shared=shared,
              max_batches=2)  # warm
    best = min(
        (run_chain(chain, plan, inputs=inputs, shared=shared,
                   n_eq=n_eq, max_batches=n_b)
         for _ in range(3)),
        key=lambda r: r.wall_s,
    )
    assert best.placement_groups is not None  # really ran placed
    print(json.dumps({
        "us_per_batch": best.wall_s / best.batches * 1e6,
        "gflops": best.elements * flops_pe / best.wall_s / 1e9,
        "groups": [list(g) for g in best.placement_groups],
        "kinds": [plan.placement.stage_kind(i)
                  for i in range(len(plan.stages))],
        "stage_e": list(plan.stage_batch_elements),
    }))


def _run_sharded_rung(p: int, E: int, n_b: int,
                      worker: str = "_sharded_worker") -> dict:
    """Launch a forced-2-host-device worker subprocess (the only way to
    exercise multi-device placement without several chips).  The child
    runs with ``JAX_PLATFORMS=cpu``: this process has already touched
    JAX and may hold the TPU, so the child must never reach for it."""
    import json
    import os
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, __file__, worker, str(p), str(E), str(n_b)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"{worker} rung subprocess failed:\n{res.stderr[-3000:]}"
        )
    return json.loads(res.stdout.strip().splitlines()[-1])


def chain_ladder() -> None:
    """The full CFD application as one ProgramChain.  Rungs compare the
    unchained baseline (every stage streams through the host, as three
    standalone plans would) against the chain plan's HBM-resident
    execution, serial and prefetched.  Results also land in
    ``chain_ladder.json`` (override the path with $CHAIN_LADDER_JSON)."""
    import json
    import os

    from repro.cfd.simulation import run_chain
    from repro.memory import chain as mchain
    from repro.memory import channels as mchan, dse

    p, E, n_b = 7, 256, 6
    n_eq = E * n_b
    target = mchan.detect_target()
    chain = operators.build_cfd_chain(p)
    flops_pe = sum(s.program.total_flops() for s in chain.stages)
    rng = np.random.default_rng(7)
    inputs = {
        "interp.u": rng.uniform(-1, 1, (n_eq, p, p, p)).astype(np.float32),
        "helmholtz.D": rng.uniform(-1, 1, (n_eq, p, p, p)).astype(np.float32),
    }
    shared = {
        name: rng.uniform(-1, 1, node.shape).astype(np.float32)
        for name, node in sorted(chain.shared_operands().items())
    }
    rows = []

    def emit(name, us_per_batch, gflops, extra="", pred_s=None,
             profile_plan=None):
        _row(f"chain_ladder/{name}", us_per_batch,
             f"{gflops:.3f}GFLOPS{';' + extra if extra else ''}")
        row = {
            "name": name, "us_per_batch": us_per_batch,
            "gflops": gflops, "extra": extra,
        }
        if pred_s is not None:
            row.update(_pred_fields(pred_s, us_per_batch, E))
        rows.append(row)
        if profile_plan is not None and pred_s is not None:
            _profile_record(profile_plan, pred_s, us_per_batch,
                            f"chain_ladder/{name}")

    # unchained baseline: each stage a separate dispatch with a host
    # round-trip between (what three standalone MemoryPlans execute)
    interp, grad, helm = (s.compiled for s in chain.stages)

    def unchained_batch(b):
        sl = slice(b * E, (b + 1) * E)
        w = np.asarray(interp.batched_fn(
            {"A": shared["A"], "u": inputs["interp.u"][sl]})["w"])
        g = grad.batched_fn({
            "Dx": shared["Dx"], "Dy": shared["Dy"], "Dz": shared["Dz"],
            "w": np.asarray(w),
        })
        gx = np.asarray(g["gx"])
        out = helm.batched_fn({
            "S": shared["S"], "D": inputs["helmholtz.D"][sl], "gx": gx,
        })
        return float(jnp.sum(out["v"]))

    unchained_batch(0)  # warm compile
    t0 = time.perf_counter()
    for b in range(n_b):
        unchained_batch(b)
    t_unchained = (time.perf_counter() - t0) / n_b
    emit("unchained_host_roundtrip", t_unchained * 1e6,
         E * flops_pe / t_unchained / 1e9)

    # rungs 2-4 run back-to-back (pipeline_stages=False) so the ladder
    # isolates staging depth; the last rung turns on cross-batch stage
    # pipelining (one dispatch ring per stage) at the same K=1
    rungs = (
        ("chained_serial", 0, False),
        ("chained_double_buffer", 1, False),
        ("chained_prefetch_2", 2, False),
        ("chained_stage_pipelined", 1, True),
    )
    for name, depth, piped in rungs:
        plan = mchain.plan_chain(
            chain, target=target, batch_elements=E,
            prefetch_depth=depth, n_eq=n_eq,
        )
        run_chain(chain, plan, inputs=inputs, shared=shared,
                  max_batches=2, pipeline_stages=piped)  # warm
        best = min(
            (run_chain(chain, plan, inputs=inputs, shared=shared,
                       n_eq=n_eq, max_batches=n_b, pipeline_stages=piped)
             for _ in range(3)),
            key=lambda r: r.wall_s,
        )
        pred = (
            plan.cost.t_overlapped if piped else plan.cost.t_back_to_back
        )
        emit(name, best.wall_s / best.batches * 1e6,
             best.elements * flops_pe / best.wall_s / 1e9,
             f"pred={pred * 1e6:.0f}us", pred_s=pred, profile_plan=plan)

    # sharded rung: the same chain under a 2-device placement (gradient
    # stage element-sharded, handoffs resharded between groups), run in
    # a subprocess with a forced host device count.  On this container
    # both "devices" share one CPU, so the rung tracks the placement
    # machinery's overhead rather than a speedup.
    sh = _run_sharded_rung(p, E, n_b)
    emit("chained_sharded_2dev", sh["us_per_batch"], sh["gflops"],
         f"groups={sh['groups']};pred={sh['pred_us']:.0f}us",
         pred_s=sh["pred_us"] * 1e-6)

    # heterogeneous rung: the same chain over a declared 2-kind topology
    # (cpu-host + alveo-u280) with the host stage re-blocked to E/2, so
    # every batch pays a real re-blocking handoff.  No prediction fields
    # -- the declared-kind pricing does not describe this CPU container.
    # The checked-in baseline caps this rung at max_ratio_vs the
    # homogeneous sharded rung: re-blocking must stay within 1.5x of the
    # plain 2-device placement, machine-independently.
    het = _run_sharded_rung(p, E, n_b, worker="_hetero_worker")
    emit("chained_hetero_2kind", het["us_per_batch"], het["gflops"],
         f"groups={het['groups']};kinds={','.join(het['kinds'])};"
         f"stage_e={het['stage_e']}")
    rows[-1].update(
        {"max_ratio_vs": "chained_sharded_2dev", "max_ratio": 1.5}
    )

    # the residency claim, in bytes: chain host streams vs the sum of
    # three standalone plans at the same E
    plan = mchain.plan_chain(
        chain, target=target, batch_elements=E, prefetch_depth=1,
        n_eq=n_eq,
    )
    standalone = sum(
        dse.make_plan(
            s.program, target=target, batch_elements=E,
            operator_name=s.name,
        ).host_stream_bytes
        for s in chain.stages
    )
    # not a timing row: keep the us_per_call column honest (0.0) and put
    # the byte accounting in the derived field + the JSON artifact
    _row("chain_ladder/host_stream_residency", 0.0,
         f"chain_bytes_per_batch={plan.host_stream_bytes};"
         f"standalone_sum={standalone};"
         f"saved={1 - plan.host_stream_bytes / standalone:.1%}")

    path = os.environ.get("CHAIN_LADDER_JSON", "chain_ladder.json")
    with open(path, "w") as f:
        json.dump({
            "p": p, "E": E, "n_batches": n_b,
            "target": target.name,
            "rows": rows,
            "host_stream_bytes": {
                "chain": plan.host_stream_bytes,
                "standalone_sum": standalone,
            },
        }, f, indent=2)


def flow_ladder() -> None:
    """The tool-flow acceptance ladder: the same CFD pipeline compiled
    (a) by hand-granularity stage cuts (``operators.build_cfd_chain``)
    and (b) fully automatically from source by ``repro.flow`` (stages
    derived from the scheduler's dataflow groups), plus the cross-batch
    stage-pipelining acceptance pair on the 3-stage chain (serial
    back-to-back vs one dispatch ring per stage; the checked-in
    baseline records the speedup and CI's regression gate enforces its
    floor).  Rows report measured us/batch; results land in
    ``flow_ladder.json`` (override the path with $FLOW_LADDER_JSON)."""
    import json
    import os

    from repro import flow
    from repro.cfd.simulation import run_chain
    from repro.memory import chain as mchain
    from repro.memory import channels as mchan

    p, E, n_b = 7, 256, 6
    n_eq = E * n_b
    target = mchan.detect_target()
    rng = np.random.default_rng(11)
    source = operators.CFD_PIPELINE_SRC.format(p=p)
    shared_arrays = {
        name: rng.uniform(-1, 1, (p, p)).astype(np.float32)
        for name in ("A", "Dx", "Dy", "Dz", "S")
    }
    rows = []

    def measure(name, chain, plan, *, E, n_b, pipeline_stages=None,
                reps=3):
        n = E * n_b
        inputs = {}
        data = {
            "u": rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32),
            "D": rng.uniform(-1, 1, (n, p, p, p)).astype(np.float32),
        }
        for i, s in enumerate(chain.stages):
            for in_name, _ in chain.host_element_inputs(i):
                inputs[f"{s.name}.{in_name}"] = data[in_name]
        flops_pe = sum(s.program.total_flops() for s in chain.stages)
        run_chain(chain, plan, inputs=inputs, shared=shared_arrays,
                  max_batches=2, pipeline_stages=pipeline_stages)  # warm
        best = min(
            (run_chain(chain, plan, inputs=inputs, shared=shared_arrays,
                       n_eq=n, max_batches=n_b,
                       pipeline_stages=pipeline_stages)
             for _ in range(reps)),
            key=lambda r: r.wall_s,
        )
        us = best.wall_s / best.batches * 1e6
        gflops = best.elements * flops_pe / best.wall_s / 1e9
        _row(f"flow_ladder/{name}", us,
             f"{gflops:.3f}GFLOPS;stages={len(chain.stages)};"
             f"pred={plan.cost.t_pipelined * 1e6:.0f}us")
        # predicted per-batch for the schedule actually run (the plan's
        # own mode unless measure() forced one)
        pred = (
            plan.cost.t_pipelined if pipeline_stages is None
            else plan.cost.t_overlapped if pipeline_stages
            else plan.cost.t_back_to_back
        )
        rows.append({
            "name": name, "us_per_batch": us, "gflops": gflops,
            "stages": len(chain.stages),
            "host_stream_bytes": plan.host_stream_bytes,
            **_pred_fields(pred, us, E),
        })
        _profile_record(plan, pred, us, f"flow_ladder/{name}")
        return us

    hand = operators.build_cfd_chain(p)
    hand_plan = mchain.plan_chain(
        hand, target=target, batch_elements=E, prefetch_depth=1, n_eq=n_eq
    )
    measure("hand_stage_cuts", hand, hand_plan, E=E, n_b=n_b)

    auto = flow.compile(
        source, name=f"cfd_pipeline_p{p}", target=target,
        batch_elements=E, prefetch_depth=1, n_eq=n_eq,
    )
    measure("flow_auto_stages", auto.chain, auto.plan, E=E, n_b=n_b)

    # cost-driven fusion: the stage count made a design axis.  The
    # max_stages=3 budget asks for the paper's 3-module granularity and
    # lets the greedy pass keep erasing boundaries while the planner
    # prices the HBM handoff above the fused roofline.  The checked-in
    # baseline carries max_ratio_vs=hand_stage_cuts: CI requires the
    # auto-fused pipeline to stay within 1.2x of the hand cuts -- a
    # same-machine ratio, so it holds across runner generations.
    fused = flow.compile(
        source, name=f"cfd_pipeline_p{p}", target=target,
        batch_elements=E, prefetch_depth=1, n_eq=n_eq, fuse="auto",
        max_stages=3,
    )
    fspec = fused.plan.fusion
    measure("chain_auto_fused", fused.chain, fused.plan, E=E, n_b=n_b)
    rows[-1].update({"max_ratio_vs": "hand_stage_cuts", "max_ratio": 1.2})

    # the stage-pipelining acceptance ladder: small batches on the
    # 3-stage chain so per-batch dispatch/sync latency -- exactly what
    # staging and the skewed dispatch rings hide -- dominates.  Three
    # rungs decompose the win: K=0 sync-per-batch (the paper's serial
    # baseline), the same K=1 plan run back-to-back (staging only), and
    # the K=1 plan stage-pipelined, so the gated speedup (pipelined vs
    # serial) and the executor's own contribution (vs back-to-back) are
    # both recorded; the skew *semantics* are guarded functionally by
    # the dispatch-order and bitwise tests in tests/test_memory.py.
    sp_E, sp_n_b = 64, 16
    serial_plan = mchain.plan_chain(
        hand, target=target, batch_elements=sp_E, prefetch_depth=0,
        n_eq=sp_E * sp_n_b,
    )
    piped_plan = mchain.plan_chain(
        hand, target=target, batch_elements=sp_E, prefetch_depth=1,
        n_eq=sp_E * sp_n_b,
    )
    us_serial = measure(
        "chain3_serial_stages", hand, serial_plan, E=sp_E, n_b=sp_n_b,
        pipeline_stages=False, reps=5,
    )
    us_b2b = measure(
        "chain3_back_to_back", hand, piped_plan, E=sp_E, n_b=sp_n_b,
        pipeline_stages=False, reps=5,
    )
    us_piped = measure(
        "chain3_stage_pipelined", hand, piped_plan, E=sp_E, n_b=sp_n_b,
        pipeline_stages=True, reps=5,
    )
    # sharded acceptance rung: same E/n_b as the chain3 pair, gradient
    # stage sharded over a 2-device placement in a subprocess
    sh = _run_sharded_rung(p, sp_E, sp_n_b)
    _row("flow_ladder/chain3_sharded_2dev", sh["us_per_batch"],
         f"{sh['gflops']:.3f}GFLOPS;groups={sh['groups']}")
    rows.append({
        "name": "chain3_sharded_2dev",
        "us_per_batch": sh["us_per_batch"], "gflops": sh["gflops"],
        "stages": 3, "host_stream_bytes": sh["host_stream_bytes"],
        **_pred_fields(sh["pred_us"] * 1e-6, sh["us_per_batch"], sp_E),
    })

    speedup = us_serial / us_piped if us_piped else 0.0
    stage_ratio = us_b2b / us_piped if us_piped else 0.0
    _row("flow_ladder/stage_pipelining_speedup", 0.0,
         f"speedup={speedup:.2f}x;serial={us_serial:.0f}us;"
         f"back_to_back={us_b2b:.0f}us;pipelined={us_piped:.0f}us;"
         f"stage_ratio={stage_ratio:.2f}x;"
         f"pred={piped_plan.cost.stage_overlap_speedup:.2f}x")

    path = os.environ.get("FLOW_LADDER_JSON", "flow_ladder.json")
    with open(path, "w") as f:
        json.dump({
            "p": p, "E": E, "n_batches": n_b, "target": target.name,
            "rows": rows,
            "fusion": {
                "groups": [list(g) for g in fspec.groups],
                "n_stages_before": fspec.n_stages_before,
                "n_stages_after": fspec.n_stages_after,
                "t_unfused_s": fspec.t_unfused,
                "t_fused_s": fspec.t_fused,
                "saved_handoff_bytes": fspec.saved_handoff_bytes,
            },
            "stage_pipelining": {
                "E": sp_E, "n_batches": sp_n_b,
                "serial_us_per_batch": us_serial,
                "back_to_back_us_per_batch": us_b2b,
                "pipelined_us_per_batch": us_piped,
                "speedup": speedup,
                "stage_ratio": stage_ratio,
                # the acceptance floor CI's gate enforces (ratio of two
                # same-machine runs: robust across runner generations).
                # 1.0 = pipelining must never lose to the serial
                # schedule; the absolute win depends on the host's
                # dispatch/sync latency (2x on slow-dispatch runners,
                # near-parity when sync is cheap), so a higher floor
                # would gate on the runner, not the executor.
                "min_speedup": 1.0,
                # the executor's own floor: stage-pipelined execution of
                # the same plan must not fall behind back-to-back by
                # more than measurement noise
                "min_stage_ratio": 0.9,
            },
        }, f, indent=2)


def lm_throughput() -> None:
    import repro.configs as configs
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.runtime.train import init_train_state, make_train_step

    cfg = configs.get_smoke("qwen3-14b")
    model = build_model(cfg, attn_impl="xla")
    state = init_train_state(model, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, AdamWConfig()))
    B, T = 8, 128
    batch = {
        "tokens": jnp.ones((B, T), jnp.int32),
        "labels": jnp.ones((B, T), jnp.int32),
    }

    def one():
        nonlocal state
        state, m = step(state, batch)
        return m["loss"]

    t = _time(one, warmup=2, iters=5)
    _row("lm/train_step_smoke", t * 1e6, f"{B * T / t:.0f}tok/s")

    cache = model.init_cache(B, 256)
    logits, cache = jax.jit(model.prefill)(
        state["params"], {"tokens": batch["tokens"]}, cache
    )
    dstep = jax.jit(model.decode_step)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)

    def dec():
        return dstep(state["params"], tok, cache, jnp.int32(T))

    td = _time(dec, warmup=2, iters=10)
    _row("lm/decode_step_smoke", td * 1e6, f"{B / td:.0f}tok/s")


BENCHES = {
    "tab2_ladder": tab2_ladder,
    "fig16_precision": fig16_precision,
    "fig17_multicu": fig17_multicu,
    "fig19_kernels": fig19_kernels,
    "memplan_ladder": memplan_ladder,
    "chain_ladder": chain_ladder,
    "flow_ladder": flow_ladder,
    "lm_throughput": lm_throughput,
}


def main() -> None:
    workers = {
        "_sharded_worker": _sharded_worker,
        "_hetero_worker": _hetero_worker,
    }
    if len(sys.argv) > 1 and sys.argv[1] in workers:
        workers[sys.argv[1]](
            int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
        )
        return
    enable_compile_cache()
    names = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()


if __name__ == "__main__":
    main()
