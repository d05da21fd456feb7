"""CFD operators end-to-end vs the numpy oracles, all backends."""
import numpy as np
import pytest

from conftest import helmholtz_chain
from repro.cfd import operators, reference, simulation
from repro.memory import channels
from repro.memory.chain import plan_chain


@pytest.mark.parametrize("backend", ["xla", "staged"])
@pytest.mark.parametrize("p", [5, 7])
def test_inverse_helmholtz_backends(backend, p, rng):
    c = operators.build_inverse_helmholtz(p, backend=backend)
    E = 6
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    D = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    got = np.asarray(c.batched_fn({"S": S, "D": D, "u": u})["v"])
    want = reference.inverse_helmholtz_batch(
        S.astype(np.float64), D.astype(np.float64), u.astype(np.float64)
    )
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_interpolation(rng):
    n, m = 7, 9
    c = operators.build_interpolation(n, m)
    A = rng.uniform(-1, 1, (m, n)).astype(np.float32)
    u = rng.uniform(-1, 1, (3, n, n, n)).astype(np.float32)
    got = np.asarray(c.batched_fn({"A": A, "u": u})["v"])
    for e in range(3):
        want = reference.interpolation(
            A.astype(np.float64), u[e].astype(np.float64)
        )
        np.testing.assert_allclose(got[e], want, rtol=3e-4, atol=3e-4)


def test_gradient(rng):
    nx, ny, nz = 8, 7, 6
    c = operators.build_gradient(nx, ny, nz)
    Dx = rng.uniform(-1, 1, (nx, nx)).astype(np.float32)
    Dy = rng.uniform(-1, 1, (ny, ny)).astype(np.float32)
    Dz = rng.uniform(-1, 1, (nz, nz)).astype(np.float32)
    u = rng.uniform(-1, 1, (2, nx, ny, nz)).astype(np.float32)
    out = c.batched_fn({"Dx": Dx, "Dy": Dy, "Dz": Dz, "u": u})
    for e in range(2):
        gx, gy, gz = reference.gradient(
            *(a.astype(np.float64) for a in (Dx, Dy, Dz)),
            u[e].astype(np.float64),
        )
        np.testing.assert_allclose(np.asarray(out["gx"])[e], gx, rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(out["gy"])[e], gy, rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(out["gz"])[e], gz, rtol=3e-4, atol=3e-4)


def test_simulation_driver_batching():
    chain = helmholtz_chain(5)
    plan = plan_chain(chain, batch_elements=128, n_eq=512)
    assert plan.batches_for(512) == 4
    res = simulation.run_chain(chain, plan, n_eq=512, max_batches=2)
    assert res.elements == 256
    assert np.isfinite(res.checksums["helmholtz.v"])


def test_simulation_double_buffer_equivalence():
    """Ping/pong staging must not change results (paper Fig. 14a)."""
    chain = helmholtz_chain(5)
    a, b = (
        simulation.run_chain(
            chain,
            plan_chain(
                chain, batch_elements=64, prefetch_depth=depth, n_eq=256
            ),
            n_eq=256, max_batches=3,
        )
        for depth in (1, 0)
    )
    assert abs(
        a.checksums["helmholtz.v"] - b.checksums["helmholtz.v"]
    ) < 1e-3


def test_batch_for_channel_matches_paper_sizing():
    """Paper: E = elements whose I/O fits one 256 MB HBM pseudo-channel."""
    E = helmholtz_chain(11).auto_batch_elements(
        channels.ALVEO_U280, bytes_per_scalar=8
    )
    assert channels.ALVEO_U280.channel_bytes == 256 * 2 ** 20
    assert E == (256 * 2 ** 20) // (3 * 11 ** 3 * 8)


def test_opcount_model():
    assert reference.paper_flops_per_element(11) == 177023
    assert reference.paper_flops_per_element(7) == 29155


# ---------------------------------------------------------------------------
# CEED bake-off problem BP3 through the flow, every staging
# ---------------------------------------------------------------------------

BP3_G = ("Grr", "Grs", "Grt", "Gss", "Gst", "Gtt")


def _bp3_source(n, q):
    """``examples/ceed_bp3.cfd`` at n nodes and q points a direction."""
    import pathlib

    src = (pathlib.Path(__file__).parent.parent / "examples"
           / "ceed_bp3.cfd").read_text()
    return (src.replace("[12 11]", f"[{q} {n}]")
            .replace("[12 12 12]", f"[{q} {q} {q}]")
            .replace("[11 11 11]", f"[{n} {n} {n}]"))


#: float32 with full-precision products reads ~1.2e-7 here, max |err| over
#: max |y| (each y sums 3 q^3 products of two rounded contractions); 1e-6
#: leaves 8x room, and a bfloat16 rounding of the stage (8 significant
#: bits, ~2e-3 relative) fails it a thousandfold
BP3_TOL = 1e-6


@pytest.mark.parametrize("max_stages", [None, 1, 2, 3, 4, 5, 6, 7, 8],
                         ids=lambda m: f"max_stages={m}")
def test_ceed_bp3_through_flow_matches_reference(max_stages, rng):
    import jax.numpy as jnp

    from repro import flow
    from repro.memory import channels

    n, q, E, n_b = 4, 5, 8, 3
    N = E * n_b
    system = flow.compile(_bp3_source(n, q), name="bp3",
                          max_stages=max_stages, target=channels.CPU_HOST,
                          batch_elements=E, n_eq=N)
    if max_stages is not None:
        assert len(system.chain.stages) <= max_stages
    B, Bd = (rng.uniform(-1, 1, (q, n)).astype(np.float32) for _ in "ab")
    by_var = {"u": rng.uniform(-1, 1, (N, n, n, n)).astype(np.float32)}
    by_var.update({g: rng.uniform(-1, 1, (N, q, q, q)).astype(np.float32)
                   for g in BP3_G})
    chain = system.chain
    inputs = {f"{s.name}.{v}": by_var[v]
              for i, s in enumerate(chain.stages)
              for v, _ in chain.host_element_inputs(i)}
    res = system.run(inputs=inputs, shared={"B": B, "Bd": Bd},
                     collect_outputs=True)
    (got,) = [a for k, a in res.outputs.items() if k.endswith(".y")]
    want = reference.ceed_bp3(B, Bd, [by_var[g] for g in BP3_G],
                              by_var["u"])
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < BP3_TOL
    rounded = np.asarray(jnp.asarray(got, jnp.bfloat16), np.float64)
    assert np.max(np.abs(rounded - want)) / scale > 100 * BP3_TOL


def test_ceed_bp3_reference_is_the_stiffness_form(rng):
    """w . y(u) is the quadrature sum of (grad w) . G (grad u): the
    stiffness form, symmetric in u and w for a symmetric G."""
    n, q = 3, 4
    B, Bd = rng.uniform(-1, 1, (q, n)), rng.uniform(-1, 1, (q, n))
    u, w = rng.uniform(-1, 1, (2, n, n, n))
    G = rng.uniform(-1, 1, (6, q, q, q))
    rr, rs, rt, ss, st, tt = G
    full = [[rr, rs, rt], [rs, ss, st], [rt, st, tt]]

    def grad(x):
        return [np.einsum("il,jm,kn,lmn->ijk", X, Y, Z, x)
                for X, Y, Z in ((Bd, B, B), (B, Bd, B), (B, B, Bd))]

    gw, gu = grad(w), grad(u)
    form = sum(np.sum(gw[a] * full[a][b] * gu[b])
               for a in range(3) for b in range(3))
    assert np.sum(w * reference.ceed_bp3(B, Bd, G, u)) == pytest.approx(form)
    assert np.sum(u * reference.ceed_bp3(B, Bd, G, w)) == pytest.approx(form)
