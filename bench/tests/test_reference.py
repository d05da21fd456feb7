"""``bench/reference.py`` against the program: the repository's own
float64 oracle, and what ``flow.compile`` runs on the CPU at small p."""
import numpy as np
import pytest

from bench import check, reference
from bench.programs import cfd_pipeline, inverse_helmholtz

P = 5


def _data(program, n, p=P, seed=0):
    rng = np.random.default_rng(seed)
    shared = {k: rng.uniform(-1, 1, (p, p)) for k in program.SHARED}
    ins = {k: rng.uniform(-1, 1, (n, p, p, p))
           for k in program.ELEMENT_INPUTS}
    return shared, ins


def test_matches_the_repositorys_float64_oracle():
    from repro.cfd import reference as oracle

    shared, ins = _data(cfd_pipeline, 6)
    got = cfd_pipeline.outputs(reference, shared, ins)
    w = oracle.interpolation_batch(shared["A"], ins["u"])
    grads = [oracle.gradient(shared["Dx"], shared["Dy"], shared["Dz"], e)
             for e in w]
    gx, gy, gz = (np.stack(g) for g in zip(*grads))
    want = {"gy": gy, "gz": gz,
            "v": oracle.inverse_helmholtz_batch(shared["S"], ins["D"], gx)}
    for q in cfd_pipeline.OUTPUTS:
        np.testing.assert_allclose(got[q], want[q], rtol=1e-12, atol=1e-12)
    v = inverse_helmholtz.outputs(reference, shared, ins)["v"]
    np.testing.assert_allclose(
        v, oracle.inverse_helmholtz_batch(shared["S"], ins["D"], ins["u"]),
        rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("example,program,backend", [
    ("cfd_pipeline.cfd", cfd_pipeline, "xla"),
    ("inverse_helmholtz.cfd", inverse_helmholtz, "pallas"),
])
def test_matches_the_program_at_small_p(example, program, backend):
    from pathlib import Path

    from repro import flow

    src = (Path(__file__).resolve().parents[2] / "examples" / example
           ).read_text().replace("11", str(P))
    system = flow.compile(src, target="tpu-v5e", n_eq=256, profile=None,
                          backend=backend, batch_elements=128)
    chain = system.chain
    shared, ins = _data(program, 256, seed=1)
    shared = {k: v.astype(np.float32) for k, v in shared.items()}
    inputs = {f"{s.name}.{n}": ins[n].astype(np.float32)
              for i, s in enumerate(chain.stages)
              for n, _ in chain.host_element_inputs(i)}
    res = system.run(inputs=inputs, shared=shared, collect_outputs=True)
    outs = {q.split(".", 1)[1]: a for q, a in res.outputs.items()}
    want = check.reference_outputs(
        program, shared, {k: v.astype(np.float32) for k, v in ins.items()})
    for q in program.OUTPUTS:
        assert check.rel_err(outs[q], want[q]) < 1e-6
