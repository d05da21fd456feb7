"""Distribution layer: sharding rules (inline) + multi-device semantics
(subprocess with forced host device count)."""
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import subprocess_env
from repro.distributed import compression, sharding
from repro.launch import mesh as mesh_mod


# -- sharding rules (single device: rules are pure functions) -----------------

def _mesh11():
    return mesh_mod.make_local_mesh(1)


def test_param_rules_match_expected_axes():
    mesh = _mesh11()
    cases = {
        "embed/tok": (("model", None), 2),
        "blocks/attn/wq/w": ((None, "model"), 2),
        "blocks/attn/wo/w": (("model", None), 2),
        "blocks/mlp/gate/w": ((None, "model"), 2),
        "blocks/mlp/down/w": (("model", None), 2),
        "blocks/moe/w_gate": (("model", None, None), 3),
        "blocks/mamba/in_proj/w": ((None, "model"), 2),
        "blocks/ln1/scale": ((), 1),
    }
    for path, (want, ndim) in cases.items():
        spec = sharding.spec_for_param(path, ndim, mesh)
        got = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
        want_padded = (None,) * (ndim - len(want)) + tuple(
            w if w in mesh.axis_names else None for w in want
        )
        assert got == want_padded, (path, got, want_padded)


def test_stacked_leading_axis_left_unsharded():
    mesh = _mesh11()
    spec = sharding.spec_for_param("blocks/attn/wq/w", 3, mesh)
    assert tuple(spec)[0] is None


def test_divisibility_fallback():
    mesh = mesh_mod.make_local_mesh(1)  # model axis size 1: all divisible
    spec = sharding._divisible((6, 64), P(None, "model"), mesh)
    assert tuple(spec) == (None, "model")


def test_quantize_roundtrip_bound(rng):
    g = rng.normal(size=(128,)).astype(np.float32)
    q, scale = compression.quantize(g)
    back = np.asarray(compression.dequantize(q, scale))
    assert np.abs(back - g).max() <= float(scale) * 0.5 + 1e-7


def test_error_feedback_reduces_bias(rng):
    """With error feedback the time-averaged quantized gradient converges
    to the true mean (unbiasedness over steps)."""
    g = rng.normal(size=(256,)).astype(np.float32) * 0.01
    import jax.numpy as jnp
    err = jnp.zeros_like(g)
    acc = np.zeros_like(g)
    n = 50
    for _ in range(n):
        q, s, err = compression.compress_with_feedback(jnp.asarray(g), err)
        acc += np.asarray(compression.dequantize(q, s))
    assert np.abs(acc / n - g).max() < 1e-4


# -- multi-device semantics (subprocess) ---------------------------------------

MULTIDEV_SCRIPT = textwrap.dedent("""
    import os, json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.runtime.train import make_train_step, init_train_state
    from repro.distributed import sharding as sr, pipeline as pp, compression
    from repro.launch import mesh as mesh_mod
    from jax import shard_map

    out = {}
    assert len(jax.devices()) == 8, jax.devices()

    # (a) sharded train step == single-device train step
    cfg = configs.get_smoke("internlm2-1.8b")
    model = build_model(cfg, attn_impl="xla")
    state = init_train_state(model, jax.random.PRNGKey(0))
    batch = {
        "tokens": jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (8, 1)),
        "labels": jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (8, 1)),
    }
    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3)))
    _, m_single = step(state, batch)

    mesh = mesh_mod.make_local_mesh(model_axis=2)   # (4, 2)
    params_sh = sr.param_shardings(state["params"], mesh)
    state_sh = {
        "params": params_sh,
        "opt_state": {"mu": params_sh, "nu": params_sh,
                      "step": sr.replicated(mesh)},
        "step": sr.replicated(mesh),
    }
    batch_sh = sr.batch_shardings(batch, mesh)
    with mesh:
        step_sharded = jax.jit(
            make_train_step(model, AdamWConfig(lr=1e-3)),
            in_shardings=(state_sh, batch_sh),
        )
        state_dev = jax.device_put(state, state_sh)
        batch_dev = jax.device_put(batch, batch_sh)
        _, m_shard = step_sharded(state_dev, batch_dev)
    out["loss_single"] = float(m_single["loss"])
    out["loss_sharded"] = float(m_shard["loss"])

    # (b) pipeline_forward == direct stacked apply
    S, L, mb, M, d = 4, 4, 2, 4, 8
    meshp = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("pod",))
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (S, d, d)) * 0.3

    def stage_fn(wi, x):
        return jnp.tanh(x @ wi)

    x = jax.random.normal(jax.random.PRNGKey(2), (M, mb, d))
    got = pp.pipeline_forward(stage_fn, w, x, mesh=meshp, stage_axis="pod")
    want = x
    for s in range(S):
        want = jnp.tanh(want @ w[s])
    out["pp_err"] = float(jnp.abs(got - want).max())

    # (c) compressed psum over 'pod'
    meshc = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("pod",))
    g = jax.random.normal(jax.random.PRNGKey(3), (4, 64)) * 0.01
    err0 = jnp.zeros((4, 64))

    def red(gl, el):
        m, ne = compression.compressed_psum(gl[0], el[0], "pod")
        return m[None], ne[None]

    mfn = shard_map(red, mesh=meshc, in_specs=(P("pod"), P("pod")),
                    out_specs=(P("pod"), P("pod")), check_vma=False)
    mean, _ = mfn(g, err0)
    true_mean = jnp.mean(g, axis=0)
    out["psum_err"] = float(jnp.abs(mean[0] - true_mean).max())
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_multidevice_semantics():
    res = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        env=subprocess_env(8), capture_output=True, text=True, timeout=420,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert abs(out["loss_single"] - out["loss_sharded"]) < 1e-3
    assert out["pp_err"] < 1e-5
    assert out["psum_err"] < 2e-4
