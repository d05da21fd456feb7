"""Compile the main path's kernels for a described TPU v5e, no chip needed.

The TPU compiler is installed with jaxlib, so a ``v5e:2x2`` topology can
be described and programs compiled for one of its chips.  This catches
what interpret mode cannot: Mosaic refusing a kernel (shape casts across
the tiled minor dimensions, VMEM over the limit) and programs that do
not fit the chip's HBM.  Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import flow
from repro.flow import patterns
from repro.kernels import gemm
from repro.kernels.helmholtz import helmholtz
from repro.memory import channels, layout

EXAMPLES = pathlib.Path(__file__).resolve().parent.parent / "examples"
N_EQ = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _fits_chip(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used <= channels.TPU_V5E.hbm_bytes, used
    return used


def _system(name, **kw):
    return flow.compile(
        (EXAMPLES / name).read_text(), target="tpu-v5e", n_eq=N_EQ, **kw
    )


def test_kernel_vmem_limit_is_the_planner_budget(one_chip, monkeypatch):
    """The flow hands every Pallas stage the budget its blocks were
    sized against, and the kernel lowers with it as Mosaic's limit."""
    seen = []
    real = patterns.gemm_kernels.make_pallas_impl

    def spy(recipe, **kw):
        seen.append(kw["vmem_limit_bytes"])
        return real(recipe, **kw)

    monkeypatch.setattr(patterns.gemm_kernels, "make_pallas_impl", spy)
    system = _system("cfd_pipeline.cfd", backend="pallas")
    budget = layout.vmem_budget_bytes(channels.TPU_V5E)
    assert seen and set(seen) == {budget}
    st, sp = system.chain.stages[0], system.plan.stages[0]
    recipe = patterns.match_gemm_chain(st.program)
    e = system.plan.batch_elements
    args = {
        name: _spec(((e,) if is_elem else ()) + shape, one_chip)
        for name, shape, is_elem in recipe.inputs
    }
    lowered = jax.jit(
        lambda env: gemm.gemm_chain_pallas(
            recipe, env, block_elements=sp.block_elements,
            vmem_limit_bytes=budget,
        )
    ).lower(args).as_text()
    assert str(budget) in lowered


@pytest.mark.parametrize("p", [7, 11])
def test_helmholtz_kernel_compiles_at_planner_block(p, one_chip):
    src = (EXAMPLES / "inverse_helmholtz.cfd").read_text().replace(
        "11", str(p)
    )
    system = flow.compile(src, target="tpu-v5e", n_eq=N_EQ,
                          backend="pallas", max_stages=1)
    assert system.backends == ("pallas",)
    assert patterns.match_inverse_helmholtz(system.chain.stages[0].program)
    e = system.plan.batch_elements
    blk = system.plan.stages[0].block_elements
    limit = layout.vmem_budget_bytes(system.target)
    elem = _spec((e, p, p, p), one_chip)
    compiled = _compile(
        lambda S, D, u: helmholtz.inverse_helmholtz_pallas(
            S, D, u, block_elements=blk, vmem_limit_bytes=limit
        ),
        _spec((p, p), one_chip), elem, elem,
    )
    assert "tpu_custom_call" in compiled.as_text()
    _fits_chip(compiled)


def test_gemm_chain_kernels_compile_for_every_pipeline_stage(one_chip):
    system = _system("cfd_pipeline.cfd", backend="pallas")
    assert set(system.backends) == {"pallas"}
    e = system.plan.batch_elements
    limit = layout.vmem_budget_bytes(system.target)
    for st, sp in zip(system.chain.stages, system.plan.stages):
        recipe = patterns.match_gemm_chain(st.program)
        assert recipe is not None, st.name
        args = {
            name: _spec(((e,) if is_elem else ()) + shape, one_chip)
            for name, shape, is_elem in recipe.inputs
        }
        compiled = _compile(
            lambda env, r=recipe, b=sp.block_elements:
                gemm.gemm_chain_pallas(
                    r, env, block_elements=b, vmem_limit_bytes=limit
                ),
            args,
        )
        assert "tpu_custom_call" in compiled.as_text(), st.name
        _fits_chip(compiled)


def test_xla_stage_compiles_at_planner_batch(one_chip):
    system = _system("cfd_pipeline.cfd")
    e = system.plan.batch_elements
    assert e == 34816
    stage = system.chain.stages[0]
    elem = set(stage.program.element_vars)
    env = {
        name: _spec(((e,) if name in elem else ()) + tuple(v.shape),
                    one_chip)
        for name, v in stage.program.inputs.items()
    }
    compiled = _compile(stage.compiled.batched_fn, env)
    assert "tpu_custom_call" not in compiled.as_text()
    _fits_chip(compiled)
