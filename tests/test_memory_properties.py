"""Property-based invariants for memory.layout (hypothesis; skipped
cleanly where hypothesis is not installed):

  * padded record sizes are burst-multiples (and minimal),
  * the chain's auto_batch_elements never overflows a pseudo-channel,
  * channel assignment never double-books a channel within one replica
    set, for single programs (one-stage chains) and for chains sharing
    one allocator.
"""
import functools

import pytest

hypothesis = pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from conftest import helmholtz_chain  # noqa: E402
from repro.core import emit, ir  # noqa: E402
from repro.memory import channels, layout  # noqa: E402
from repro.memory.chain import ProgramChain, plan_chain  # noqa: E402


#: the policy whose scalars are ``bps`` bytes wide
POLICY_OF_WIDTH = {2: "bfloat16", 4: "float32", 8: "float64"}


def _one_stage(prog):
    """A random program as a one-stage chain (how the planner sees a
    single operator)."""
    return ProgramChain([("s", emit.compile_program(prog))])


@functools.lru_cache(maxsize=None)
def _helmholtz(p):
    """The one-stage inverse Helmholtz chain, compiled once per degree."""
    return helmholtz_chain(p)


# -- strategies --------------------------------------------------------------

bursts = st.sampled_from([1, 2, 16, 64, 128, 512])
targets = st.builds(
    lambda burst, n_ch, cap_mib: channels.ALVEO_U280.with_(
        burst_bytes=burst, n_channels=n_ch,
        hbm_bytes=n_ch * cap_mib * 2 ** 20,
    ),
    burst=bursts,
    n_ch=st.integers(1, 64),
    cap_mib=st.integers(1, 512),
)


@st.composite
def small_programs(draw):
    """Random multi-stream programs: k element inputs of assorted shapes,
    each transposed into an element output, plus optional shared
    operands -- enough structure to exercise every layout path."""
    n_elem = draw(st.integers(1, 4))
    n_shared = draw(st.integers(0, 2))
    inputs = {}
    outputs = {}
    elem_vars = []
    for i in range(n_elem):
        shape = tuple(
            draw(st.integers(1, 12))
            for _ in range(draw(st.integers(1, 3)))
        )
        x = ir.Input(shape=shape, name=f"x{i}")
        inputs[f"x{i}"] = x
        perm = list(range(len(shape)))[::-1]
        outputs[f"y{i}"] = ir.transpose(x, perm)
        elem_vars += [f"x{i}", f"y{i}"]
    for i in range(n_shared):
        shape = (draw(st.integers(1, 8)), draw(st.integers(1, 8)))
        inputs[f"s{i}"] = ir.Input(shape=shape, name=f"s{i}")
    return ir.Program(
        inputs=inputs, outputs=outputs, element_vars=tuple(elem_vars)
    )


# -- padding -----------------------------------------------------------------


@given(nbytes=st.integers(0, 1 << 20), burst=bursts)
def test_pad_to_burst_is_minimal_burst_multiple(nbytes, burst):
    t = channels.ALVEO_U280.with_(burst_bytes=burst)
    padded = channels.pad_to_burst(nbytes, t)
    assert padded % burst == 0
    assert padded >= nbytes
    assert padded - nbytes < burst  # minimal: one burst of slack at most


@given(prog=small_programs(), target=targets,
       bps=st.sampled_from([2, 4, 8]))
@settings(max_examples=50, deadline=None)
def test_padded_records_are_burst_multiples(prog, target, bps):
    plan = plan_chain(
        _one_stage(prog), target=target, policy=POLICY_OF_WIDTH[bps],
        batch_elements=7, prefetch_depth=1,
    )
    for b in plan.buffers:
        assert b.padded_bytes % target.burst_bytes == 0
        assert b.padded_bytes >= b.element_bytes
        if b.role != "shared":
            assert b.batch_bytes == b.padded_bytes * 7


# -- batch sizing ------------------------------------------------------------


@given(prog=small_programs(), target=targets,
       bps=st.sampled_from([2, 4, 8]),
       n_eq=st.one_of(st.none(), st.integers(1, 1 << 22)))
@settings(max_examples=50, deadline=None)
def test_auto_batch_never_overflows_channel(prog, target, bps, n_eq):
    e = _one_stage(prog).auto_batch_elements(
        target, bytes_per_scalar=bps, n_eq=n_eq
    )
    per = layout.stream_bytes_per_element(prog, bps)
    assert e >= 1
    if n_eq is not None:
        assert e <= max(1, n_eq)
    # E fills at most one pseudo-channel; E=1 is the floor when even a
    # single element's streams exceed the channel (capacity feasibility
    # is the DSE's global check, not the sizing rule's)
    if e > 1:
        assert e * per <= target.channel_bytes
    if n_eq is None and (e + 1) * per <= target.channel_bytes:
        pytest.fail("E not maximal for the channel")


# -- channel assignment ------------------------------------------------------


@given(prog=small_programs(), target=targets,
       depth=st.integers(0, 4))
@settings(max_examples=50, deadline=None)
def test_channels_never_double_booked(prog, target, depth):
    plan = plan_chain(
        _one_stage(prog), target=target, batch_elements=3,
        prefetch_depth=depth,
    )
    for b in plan.buffers:
        assert len(b.channels) == len(set(b.channels)), b.name
        assert all(0 <= c < target.n_channels for c in b.channels)


@given(n_channels=st.integers(1, 64),
       takes=st.lists(st.integers(1, 100), min_size=1, max_size=20))
def test_allocator_takes_are_duplicate_free(n_channels, takes):
    alloc = layout.ChannelAllocator(n_channels)
    for count in takes:
        ids = alloc.take(count)
        assert len(ids) == len(set(ids))
        assert len(ids) == min(max(1, count), n_channels)


@given(p=st.sampled_from([3, 5, 7]), e=st.integers(1, 4096),
       target=targets)
@settings(max_examples=25, deadline=None)
def test_plan_blocks_divide_e_and_fit_vmem(p, e, target):
    plan = plan_chain(_helmholtz(p), target=target, batch_elements=e)
    sp = plan.stages[0]
    assert sp.block_elements >= 1
    assert plan.batch_elements % sp.block_elements == 0
    # the ALVEO-derived targets keep 43 MiB of PLM, so even the BE=1
    # floor fits; the chosen block must always respect the capacity
    assert sp.block_working_set_bytes <= target.vmem_bytes


@given(depth=st.integers(0, 3), e=st.integers(1, 512))
@settings(max_examples=20, deadline=None)
def test_chain_buffers_unique_names_and_channels(depth, e):
    from repro.cfd import operators

    chain = operators.build_cfd_chain(5)
    plan = plan_chain(
        chain, target=channels.ALVEO_U280, batch_elements=e,
        prefetch_depth=depth,
    )
    names = [b.name for b in plan.buffers]
    assert len(names) == len(set(names))
    for b in plan.buffers:
        assert len(b.channels) == len(set(b.channels))
