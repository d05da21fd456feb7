"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
shape/dtype sweeps as required per kernel."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import reference
from repro.kernels import gemm
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention.ref import attention_ref
from repro.kernels.helmholtz import ops as hh_ops
from repro.memory import channels, layout


# ---------------------------------------------------------------------------
# helmholtz kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [3, 5, 7, 11])
@pytest.mark.parametrize("be", [2, 4])
def test_helmholtz_kernel_shapes(p, be, rng):
    E = 8
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    D = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    got = np.asarray(
        hh_ops.inverse_helmholtz(S, D, u, impl="interpret", block_elements=be)
    )
    want = reference.inverse_helmholtz_batch(
        S.astype(np.float64), D.astype(np.float64), u.astype(np.float64)
    )
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_helmholtz_kernel_bf16(rng):
    p, E = 7, 4
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    D = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    got = np.asarray(
        hh_ops.inverse_helmholtz(
            jnp.asarray(S, jnp.bfloat16), jnp.asarray(D, jnp.bfloat16),
            jnp.asarray(u, jnp.bfloat16), impl="interpret", block_elements=4,
        ).astype(jnp.float32)
    )
    want = reference.inverse_helmholtz_batch(
        S.astype(np.float64), D.astype(np.float64), u.astype(np.float64)
    )
    # bf16 storage, f32 accumulation: coarse bound
    np.testing.assert_allclose(got, want, rtol=0.15, atol=0.3)


def test_helmholtz_kernel_rejects_ragged_blocks(rng):
    p = 5
    S = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    D = rng.uniform(-1, 1, (6, p, p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (6, p, p, p)).astype(np.float32)
    with pytest.raises(ValueError):
        hh_ops.inverse_helmholtz(S, D, u, impl="interpret", block_elements=4)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

SWEEP = [
    # B, Hq, Hkv, Tq, Tk, d, causal
    (2, 4, 2, 64, 64, 32, True),
    (1, 8, 2, 32, 128, 16, True),     # GQA 4:1, cross-length causal
    (2, 2, 2, 64, 64, 64, False),
    (1, 4, 1, 128, 128, 32, True),    # MQA
    (1, 2, 2, 16, 16, 128, True),
]


@pytest.mark.parametrize("case", SWEEP)
def test_flash_attention_vs_oracle(case, rng):
    B, Hq, Hkv, Tq, Tk, d, causal = case
    q = rng.normal(size=(B, Hq, Tq, d)).astype(np.float32)
    k = rng.normal(size=(B, Hkv, Tk, d)).astype(np.float32)
    v = rng.normal(size=(B, Hkv, Tk, d)).astype(np.float32)
    want = np.asarray(
        attention_ref(
            q.reshape(B * Hq, Tq, d), k.reshape(B * Hkv, Tk, d),
            v.reshape(B * Hkv, Tk, d),
            n_q_heads=Hq, n_kv_heads=Hkv, causal=causal,
        )
    ).reshape(B, Hq, Tq, d)
    got = np.asarray(
        attn_ops.multi_head_attention(
            q, k, v, causal=causal, impl="interpret",
            block_q=16, block_k=32,
        )
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_block_size_invariance(rng):
    B, Hq, Hkv, T, d = 1, 2, 1, 128, 32
    q = rng.normal(size=(B, Hq, T, d)).astype(np.float32)
    k = rng.normal(size=(B, Hkv, T, d)).astype(np.float32)
    v = rng.normal(size=(B, Hkv, T, d)).astype(np.float32)
    outs = [
        np.asarray(attn_ops.multi_head_attention(
            q, k, v, impl="interpret", block_q=bq, block_k=bk,
        ))
        for bq, bk in [(16, 16), (32, 64), (128, 128)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-5, atol=2e-5)


def test_flash_attention_xla_path_matches(rng):
    B, Hq, Hkv, T, d = 2, 4, 2, 64, 32
    q = rng.normal(size=(B, Hq, T, d)).astype(np.float32)
    k = rng.normal(size=(B, Hkv, T, d)).astype(np.float32)
    v = rng.normal(size=(B, Hkv, T, d)).astype(np.float32)
    a = np.asarray(attn_ops.multi_head_attention(q, k, v, impl="xla"))
    b = np.asarray(attn_ops.multi_head_attention(
        q, k, v, impl="interpret", block_q=16, block_k=16))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# tiled GEMM-chain kernel
# ---------------------------------------------------------------------------

def _interp_recipe(p):
    """Interpolation: three mode contractions of A against u."""
    return gemm.GemmRecipe(
        p=p,
        inputs=(("A", (p, p), False), ("u", (p, p, p), True)),
        ops=(
            ("contract", 1, 0, 0, 0, (0, 1, 2)),
            ("contract", 2, 0, 1, 0, (0, 1, 2)),
            ("contract", 3, 0, 2, 0, (0, 1, 2)),
        ),
        outputs=(("w", 4),),
    )


def _interp_oracle(A, u):
    return np.einsum("li,mj,nk,elmn->eijk", A, A, A, u)


@pytest.mark.parametrize("p", [3, 5, 11])
@pytest.mark.parametrize("be", [2, 4])
def test_gemm_chain_interpolation_vs_oracle(p, be, rng):
    E = 8
    A = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    recipe = _interp_recipe(p)
    want = _interp_oracle(A.astype(np.float64), u.astype(np.float64))
    for impl in ("xla", "interpret"):
        got = np.asarray(gemm.gemm_chain(
            recipe, {"A": A, "u": u}, impl=impl, block_elements=be,
        )["w"])
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_gemm_chain_perm_moves_free_axis(rng):
    """A gradient-style contraction whose output reorders the element
    axes: y[e,f,a,c] = sum_l M[l,f] u[e,a,l,c] (free axis moved to the
    front via the recipe's perm, not left in place)."""
    p, E = 5, 4
    M = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    recipe = gemm.GemmRecipe(
        p=p,
        inputs=(("M", (p, p), False), ("u", (p, p, p), True)),
        ops=(("contract", 1, 0, 1, 0, (1, 0, 2)),),
        outputs=(("y", 2),),
    )
    want = np.einsum("lf,ealc->efac", M, u)
    for impl in ("xla", "interpret"):
        got = np.asarray(gemm.gemm_chain(
            recipe, {"M": M, "u": u}, impl=impl, block_elements=2,
        )["y"])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gemm_chain_ewise_and_multi_output(rng):
    """Elementwise ops between matched values plus two outputs sharing
    the chain: w = A.u (mode 0), z = (w * u) scaled by 0.5."""
    p, E = 4, 8
    A = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    recipe = gemm.GemmRecipe(
        p=p,
        inputs=(("A", (p, p), False), ("u", (p, p, p), True)),
        ops=(
            ("contract", 1, 0, 0, 0, (0, 1, 2)),
            ("ewise", "mul", 2, 1, None),
            ("ewise", "scale", 3, -1, 0.5),
        ),
        outputs=(("w", 2), ("z", 4)),
    )
    w = np.einsum("li,elmn->eimn", A, u)
    for impl in ("xla", "interpret"):
        got = gemm.gemm_chain(
            recipe, {"A": A, "u": u}, impl=impl, block_elements=4,
        )
        np.testing.assert_allclose(
            np.asarray(got["w"]), w, rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(got["z"]), 0.5 * w * u, rtol=1e-5, atol=1e-5
        )


def test_gemm_chain_block_size_invariance(rng):
    p, E = 5, 8
    A = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (E, p, p, p)).astype(np.float32)
    recipe = _interp_recipe(p)
    outs = [
        np.asarray(gemm.gemm_chain(
            recipe, {"A": A, "u": u}, impl="interpret", block_elements=be,
        )["w"])
        for be in (1, 2, 8)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6, atol=1e-6)


def test_gemm_chain_rejects_ragged_blocks(rng):
    p = 3
    A = rng.uniform(-1, 1, (p, p)).astype(np.float32)
    u = rng.uniform(-1, 1, (6, p, p, p)).astype(np.float32)
    with pytest.raises(ValueError, match="not divisible"):
        gemm.gemm_chain(
            _interp_recipe(p), {"A": A, "u": u},
            impl="interpret", block_elements=4,
        )


def test_gemm_recipe_flops_match_ir():
    """The recipe's flop model agrees with the IR's on the matched
    interpolation stage (3 contractions of 2*p^4 each)."""
    p = 7
    recipe = _interp_recipe(p)
    assert recipe.flops_per_element() == 3 * 2 * p ** 4
    assert recipe.slot_shape(4) == (p, p, p)


# ---------------------------------------------------------------------------
# CHARM-style tile candidates (cdse/cdac)
# ---------------------------------------------------------------------------

def _plm_target(vmem_bytes):
    """An untiled (dataflow PLM) target with a round roofline."""
    return channels.ALVEO_U280.with_(
        vmem_bytes=vmem_bytes, peak_flops=1e12, hbm_bw=400e9
    )


def test_tile_candidates_filter_class_and_rank():
    recipe = _interp_recipe(11)
    vmem = 16 * 2 ** 20
    target = _plm_target(vmem)
    cands = gemm.tile_candidates(recipe, target)
    assert cands
    budget = vmem * 0.5
    for c in cands:
        # the VMEM constraint is honored and the working set is exact
        assert c.working_set_bytes <= budget
        assert c.working_set_bytes == gemm.block_working_set_bytes(
            recipe, c.block_elements, target
        )
        expect = (
            "cdse" if c.working_set_bytes
            > budget * gemm.cdse_cdac.LARGE_CLASS_FRACTION else "cdac"
        )
        assert c.klass == expect
    # ranked best-first by modeled throughput
    ths = [c.predicted_throughput for c in cands]
    assert ths == sorted(ths, reverse=True)
    # both classes are represented across the block range
    assert {c.klass for c in cands} == {"cdse", "cdac"}


def test_tile_candidates_respect_batch_divisibility():
    recipe = _interp_recipe(5)
    cands = gemm.tile_candidates(
        recipe, _plm_target(64 * 2 ** 20), batch_elements=96
    )
    assert cands
    for c in cands:
        assert 96 % c.block_elements == 0
    assert max(c.block_elements for c in cands) == 32


def test_tile_candidates_empty_when_vmem_too_small():
    assert gemm.tile_candidates(
        _interp_recipe(11), _plm_target(4096)
    ) == []


def test_tile_candidates_on_a_tpu_fit_the_kernel_budget():
    """On a tiled target every candidate's padded, double-buffered
    working set fits the budget the kernel is compiled with, and costs
    at least the unpadded count."""
    recipe = _interp_recipe(11)
    t = channels.TPU_V5E
    cands = gemm.tile_candidates(recipe, t)
    assert cands
    for c in cands:
        assert c.working_set_bytes <= layout.vmem_budget_bytes(t)
        assert c.working_set_bytes == gemm.block_working_set_bytes(
            recipe, c.block_elements, t
        )
        assert c.working_set_bytes >= gemm.block_working_set_bytes(
            recipe, c.block_elements, t.with_(tiled_vmem=False)
        )
