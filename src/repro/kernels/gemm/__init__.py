"""Tiled Pallas GEMM-chain kernel class: shared-matrix mode contractions
plus elementwise ops, fused into one VMEM-resident CU per stage.  See
``gemm`` (kernel + recipe), ``ops`` (public wrappers / block sizing),
``cdse_cdac`` (CHARM-style large/small tile candidate classes)."""
from .gemm import (DEFAULT_BLOCK_ELEMENTS, EWISE_OPS, GemmRecipe,
                   apply_recipe, gemm_chain_pallas, gemm_chain_ref)
from .ops import block_working_set_bytes, gemm_chain, make_pallas_impl
from .cdse_cdac import LARGE_CLASS_FRACTION, TileCandidate, tile_candidates

__all__ = [
    "DEFAULT_BLOCK_ELEMENTS", "EWISE_OPS", "GemmRecipe", "apply_recipe",
    "gemm_chain_pallas", "gemm_chain_ref", "block_working_set_bytes",
    "gemm_chain", "make_pallas_impl",
    "LARGE_CLASS_FRACTION", "TileCandidate", "tile_candidates",
]
