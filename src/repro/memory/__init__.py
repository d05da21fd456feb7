"""Automatic memory-architecture planning (the paper's core contribution).

Turns a compiled tensor program -- a :class:`~repro.memory.chain.ProgramChain`
of one or more stages; a single operator is a one-stage chain -- into an
explicit :class:`~repro.memory.chain.ChainPlan`: which pseudo-channel each
stream lives in, how big a batch (E) is, how deep each stage's prefetch
ring runs, where each stage is placed, and what it is predicted to cost
-- then explores that design space CHARM-style and verifies the winners
by measurement.

  channels  -- per-target memory datasheets (shared with analysis.roofline)
  plan      -- BufferSpec, CostBreakdown and the three-term cost model
               (predict_cost) beneath the planner and the explorer
  layout    -- stream->buffer assignment, packing, auto batch sizing,
               VMEM block sizing (the Pallas kernel's block_elements)
  placement -- device topology and per-stage CU groups
  chain     -- the planner: ProgramChain -> ChainPlan (inter-stage streams
               stay resident in HBM; one co-sized E for the pipeline)
  fusion    -- cost-driven stage fusion: the stage count as a DSE axis
               (merge adjacent stages when the handoff beats the roofline)
  pipeline  -- the skewed K-deep dispatch rings the executor runs on
  dse       -- design-space explorer over chain plans + the measured-
               feedback CostCorrection
"""
from . import chain, channels, dse, fusion, layout, pipeline, placement, plan
from .chain import (ChainPlan, ChainStage, PipelineSpec, ProgramChain,
                    apply_profile_contention, derive_pipeline,
                    fit_contention, plan_chain)
from .fusion import FusionSpec, fuse_chain, fuse_chain_auto
from .channels import (ALVEO_U280, CPU_HOST, TPU_V5E, MemoryTarget,
                       UnknownTargetError, detect_target, resolve_target)
from .placement import (DeviceTopology, PlacementError, PlacementPlan,
                        StagePlacement, place_chain)
from .dse import (Candidate, ChainDesignSpace, CostCorrection,
                  explore_chain, fit_correction, format_chain_ranking,
                  measure_chain_plan, pareto_front)
from .plan import BufferSpec, CostBreakdown

__all__ = [
    "chain", "channels", "dse", "layout", "pipeline", "placement", "plan",
    "MemoryTarget", "ALVEO_U280", "TPU_V5E", "CPU_HOST", "detect_target",
    "UnknownTargetError", "resolve_target",
    "DeviceTopology", "PlacementError", "PlacementPlan", "StagePlacement",
    "place_chain",
    "PipelineSpec", "derive_pipeline",
    "Candidate", "pareto_front", "ChainDesignSpace", "CostCorrection",
    "explore_chain", "fit_correction", "format_chain_ranking",
    "measure_chain_plan",
    "ProgramChain", "ChainStage", "ChainPlan", "plan_chain",
    "fit_contention", "apply_profile_contention",
    "FusionSpec", "fuse_chain", "fuse_chain_auto", "fusion",
    "BufferSpec", "CostBreakdown",
]
