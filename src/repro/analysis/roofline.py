"""Three-term roofline analysis from compiled dry-run artifacts.

Terms (seconds), per the TPU v5e target constants:

  compute    = device_FLOPs / peak_FLOP/s            (197 TF/s bf16 / chip)
  memory     = device_HBO_bytes / HBM_bw             (819 GB/s / chip)
  collective = device_collective_bytes / link_bw     (~50 GB/s / link ICI)

Sources: ``compiled.cost_analysis()`` reports per-device FLOPs and bytes
(the executable is the per-device SPMD program -- verified empirically);
collective bytes are parsed from the post-optimization HLO
(``compiled.as_text()``), summing the RESULT buffer size of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute.
Result-size is a within-2x proxy for wire traffic (ring all-gather moves
(n-1)/n of the result; all-reduce ~2x its operand); we use it consistently
so perf iterations compare like against like.

MODEL_FLOPS sanity: 6*N*D for dense training (N params, D tokens), 2*N*D
for inference; MoE uses active parameters.  The ratio MODEL_FLOPS /
(chips x device_FLOPs) flags remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

# ---- TPU v5e target constants --------------------------------------------
# Shared with the memory planner: repro.memory.channels.TPU_V5E is the
# single source of truth, so roofline analysis and ChainPlan costing can
# never disagree on peak numbers.
from ..memory.channels import TPU_V5E as _TPU_V5E

PEAK_FLOPS_BF16 = _TPU_V5E.peak_flops   # per chip
HBM_BW = _TPU_V5E.hbm_bw                # bytes/s per chip
ICI_LINK_BW = _TPU_V5E.ici_bw           # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# result type on the LHS: %name = f32[128,256]{1,0} all-reduce(
_LINE_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\s"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
# tuple-result collectives: (f32[8,128], f32[8,128]) all-to-all(...)
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_TYPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _type_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum collective result-buffer bytes per op kind (per device)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if not any(c in line for c in _COLLECTIVES):
            continue
        if "-done(" in line:
            continue  # async pair: count the -start only
        m = _TUPLE_RE.search(line)
        if m:
            types, kind = m.groups()
            for dtype, dims in _TYPE_RE.findall(types):
                out[kind] += _type_bytes(dtype, dims)
            continue
        m = _LINE_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _type_bytes(dtype, dims)
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_flops: float
    device_bytes: float
    coll_bytes: float
    coll_breakdown: Dict[str, int]
    bytes_per_device: int          # peak live memory (args+temps+outputs)
    model_flops: float             # analytic useful flops (global)

    @property
    def t_compute(self) -> float:
        return self.device_flops / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.device_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.device_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """compute-term share of the critical term: 1.0 means the program
        is exactly compute-bound with zero overhead above the MXU floor."""
        t_max = max(self.t_compute, self.t_memory, self.t_collective)
        return self.t_compute / t_max if t_max else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(
            t_compute=self.t_compute,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            bottleneck=self.bottleneck,
            useful_flops_ratio=self.useful_flops_ratio,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def model_flops(
    *, params: int, tokens: int, kind: str, active_params: Optional[int] = None
) -> float:
    """6ND (train) / 2ND (inference) with MoE active-param correction."""
    n = active_params if active_params is not None else params
    if kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def analyze(
    compiled,
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    model_flops_value: float,
    extra_flops: float = 0.0,
    extra_bytes: float = 0.0,
) -> RooflineReport:
    """``extra_flops``/``extra_bytes``: scan-body corrections from
    analysis.scancost (XLA counts while bodies once)."""
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    bytes_dev = (
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    )
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        device_flops=float(ca.get("flops", 0.0)) + extra_flops,
        device_bytes=float(ca.get("bytes accessed", 0.0)) + extra_bytes,
        coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll,
        bytes_per_device=int(bytes_dev),
        model_flops=model_flops_value,
    )


def format_table(reports) -> str:
    hdr = (
        f"{'arch':<24} {'shape':<12} {'mesh':<10} {'t_comp(s)':>10} "
        f"{'t_mem(s)':>10} {'t_coll(s)':>10} {'bound':>10} {'useful':>7} "
        f"{'frac':>6} {'GB/dev':>7}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in reports:
        lines.append(
            f"{r.arch:<24} {r.shape:<12} {r.mesh:<10} {r.t_compute:>10.4g} "
            f"{r.t_memory:>10.4g} {r.t_collective:>10.4g} {r.bottleneck:>10} "
            f"{r.useful_flops_ratio:>7.3f} {r.roofline_fraction:>6.3f} "
            f"{r.bytes_per_device/2**30:>7.2f}"
        )
    return "\n".join(lines)
