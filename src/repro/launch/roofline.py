"""Roofline-term extraction from compiled dry-run artifacts.

Method (DESIGN.md §9): XLA's cost_analysis() counts a while-loop body once
regardless of trip count, so
  * sequence-dimension scans (flash blocks, SSD chunks) are statically
    unrolled in dry-run mode (scan_utils.UNROLL_SCANS) — fully visible;
  * the layer scan is corrected by lowering the model at 1 and 2 layer
    units and extrapolating: total = c(1) + (U-1) * (c(2) - c(1));
  * the sLSTM time recurrence (xlstm only) cannot be unrolled at 4k+ —
    its FLOPs are added analytically (documented in EXPERIMENTS.md).

Collective bytes are parsed from the optimized per-device HLO: the result
shape of every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute (L1/L2-extrapolated like FLOPs).

Hardware constants (assignment): 197 TF/s bf16, 819 GB/s HBM, 50 GB/s/link.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*((?:\(?[a-z0-9]+\[[0-9,]*\][^)=]*?)+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes by collective kind (result-shape convention)."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind = m.group(2)
        b = _shape_bytes(m.group(1))
        out[kind] = out.get(kind, 0) + b
    return out


@dataclasses.dataclass
class CellCosts:
    flops: float                  # per device
    bytes_accessed: float         # per device
    coll_bytes: Dict[str, int]    # per device, by kind

    @property
    def coll_total(self) -> float:
        return float(sum(self.coll_bytes.values()))

    def scale_add(self, other: "CellCosts", k: float) -> "CellCosts":
        cb = dict(self.coll_bytes)
        for kk, v in other.coll_bytes.items():
            cb[kk] = cb.get(kk, 0) + int(k * v)
        return CellCosts(self.flops + k * other.flops,
                         self.bytes_accessed + k * other.bytes_accessed, cb)

    def sub(self, other: "CellCosts") -> "CellCosts":
        cb = {k: v - other.coll_bytes.get(k, 0)
              for k, v in self.coll_bytes.items()}
        cb = {k: max(0, v) for k, v in cb.items()}
        return CellCosts(max(0.0, self.flops - other.flops),
                         max(0.0, self.bytes_accessed - other.bytes_accessed),
                         cb)


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """Compiled.cost_analysis() as a plain dict (empty when XLA has none)."""
    return dict(compiled.cost_analysis() or {})


def costs_of(compiled) -> CellCosts:
    ca = cost_analysis_dict(compiled)
    return CellCosts(float(ca.get("flops", 0.0)),
                     float(ca.get("bytes accessed", 0.0)),
                     collective_bytes(compiled.as_text()))


def units_of(cfg) -> Tuple[int, int]:
    """(number of layer-scan units U, layers per unit)."""
    bp = cfg.block_pattern
    if bp == "gemma2":
        return cfg.n_layers // 2, 2
    if bp == "xlstm":
        return cfg.n_layers // 8, 8
    if bp == "zamba":
        return cfg.n_layers // cfg.attn_every, cfg.attn_every
    return cfg.n_layers, 1


def with_units(cfg, u: int):
    import dataclasses as dc
    _, per = units_of(cfg)
    return dc.replace(cfg, n_layers=u * per)


def seq_fit(cA: CellCosts, cB: CellCosts, sA: int, sB: int,
            s_target: int) -> CellCosts:
    """Fit cost(S) = a*S + b*S^2 from two sequence lengths, evaluate at
    s_target (used for cells whose unrolled chunk scans are too large to
    compile on the 1-core CPU proxy)."""
    def fit(yA, yB):
        b = (yB / sB - yA / sA) / (sB - sA)
        a = yA / sA - b * sA
        v = a * s_target + b * s_target ** 2
        return max(v, yB)        # monotone guard
    keys = set(cA.coll_bytes) | set(cB.coll_bytes)
    cb = {k: int(fit(cA.coll_bytes.get(k, 0), cB.coll_bytes.get(k, 0)))
          for k in keys}
    return CellCosts(fit(cA.flops, cB.flops),
                     fit(cA.bytes_accessed, cB.bytes_accessed), cb)


def extrapolate(c1: CellCosts, c2: CellCosts, cfg) -> CellCosts:
    """total = c1 + (U-1) * (c2 - c1), plus pattern-specific tails."""
    U, per = units_of(cfg)
    delta = c2.sub(c1)
    total = c1.scale_add(delta, U - 1)
    if cfg.block_pattern == "zamba":
        # 81 = 13*6 + 3 tail mamba layers ~ 3 of the 7 blocks in a unit
        tail = (cfg.n_layers - U * per) / (per + 1)
        total = total.scale_add(delta, tail)
    return total


def slstm_flops_correction(cfg, shape, per_device: int) -> float:
    """xlstm only: R-matmul inside the time scan (undercounted by XLA).
    fwd per token: 4 gates x H x hd^2 x 2; train charges 3x (fwd+bwd)."""
    if cfg.block_pattern != "xlstm" or shape.kind == "decode":
        return 0.0
    hd = cfg.d_model // cfg.n_heads
    n_slstm = cfg.n_layers // 8
    per_tok = 4 * cfg.n_heads * hd * hd * 2
    tokens = shape.global_batch * shape.seq_len
    mult = 3 if shape.kind == "train" else 1
    return n_slstm * per_tok * tokens * mult / per_device


def model_flops(cfg, shape) -> float:
    """Assignment convention: 6*N*D train (N_active for MoE); decode:
    2*N_active per generated token."""
    n = cfg.active_param_count()
    if shape.kind == "decode":
        return 2.0 * n * shape.global_batch
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * tokens
    return 6.0 * n * tokens


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_global: float
    logical_bytes_s: float = 0.0   # diagnostic: unfused "bytes accessed"

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / max(1.0, self.hlo_flops_global)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the ideal: time the *useful* model FLOPs would take
        at peak vs. the dominant modeled term. Clipped at 1 (XLA sometimes
        counts fewer FLOPs than the 6ND convention, e.g. gather-only
        embeddings)."""
        ideal = self.model_flops / PEAK_FLOPS   # per-chip share / chip peak
        return min(1.0, ideal / max(self.bound_s, ideal, 1e-12))

    def row(self) -> Dict[str, Any]:
        return dict(compute_s=self.compute_s, memory_s=self.memory_s,
                    collective_s=self.collective_s, dominant=self.dominant,
                    useful_ratio=self.useful_ratio,
                    roofline_fraction=self.roofline_fraction)


def make_roofline(costs: CellCosts, cfg, shape, n_chips: int,
                  traffic_bytes: Optional[float] = None) -> Roofline:
    """traffic_bytes: HBM-traffic estimate from the full compile's
    memory_analysis (2 x (args + temps + outputs) — every buffer written
    and read once). The raw HLO "bytes accessed" has no fusion credit on
    the CPU backend (flash blocks that live in VMEM on TPU are charged as
    HBM traffic), so it is kept only as a diagnostic."""
    mf = model_flops(cfg, shape)
    mem_bytes = traffic_bytes if traffic_bytes else costs.bytes_accessed
    return Roofline(
        compute_s=costs.flops / PEAK_FLOPS,
        memory_s=mem_bytes / HBM_BW,
        collective_s=costs.coll_total / ICI_BW,
        model_flops=mf / n_chips,          # per-chip ideal share
        hlo_flops_global=costs.flops,      # per-chip HLO flops
        logical_bytes_s=costs.bytes_accessed / HBM_BW,
    )
