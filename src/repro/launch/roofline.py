"""Roofline terms from a compiled dry-run artifact (no hardware needed).

    compute    = HLO_FLOPs_per_device / peak_FLOP/s        [s]
    memory     = HLO_bytes_per_device / HBM_bw             [s]
    collective = per-device collective bytes / link_bw     [s]

FLOPs / bytes / collective bytes come from the loop-aware HLO text analysis
in ``hlo_analysis.py`` (XLA's own cost_analysis counts while bodies once —
useless for scanned programs; both numbers are recorded so the undercount is
visible).  The compiled module is the per-device partitioned program, so
everything is per-chip already; all-reduce counts 2x its tensor (ring
reduce-scatter + all-gather), other collectives 1x.

Hardware model (TPU v5e-like, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s per ICI link.
"""
from __future__ import annotations

import dataclasses
from typing import Any

from repro.launch import hlo_analysis

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device (loop-aware)
    dot_flops: float             # matmul-only portion
    flops_xla: float             # XLA cost_analysis (loop-undercounted)
    bytes_hbm: float             # per device
    bytes_coll: float            # per device
    coll_by_op: dict[str, float]
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float           # 6ND (train) / 2ND (fwd) global
    useful_ratio: float          # model_flops / (flops * chips)
    warnings: list[str]

    def table_row(self) -> dict[str, Any]:
        return {
            "flops_per_dev": self.flops, "dot_flops_per_dev": self.dot_flops,
            "flops_xla_ca": self.flops_xla,
            "bytes_per_dev": self.bytes_hbm,
            "coll_bytes_per_dev": self.bytes_coll,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "coll_by_op": self.coll_by_op,
            "warnings": self.warnings,
        }


def analyze(compiled, *, n_chips: int, model_flops: float) -> Roofline:
    ca = compiled.cost_analysis()
    totals = hlo_analysis.analyze_text(compiled.as_text())
    flops = float(totals.flops)
    bytes_hbm = float(totals.bytes)
    bytes_coll = float(totals.coll_bytes)
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_hbm / HBM_BW
    collective_s = bytes_coll / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops / (flops * n_chips) if flops else 0.0
    return Roofline(flops=flops, dot_flops=float(totals.dot_flops),
                    flops_xla=float(ca.get("flops", 0.0)),
                    bytes_hbm=bytes_hbm, bytes_coll=bytes_coll,
                    coll_by_op=dict(totals.coll_by_op or {}),
                    compute_s=compute_s, memory_s=memory_s,
                    collective_s=collective_s, bottleneck=bottleneck,
                    model_flops=model_flops, useful_ratio=useful,
                    warnings=list(totals.warnings or []))


def model_flops_for(cfg, shape_name: str) -> float:
    """6·N·D for training, 2·N·D for prefill, 2·N·B per decoded token
    (N = active params for MoE)."""
    from repro.configs.base import SHAPES, active_params
    cell = SHAPES[shape_name]
    n = active_params(cfg)
    if cell.kind == "train":
        return 6.0 * n * cell.seq_len * cell.global_batch
    if cell.kind == "prefill":
        return 2.0 * n * cell.seq_len * cell.global_batch
    return 2.0 * n * cell.global_batch            # one token per sequence
