"""Mesh-level applications of the analytical model (beyond-paper extension).

The paper scopes itself to one GPU (§III-A Non-Goals).  We extend its
max(compute, data-movement) scoring with ICI terms to *rank sharding
layouts* for a GEMM on the production mesh — the same zero-autotune
decision procedure, one level up the hierarchy:

    per-chip GEMM latency (paper model)  vs  collective latency (ring model)

The deployment shape these layouts price — the Pallas kernel under
shard_map on *local* shapes, followed by the psum of a K-split — is the
boundary ``nn.layers.dense`` puts around every layer GEMM on a mesh.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

from repro.core.dtypes import DTYPE_BYTES
from repro.core.hardware import TPU_V5E
from repro.core.topology import HardwareSpec
from repro.core.selector import select_gemm_config


def ring_all_reduce_s(nbytes: float, n: int, hw: HardwareSpec) -> float:
    """Bidirectional-ring all-reduce time: 2(n-1)/n * bytes / link_bw."""
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) / n * nbytes / hw.ici_bandwidth


def ring_all_gather_s(nbytes_local: float, n: int, hw: HardwareSpec) -> float:
    if n <= 1:
        return 0.0
    return (n - 1) * nbytes_local / hw.ici_bandwidth


@dataclass(frozen=True)
class LayoutChoice:
    layout: str            # "dp" | "tp_n" | "tp_k" | "replicated"
    predicted_s: float
    per_chip: Tuple[int, int, int]
    collective_s: float


def choose_gemm_layout(M: int, N: int, K: int, n_chips: int,
                       in_dtype: str = "bfloat16",
                       hw: HardwareSpec = TPU_V5E) -> LayoutChoice:
    """Rank {row-shard M (DP), col-shard N (TP-n), shard K (TP-k + psum)}
    with the paper's per-chip latency model + ring collective terms."""
    b = DTYPE_BYTES[in_dtype]
    cands = []
    if M % n_chips == 0:
        sel = select_gemm_config(M // n_chips, N, K, in_dtype=in_dtype, hw=hw)
        cands.append(LayoutChoice("dp", sel.predicted.total,
                                  (M // n_chips, N, K), 0.0))
    if N % n_chips == 0:
        sel = select_gemm_config(M, N // n_chips, K, in_dtype=in_dtype, hw=hw)
        cands.append(LayoutChoice("tp_n", sel.predicted.total,
                                  (M, N // n_chips, K), 0.0))
    if K % n_chips == 0:
        sel = select_gemm_config(M, N, K // n_chips, in_dtype=in_dtype, hw=hw)
        coll = ring_all_reduce_s(M * N * 4.0, n_chips, hw)
        cands.append(LayoutChoice(
            "tp_k", sel.predicted.total + coll, (M, N, K // n_chips), coll))
    if not cands:
        sel = select_gemm_config(M, N, K, in_dtype=in_dtype, hw=hw)
        cands.append(LayoutChoice("replicated", sel.predicted.total,
                                  (M, N, K), 0.0))
    return min(cands, key=lambda c: c.predicted_s)
