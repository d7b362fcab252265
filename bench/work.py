"""Operations and bytes that the algorithm needs, computed from shapes.

Work is what the computation requires, not what the code happens to do:
a decode step reads the weights once, the keys and values of the positions
each active slot holds (never the whole ``max_len`` cache, never an f32
copy or a grouped-query repeat of it), and writes the logits; attention
counts the positions held.  A prefill counts the prompt's real tokens, not
its padding.  So a later implementation that skips work the code does
today cannot push a share past 100%.

A roofline share is ``max(flops / peak FLOP/s, bytes / peak bytes/s)``
over the measured time; ``Work.bound`` names which of the two sets it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

from bench.peaks import Peaks
from bench.reference import Dims

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, f: float) -> "Work":
        return Work(self.flops * f, self.bytes * f)

    __rmul__ = __mul__

    def seconds(self, p: Peaks) -> float:
        """The least time the chip could take."""
        return max(self.flops / p.bf16_flops, self.bytes / p.hbm_bytes)

    def bound(self, p: Peaks) -> str:
        return ("compute" if self.flops / p.bf16_flops
                >= self.bytes / p.hbm_bytes else "memory")


def gemm(m: int, n: int, k: int, *, extra_mn: int = 0) -> Work:
    """(m, k) @ (k, n) in bf16 with a bf16 result; ``extra_mn`` more
    (m, n) bf16 operands read by the epilogue (gate, residual)."""
    return Work(2.0 * m * n * k,
                BF16 * (m * k + k * n + m * n * (1 + extra_mn)))


def layer_gemms(d: Dims, rows: int) -> Work:
    """The block's seven projections for ``rows`` tokens: q, k, v, o,
    up, gate (reading up), down (reading the residual), as the program
    fuses them."""
    D, F = d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return (gemm(rows, q, D) + gemm(rows, kv, D) + gemm(rows, kv, D)
            + gemm(rows, D, q, extra_mn=1) + gemm(rows, F, D)
            + gemm(rows, F, D, extra_mn=1) + gemm(rows, D, F, extra_mn=1))


def flash_attention(d: Dims, q_len: int) -> Work:
    """Causal self-attention of one prompt of ``q_len`` real tokens, one
    layer: scores and values over the (q_len + 1) q_len / 2 pairs, q, k, v
    read and the output written once."""
    pairs = q_len * (q_len + 1) / 2
    flops = 4.0 * d.heads * d.head_dim * pairs
    io = BF16 * d.head_dim * q_len * (2 * d.heads + 2 * d.kv_heads)
    return Work(flops, io)


def _layer_weights(d: Dims) -> int:
    D, F = d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return D * (q + 2 * kv) + q * D + 3 * D * F


def decode_step(d: Dims, held: Iterable[int]) -> Work:
    """One decode step over the active slots; ``held[b]`` is the number of
    positions slot b attends to (its cache, the new token included)."""
    held = list(held)
    B = len(held)
    L, D, V = d.layers, d.d_model, d.vocab
    kv_row = 2 * d.kv_heads * d.head_dim          # K and V of one position
    wts = L * _layer_weights(d) + D * V
    flops = (2.0 * B * wts
             + 4.0 * L * d.heads * d.head_dim * sum(held))
    byts = (BF16 * wts
            + BF16 * L * kv_row * (sum(held) + B)  # read held, write new
            + F32 * B * V                          # logits
            + BF16 * B * D)                        # embedding rows
    return Work(flops, byts)


def prefill(d: Dims, prompt_len: int) -> Work:
    """One prompt through every layer, its cache written, and the head at
    its last position."""
    L, D, V = d.layers, d.d_model, d.vocab
    kv_row = 2 * d.kv_heads * d.head_dim
    w = L * (layer_gemms(d, prompt_len) + flash_attention(d, prompt_len))
    w = w + Work(0.0, BF16 * L * kv_row * prompt_len)
    return w + gemm(1, V, D)
