"""Blocked online-softmax attention kernel with analytically selected blocks.

The paper scopes itself to GEMM and lists attention as future work (§III-A);
this kernel is our *beyond-paper extension*: the same latency model —
max(compute, DMA) per grid step over a VMEM-constrained candidate space —
selects (block_q, block_kv) deterministically, with zero autotuning.

Layout: q (B, H, Sq, d), k/v (B, Hkv, Skv, d); GQA is handled by mapping each
q head onto its kv group in the index maps (no materialized KV repeat).
Grid: (B, H, Tq, Tkv), kv innermost; running (m, l, acc) scratch in VMEM.
Sequences must be pre-padded to block multiples (ops.flash_attention pads and
masks).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dtypes import DTYPE_BYTES
from repro.core.hardware import TPU_V5E
from repro.core.topology import HardwareSpec
from repro.core.latency import cdiv
from repro.kernels.matmul import no_vjp

_NEG_INF = float("-inf")
_LANES = 128


def select_attention_blocks(
    s_q: int,
    s_kv: int,
    head_dim: int,
    *,
    in_dtype: str = "bfloat16",
    hw: HardwareSpec = TPU_V5E,
    causal: bool = False,
) -> Tuple[int, int]:
    """Analytical (block_q, block_kv) selection — tritonBLAS model applied to
    the attention inner loop (two chained GEMMs per step).

    Per (bq, bkv) grid step:
      FLOPs  = 2*bq*bkv*d (qk) + 2*bq*bkv*d (pv) + O(bq*bkv) softmax VPU work
      HBM    = (k + v blocks) = 2*bkv*d*bytes   (q amortized over Tkv)
      VMEM   = q, k, v, acc, s blocks (+double buffering on k, v)
    Score = steps * max(compute, memory); argmin over the menu.
    """
    bi = DTYPE_BYTES[in_dtype]
    menu = (128, 256, 512, 1024, 2048)
    budget = hw.vmem_budget()
    flops = hw.flops(in_dtype)
    best, best_score = None, None
    for bq in menu:
        if bq > max(s_q, 128) * 2:
            continue
        for bkv in menu:
            if bkv > max(s_kv, 128) * 2:
                continue
            # VMEM: q,acc (f32),m,l + double-buffered k,v + s scores
            use = (bq * head_dim * (bi + 4)
                   + hw.pipeline_depth * 2 * bkv * head_dim * bi
                   + bq * bkv * 4 + 2 * bq * _LANES * 4)
            if use > budget:
                continue
            steps = cdiv(s_q, bq) * cdiv(s_kv, bkv)
            if causal:
                steps = max(1, steps // 2)        # half the blocks masked off
            comp = (4.0 * bq * bkv * head_dim) / flops
            vpu = (6.0 * bq * bkv) / (hw.vmem_bandwidth / 4)  # exp/max/scale
            mem = (2.0 * bkv * head_dim * bi) / hw.hbm_bandwidth + hw.dma_fixed
            score = steps * max(comp + vpu, mem)
            key = (score, -(bq * bkv))
            if best_score is None or key < best_score:
                best, best_score = (bq, bkv), key
    assert best is not None, "attention candidate space empty"
    return best


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 n_kv: int, scale: float, causal: bool,
                 block_q: int, block_kv: int, q_len: int, kv_len: int,
                 out_dtype):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_kv
    # Skip blocks strictly above the causal diagonal.
    run = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)           # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_ids < kv_len                          # padding mask
        if causal:
            mask = jnp.logical_and(mask, q_ids >= k_ids)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                          # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Rows with no valid key yet keep m = -inf; guard the exp.
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(mask, s - safe_m, _NEG_INF))
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - safe_m), 0.0)  # (bq, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _finish():
        l = l_ref[:, :1]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(out_dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_q: int,
    block_kv: int,
    causal: bool = False,
    scale: Optional[float] = None,
    q_len: Optional[int] = None,
    kv_len: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, Sq, d) padded to block_q; k/v: (B, Hkv, Skv, d) padded to
    block_kv.  q_len/kv_len are the *real* lengths for masking."""
    B, H, Sq, d = q.shape
    _, Hkv, Skv, _ = k.shape
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    assert Sq % block_q == 0 and Skv % block_kv == 0
    Tq, Tkv = Sq // block_q, Skv // block_kv
    scale = scale if scale is not None else d ** -0.5
    q_len = q_len or Sq
    kv_len = kv_len or Skv

    kernel = functools.partial(
        _attn_kernel, n_kv=Tkv, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, q_len=q_len, kv_len=kv_len,
        out_dtype=q.dtype)

    call = pl.pallas_call(
        kernel,
        grid=(B, H, Tq, Tkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),        # accumulator
        ],
        interpret=interpret,
    )
    return no_vjp(call, "flash attention")(q, k, v)
