"""Attention implementations.

``chunked_attention`` is the jax-native flash equivalent: online softmax over
kv chunks inside a lax.scan — never materializes the (Sq, Skv) score matrix.
It is the dry-run / CPU / GSPMD path; its FLOP and byte profile matches the
Pallas kernel algorithm, which is what the roofline reads.  On TPU runtimes
``repro.kernels.flash_attention`` (selector-tiled Pallas) is used instead.

GQA note (sharding-critical): in ``chunked_attention`` q stays (B, H, S, d)
and KV is broadcast to H heads with jnp.repeat.  H divides the 16-way
"model" axis for every assigned arch, whereas a (B, Hkv, group, S, d)
grouping would leave GSPMD with two non-dividing head dims (Hkv=8, group=6)
and force *full attention replication* on every chip — a 16x flop/byte
blow-up we measured in the dry-run probes (EXPERIMENTS.md §Perf, iteration
1).

``decode_attention`` scores one query step against a long KV cache and never
repeats it: the decode cache shards on SEQUENCE, not heads, so no head dim
must divide the "model" axis, and the queries are grouped by kv head
instead.  With the cache's sequence axis sharded over "model" this becomes
flash-decode (partial softmax + cross-chip reduction, inserted by GSPMD);
the cache itself never moves between chips.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import scanning

NEG_INF = float("-inf")


def chunked_attention(
    q: jax.Array,                    # (B, H, Sq, d)
    k: jax.Array,                    # (B, Hkv, Skv, d)
    v: jax.Array,                    # (B, Hkv, Skv, d)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    scale: Optional[float] = None,
    chunk_q: int = 512,
    chunk_k: int = 512,
    q_offset: int = 0,               # absolute position of q[0] (for caches)
) -> jax.Array:
    B, H, Sq, d = q.shape
    _, Hkv, Skv, _ = k.shape
    group = H // Hkv
    scale = scale if scale is not None else d ** -0.5
    if group > 1:                    # broadcast KV to H heads (see docstring)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    cq, ck = min(chunk_q, Sq), min(chunk_k, Skv)
    pq, pk = (-Sq) % cq, (-Skv) % ck
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = (Sq + pq) // cq, (Skv + pk) // ck

    qc = q.reshape(B, H, nq, cq, d)
    kc = k.reshape(B, H, nk, ck, d)
    vc = v.reshape(B, H, nk, ck, d)

    def q_block(iq, q_blk):
        # q_blk: (B, H, cq, d)
        q32 = q_blk.astype(jnp.float32) * scale
        q_pos = q_offset + iq * cq + jnp.arange(cq)

        def kv_step(carry, inputs):
            m_prev, l_prev, acc = carry
            ik, k_blk, v_blk = inputs
            k_pos = ik * ck + jnp.arange(ck)
            s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                           k_blk.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            mask = (k_pos[None, :] < Skv)
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            if sliding_window > 0:
                mask = mask & (q_pos[:, None] - k_pos[None, :]
                               < sliding_window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            alpha = jnp.where(jnp.isfinite(m_prev),
                              jnp.exp(m_prev - safe), 0.0)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bhkd->bhqd", p,
                            v_blk.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            acc = acc * alpha[..., None] + pv
            return (m_new, l_new, acc), None

        init = (
            jnp.full((B, H, cq), NEG_INF, jnp.float32),
            jnp.zeros((B, H, cq), jnp.float32),
            jnp.zeros((B, H, cq, d), jnp.float32),
        )
        # Checkpoint each kv step: backward recomputes the (cq, ck) score /
        # prob tiles instead of stashing them per step — the flash-attention
        # memory profile (saves O(S^2/ck) residuals per layer).
        (m, l, acc), _ = scanning.scan(
            jax.checkpoint(kv_step), init,
            (jnp.arange(nk), jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0)))
        denom = jnp.where(l > 0, l, 1.0)[..., None]
        return acc / denom

    # Scan over q chunks (keeps peak memory at one (cq, ck) tile per head).
    _, out = scanning.scan(
        lambda _, args: (None, q_block(*args)), None,
        (jnp.arange(nq), jnp.moveaxis(qc, 2, 0)))
    # out: (nq, B, H, cq, d) -> (B, H, Sq, d)
    out = jnp.moveaxis(out, 0, 2).reshape(B, H, nq * cq, d)[:, :, :Sq]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,                    # (B, H, 1, d) — one new token
    k_cache: jax.Array,              # (B, Hkv, S, d)
    v_cache: jax.Array,              # (B, Hkv, S, d)
    *,
    pos: jax.Array,                  # current length: scalar or (B,) int32
    sliding_window: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Flash-decode: one query step against the cache.

    The H queries are grouped by kv head, (B, Hkv, G, d) with G = H // Hkv
    (MHA is G = 1), and each group is scored against its head's cache as
    stored: the cache is never repeated to H heads nor copied to f32.  Both
    contractions take their operands in the cache's dtype and accumulate
    in f32; mask, max, exp and sum run in f32."""
    B, H, _, d = q.shape
    _, Hkv, S, _ = k_cache.shape
    group = H // Hkv
    scale = scale if scale is not None else d ** -0.5
    k_pos = jnp.arange(S)
    if jnp.ndim(pos) == 0:
        # Scalar step (step-synchronous batch): mask broadcasts over B.
        mask = k_pos <= pos
        if sliding_window > 0:
            mask = mask & (pos - k_pos < sliding_window)
        mask = mask.reshape(1, 1, 1, S)
    else:
        # Per-slot positions (continuous batching): each row masks its own
        # prefix, so slots mid-decode coexist with freshly admitted ones.
        mask = k_pos[None, :] <= pos[:, None]                 # (B, S)
        if sliding_window > 0:
            mask = mask & (pos[:, None] - k_pos[None, :] < sliding_window)
        mask = mask[:, None, None, :]

    qg = q.reshape(B, Hkv, group, d).astype(k_cache.dtype)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32) / l
    return out.reshape(B, H, 1, d).astype(q.dtype)
