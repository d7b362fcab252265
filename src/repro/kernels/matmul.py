"""Pallas TPU GEMM kernel, parameterized by the analytical selector's config.

This is the tritonBLAS kernel ported to the TPU execution model: one kernel
template whose BlockSpec tiling (bm, bn, bk), grid iteration order (grouped
row swizzle), split-K factor and fused epilogue are *runtime parameters
chosen analytically* — never autotuned.

Grid layout: ``(num_output_tiles, split_k, Tk)`` iterated row-major (k
fastest, then the k-shard index), so the f32 accumulator scratch carries
across ALL of a tile's k-shards and flushes exactly once — split-K is
*in-kernel*: no ``(sk, M, N)`` HBM partial tensor, no follow-up combine pass.
The grouped iteration order (paper Alg. 6's cache-tile factorization) is
folded into the index maps; since the topology refactor the selector prices
``group_m`` per memory hierarchy — on TPU it selects which operand benefits
from the Mosaic revisit-skip, on multi-level topologies it buys L2 residency
of the re-walked operand — and this kernel executes whatever swizzle the
selection carries, semantics unchanged.

The epilogue (bias add, gelu/silu/swiglu-gate, residual add, out-dtype cast
— see ``repro.core.latency.Epilogue``) runs inside the flush step on the f32
accumulator, removing the full-output HBM round trips XLA would spend on
separate post-ops (DESIGN.md §3).

``TileConfig.schedule`` (occupancy stage, DESIGN.md §2): selections made on
multi-core topologies may carry ``schedule="stream_k"`` — a persistent
strip-scheduled kernel on GPUs.  The TPU Pallas grid is already persistent
(one sequential pipeline walks every tile), so this kernel LOWERS stream_k
to the existing split-K grid: the ``(tiles, sk, Tk)`` iteration order is
exactly the flattened strip walk of a single core, and the in-VMEM
accumulator plays the role of the strip-boundary partial (of which a
1-core schedule has none).  The field therefore changes nothing about the
lowering here — it exists so one selection table can drive both backends.

Inputs must be pre-padded to block multiples — ``ops.matmul`` does this.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.latency import EPILOGUE_NONE, Epilogue, TileConfig, cdiv


def no_vjp(fn: Callable, what: str) -> Callable:
    """``fn`` (a kernel launch over array arguments) with a VJP that refuses.

    The Pallas kernels have no backward rule.  Left alone, Pallas's own JVP
    rule fails with a bare ``AssertionError`` — which a caller's fallback
    can mistake for a launch failure and answer with the reference kernel.
    Differentiating through a kernel must fail loudly instead."""
    f = jax.custom_vjp(fn)

    def fwd(*args):
        return fn(*args), None

    def bwd(_res, _g):
        raise NotImplementedError(
            f"no VJP for the Pallas {what}; use backend='reference' to train")

    f.defvjp(fwd, bwd)
    return f


def _swizzle(pid, Tm: int, Tn: int, group_m: int):
    """Flattened tile id -> (pid_m, pid_n) under grouped iteration order."""
    if group_m <= 1:
        return pid // Tn, pid % Tn
    group_size = group_m * Tn
    gid = pid // group_size
    first_m = gid * group_m
    rows = jnp.minimum(Tm - first_m, group_m)   # ragged final group
    local = pid % group_size
    pid_m = first_m + local % rows
    pid_n = local // rows
    return pid_m, pid_n


def _apply_epilogue(acc, ep: Epilogue, bias_ref, gate_ref, res_ref):
    """Flush-step epilogue on the f32 accumulator (order: DESIGN.md §3)."""
    if ep.bias:
        acc = acc + bias_ref[...].astype(jnp.float32)
    if ep.activation == "gelu":
        acc = jax.nn.gelu(acc)
    elif ep.activation == "silu":
        acc = jax.nn.silu(acc)
    elif ep.activation == "swiglu_gate":
        acc = jax.nn.silu(acc) * gate_ref[...].astype(jnp.float32)
    if ep.residual:
        acc = acc + res_ref[...].astype(jnp.float32)
    return acc


def _make_kernel(ep: Epilogue, n_sk: int, n_k: int, out_dtype):
    def kernel(*refs):
        a_ref, b_ref = refs[0], refs[1]
        i = 2
        bias_ref = gate_ref = res_ref = None
        if ep.bias:
            bias_ref, i = refs[i], i + 1
        if ep.activation == "swiglu_gate":
            gate_ref, i = refs[i], i + 1
        if ep.residual:
            res_ref, i = refs[i], i + 1
        o_ref, acc_ref = refs[i], refs[i + 1]

        s, k = pl.program_id(1), pl.program_id(2)

        @pl.when((s == 0) & (k == 0))
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when((s == n_sk - 1) & (k == n_k - 1))
        def _flush():
            acc = _apply_epilogue(acc_ref[...], ep,
                                  bias_ref, gate_ref, res_ref)
            o_ref[...] = acc.astype(out_dtype)

    return kernel


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    config: TileConfig,
    *,
    out_dtype=jnp.float32,
    epilogue: Optional[Epilogue] = None,
    bias: Optional[jax.Array] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """C = epilogue(A @ B) with A:(M,K), B:(K,N) already padded to block
    multiples (K to ``bk * split_k``).  Epilogue operands, when present, are
    padded alongside the output: bias (1, N), gate/residual (M, N).

    One ``pallas_call`` regardless of split_k: k-shards accumulate into the
    VMEM scratch and the output is written exactly once.
    ``config.schedule`` is accepted from any selection (TPU or GPU-shaped
    topology) and lowered identically — ``stream_k`` degenerates to the
    sequential split-K grid on a single-core chip (module docstring).
    """
    ep = epilogue or EPILOGUE_NONE
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = config.bm, config.bn, config.bk
    sk = config.split_k
    assert M % bm == 0 and N % bn == 0 and K % (bk * sk) == 0, (
        f"inputs must be padded to blocks: {(M, N, K)} vs {config}")
    Tm, Tn = M // bm, N // bn
    Tk = K // (bk * sk)                 # k blocks per shard
    gm = config.group_m

    def a_index(pid, s, k):
        pid_m, _ = _swizzle(pid, Tm, Tn, gm)
        return pid_m, s * Tk + k

    def b_index(pid, s, k):
        _, pid_n = _swizzle(pid, Tm, Tn, gm)
        return s * Tk + k, pid_n

    def out_index(pid, s, k):
        pid_m, pid_n = _swizzle(pid, Tm, Tn, gm)
        return pid_m, pid_n

    def bias_index(pid, s, k):
        _, pid_n = _swizzle(pid, Tm, Tn, gm)
        return 0, pid_n

    inputs = [a, b]
    in_specs = [
        pl.BlockSpec((bm, bk), a_index),
        pl.BlockSpec((bk, bn), b_index),
    ]
    if ep.bias:
        assert bias is not None and bias.shape == (1, N), (
            "bias must be pre-shaped (1, N)", None if bias is None
            else bias.shape)
        inputs.append(bias)
        in_specs.append(pl.BlockSpec((1, bn), bias_index))
    if ep.activation == "swiglu_gate":
        assert gate is not None and gate.shape == (M, N), (
            "gate must be pre-padded (M, N)")
        inputs.append(gate)
        in_specs.append(pl.BlockSpec((bm, bn), out_index))
    if ep.residual:
        assert residual is not None and residual.shape == (M, N), (
            "residual must be pre-padded (M, N)")
        inputs.append(residual)
        in_specs.append(pl.BlockSpec((bm, bn), out_index))

    kernel = _make_kernel(ep, n_sk=sk, n_k=Tk, out_dtype=out_dtype)
    call = pl.pallas_call(
        kernel,
        grid=(Tm * Tn, sk, Tk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), out_index),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )
    return no_vjp(call, "GEMM")(*inputs)
