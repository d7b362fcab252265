"""Compile-only checks of the serving path's kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  These compiles catch what interpret mode cannot —
tiles the Mosaic compiler refuses, VMEM overruns, kernels GSPMD cannot
partition — at phi4-mini-3.8b's published widths, with the selector's own
configs.  The topology is described inside a module fixture (never at
import time), and every such test lives in this one file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.bucketing import step_gemms
from repro.distributed.sharding import cache_shardings, rules_for, spec_for
from repro.kernels import ops
from repro.meshctx import use_mesh
from repro.nn import layers as L
from repro.nn.attention import decode_attention

CFG = get_config("phi4-mini-3.8b")
# (N, K) of one decoder step: fused QKV, attention out, MLP up+gate, MLP
# down, lm_head.
GEMMS = step_gemms(CFG.d_model, CFG.d_ff,
                   kv_dim=CFG.num_kv_heads * CFG.head_dim,
                   vocab=CFG.vocab_size, swiglu=CFG.activation == "swiglu")
DECODE_M = 8                    # the serving batch
PREFILL_MS = (512, 2048)
GEMM_CASES = ([(DECODE_M, n, k) for n, k in GEMMS]
              + [(m, n, k) for m in PREFILL_MS for n, k in GEMMS[:-1]])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep the cache out of it.
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                      # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", prev_cache)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas():
    ops.set_backend("pallas")
    yield
    ops.set_backend(None)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("m,n,k", GEMM_CASES,
                         ids=[f"{m}x{n}x{k}" for m, n, k in GEMM_CASES])
def test_gemm_compiles_for_v5e(one_chip, m, n, k):
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(lambda a, b: ops.matmul(a, b, backend="pallas"),
                          a, b)
    assert "tpu_custom_call" in text


def test_swiglu_epilogue_gemm_compiles_for_v5e(one_chip):
    M, N, K = PREFILL_MS[0], CFG.d_ff, CFG.d_model
    a = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((K, N), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((M, N), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda a, b, g: ops.matmul(a, b, epilogue="swiglu_gate", gate=g,
                                   backend="pallas"), a, b, g)
    assert "tpu_custom_call" in text


def test_causal_flash_attention_compiles_for_v5e(one_chip):
    S, d = 2048, CFG.head_dim
    q = jax.ShapeDtypeStruct((1, CFG.num_heads, S, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, CFG.num_kv_heads, S, d), jnp.bfloat16,
                              sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            backend="pallas"), q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", ["wu", "wd"])
def test_dense_compiles_on_four_chips(topo, pallas, name):
    """phi4's MLP weights, laid out by the sharding rules on a (1, 4) mesh:
    ``dense`` puts the kernel under shard_map, so it compiles (GSPMD alone
    refuses a Mosaic call); its boundary takes the weight as it lies (no
    all-gather), and the row-split wd all-reduces its partial products."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    d = L.mlp_defs(CFG)[name]
    w_spec = spec_for(d.shape, d.axes, rules_for(CFG), mesh)
    K = d.shape[0]
    x_spec = P(None, None, w_spec[0])
    x = jax.ShapeDtypeStruct((DECODE_M, 1, K), jnp.bfloat16,
                             sharding=NamedSharding(mesh, x_spec))
    w = jax.ShapeDtypeStruct(d.shape, jnp.bfloat16,
                             sharding=NamedSharding(mesh, w_spec))
    with use_mesh(mesh):
        text = _compiled_text(lambda x, w: L.dense(x, w, axes=d.axes), x, w)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
    assert ("all-reduce" in text) == (w_spec[0] == "model")


def _decode_specs(B, H, Hkv, S, d, q_sh, kv_sh, pos_sh):
    q = jax.ShapeDtypeStruct((B, H, 1, d), jnp.bfloat16, sharding=q_sh)
    kv = jax.ShapeDtypeStruct((B, Hkv, S, d), jnp.bfloat16, sharding=kv_sh)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=pos_sh)
    return q, kv, kv, pos


def _decode(q, k, v, pos):
    return decode_attention(q, k, v, pos=pos)


def test_decode_attention_holds_no_cache_copy(one_chip):
    """phi4's decode attention at the serving shapes (12 slots of 2048,
    per-slot positions) reads the bf16 cache as it lies: its scratch stays
    below one layer's K cache, where a repeated f32 copy needs six."""
    B, S, d = 12, 2048, CFG.head_dim
    specs = _decode_specs(B, CFG.num_heads, CFG.num_kv_heads, S, d,
                          one_chip, one_chip, one_chip)
    mem = jax.jit(_decode).lower(*specs).compile().memory_analysis()
    k_cache_bytes = B * CFG.num_kv_heads * S * d * 2
    assert mem.temp_size_in_bytes < k_cache_bytes


def test_decode_attention_on_four_chips_moves_no_cache(topo):
    """Minitron-8B's decode attention (48 heads over 8 kv heads, 16 slots
    of 2048) on a (1, 4) mesh, the cache laid out by ``cache_shardings``
    (sequence over "model"): flash-decode over the split cache, so no
    all-to-all moves the cache from a sequence split to a head split."""
    B, H, Hkv, S, d = 16, 48, 8, 2048, 128
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    layer = jax.ShapeDtypeStruct((1, B, Hkv, S, d), jnp.bfloat16)
    kv_sh = cache_shardings({"k": layer}, mesh, CFG)["k"]
    assert "model" in kv_sh.spec
    specs = _decode_specs(
        B, H, Hkv, S, d, NamedSharding(mesh, P(None, "model", None, None)),
        NamedSharding(mesh, P(*kv_sh.spec[1:])), NamedSharding(mesh, P()))
    text = _compiled_text(_decode, *specs)
    assert "all-to-all" not in text
