"""chip_smoke.py's logit tolerance against planted faults, on the CPU.

chip_smoke.py holds the kernel backend's logits against the reference
backend's on the same weights, one request in each decode slot, and fails
above ``LOGIT_RTOL``.  Here the same comparison (``compare_backends``) runs
phi4-mini-3.8b at smoke width with interpret-mode kernels: clean, it stays
under the limit; with each planted fault it reads above it.  A fault that a
2-layer model carries too weakly is planted at the depth where it first
clears the limit.
"""
import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core.bucketing import BucketPlan
from repro.kernels import ops
from repro.launch.engine import ServingEngine
from repro.nn import attention as attn_lib
from repro.nn.model import Model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

B = 8                                     # decode slots, as chip_smoke
EDGE = 96                                 # one bucket: one prefill compile
LENS = [40 + 8 * i for i in range(B)]     # every slot at its own position
PLAN = BucketPlan(edges=(EDGE,), policy="fixed", modeled_total_s=0.0,
                  modeled_request_s=0.0, pad_fraction=0.0,
                  bucket_overhead_s=0.0)
TILE = 16

_matmul, _chunked, _decode = (ops.matmul, attn_lib.chunked_attention,
                              attn_lib.decode_attention)


def _k_tile_zeroed(a, b, *args, **kw):
    K = a.shape[-1]
    return _matmul(a.at[..., K - TILE:].set(0), b, *args, **kw)


def _n_tiles_swapped(a, b, *args, **kw):
    y = _matmul(a, b, *args, **kw)
    return jnp.concatenate([y[..., TILE:2 * TILE], y[..., :TILE],
                            y[..., 2 * TILE:]], axis=-1)


def _residual_dropped(a, b, *args, residual=None, epilogue=None, **kw):
    return _matmul(a, b, *args, epilogue=None if epilogue == "residual"
                   else epilogue, **kw)


def _decode_row_zeroed(a, b, *args, **kw):
    """Row 5 of every decode-batch GEMM (leading dim B) comes back zero."""
    y = _matmul(a, b, *args, **kw)
    return y.at[5].set(0) if a.shape[0] == B else y


def _not_causal(q, k, v, **kw):
    return _chunked(q, k, v, **{**kw, "causal": False})


def _first_key_zeroed(q, k, v, **kw):
    return _chunked(q, k.at[:, :, :1].set(0), v.at[:, :, :1].set(0), **kw)


def _slot0_position(q, k, v, *, pos, **kw):
    """Every slot masked at slot 0's position: per-slot cache handling."""
    return _decode(q, k, v, pos=jnp.full_like(pos, pos[0]), **kw)


# name -> (patched attribute, replacement, layers at which it first reads
# above LOGIT_RTOL)
FAULTS = {
    "k_tile_zeroed": ((ops, "matmul"), _k_tile_zeroed, 2),
    "n_tiles_swapped": ((ops, "matmul"), _n_tiles_swapped, 2),
    "residual_dropped": ((ops, "matmul"), _residual_dropped, 2),
    "decode_row5_zeroed": ((ops, "matmul"), _decode_row_zeroed, 2),
    "prefill_not_causal": ((attn_lib, "chunked_attention"), _not_causal, 2),
    # 0.14 at 2 layers, too near the limit to pin; 0.74 at 4.
    "first_key_zeroed": ((attn_lib, "chunked_attention"), _first_key_zeroed,
                         4),
    "slots_at_slot0_position": ((attn_lib, "decode_attention"),
                                _slot0_position, 2),
}


def _errors(layers: int, patch=None):
    """(prefill (B,), decode (B,)) relative logit errors of interpret-mode
    kernels against the reference backend, ``patch`` applied to the kernel
    side only."""
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b", smoke=True),
                              num_layers=layers)
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(chip_smoke.SEED))
    rng = np.random.default_rng(chip_smoke.SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in LENS]
    ops.set_backend("pallas_interpret")
    try:
        engine = ServingEngine(model, params, max_batch=B, max_len=128,
                               plan=PLAN, quiet=True)
        with pytest.MonkeyPatch.context() as mp:
            if patch is not None:
                (owner, name), fn = patch
                mp.setattr(owner, name, fn)
            kernel = engine.probe(prompts)
        # The kernel side is traced and cached; compare_backends reruns it
        # as traced, and traces the reference side clean.
        _, errs = chip_smoke.compare_backends(engine, prompts)
        np.testing.assert_array_equal(engine.probe(prompts)[1], kernel[1])
    finally:
        ops.set_backend(None)
    return errs


@pytest.mark.parametrize("layers", [2, 4])
def test_clean_kernels_stay_under_the_tolerance(layers):
    prefill, decode = _errors(layers)
    assert prefill.shape == decode.shape == (B,)
    assert max(prefill.max(), decode.max()) <= chip_smoke.LOGIT_RTOL


@pytest.mark.parametrize("name", list(FAULTS))
def test_planted_fault_reads_above_the_tolerance(name):
    target, fn, layers = FAULTS[name]
    prefill, decode = _errors(layers, (target, fn))
    worst = max(prefill.max(), decode.max())
    assert worst > chip_smoke.LOGIT_RTOL, (prefill, decode)
    if name in ("decode_row5_zeroed", "slots_at_slot0_position"):
        # Invisible to a probe of slot 0 alone.
        assert decode[0] <= chip_smoke.LOGIT_RTOL, decode
