"""Mamba2 / SSD (state-space duality) block — arXiv:2405.21060.

The chunked SSD algorithm is GEMM-rich (intra-chunk quadratic blocks +
inter-chunk state GEMMs), which is exactly where the paper's selector
applies for the attention-free archs (DESIGN.md §5).  Contractions lower to
dot_general on the MXU; the chunk length is the tiling knob and defaults to
the MXU-aligned 256.

Shapes: x (B, S, D); internal heads (B, S, nh, hd); state (B, nh, hd, ns).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.nn import scanning
from repro.nn.config import ModelConfig
from repro.nn.layers import (ParamDef, axes_tree, dense, norm, norm_defs,
                             rmsnorm)

NEG_INF = float("-inf")


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., l) -> (..., l, l) with out[i, j] = sum_{j < t <= i} a[t],
    -inf above the diagonal (the 1-semiseparable decay matrix)."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, ss, NEG_INF)


def ssd_chunked(
    x: jax.Array,        # (B, S, nh, hd)  — pre-scaled by dt
    dA: jax.Array,       # (B, S, nh)      — log-decay per step (dt * A <= 0)
    Bm: jax.Array,       # (B, S, ns)
    Cm: jax.Array,       # (B, S, ns)
    chunk: int,
    initial_state=None,  # (B, nh, hd, ns)
) -> Tuple[jax.Array, jax.Array]:
    B, S, nh, hd = x.shape
    ns = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    c, l = S // chunk, chunk

    xc = x.reshape(B, c, l, nh, hd)
    Ac = dA.reshape(B, c, l, nh).transpose(0, 3, 1, 2)        # (B, nh, c, l)
    Bc = Bm.reshape(B, c, l, ns)
    Cc = Cm.reshape(B, c, l, ns)

    A_cs = jnp.cumsum(Ac, axis=-1)                            # (B, nh, c, l)
    L = jnp.exp(_segsum(Ac))                                  # (B, nh, c, l, l)

    # 1) intra-chunk (diagonal blocks): quadratic attention-like GEMMs.
    Y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xc,
                        preferred_element_type=jnp.float32)

    # 2) chunk-local final states.
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)             # (B, nh, c, l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, xc,
                        preferred_element_type=jnp.float32)

    # 3) inter-chunk recurrence (sequential scan over chunks).
    chunk_decay = jnp.exp(A_cs[..., -1])                      # (B, nh, c)
    init = (jnp.zeros((B, nh, hd, ns), jnp.float32)
            if initial_state is None else initial_state.astype(jnp.float32))

    def step(carry, inp):
        s_c, d_c = inp                 # (B, nh, hd, ns), (B, nh)
        new = s_c + d_c[..., None, None] * carry
        return new, carry              # emit the state *entering* the chunk

    final, prev = scanning.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0).astype(jnp.float32),
         jnp.moveaxis(chunk_decay, 2, 0)))
    prev = jnp.moveaxis(prev, 0, 1)                           # (B, c, nh, hd, ns)

    # 4) prior-state contribution to each position.
    state_decay = jnp.exp(A_cs)                               # (B, nh, c, l)
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, prev, state_decay,
                       preferred_element_type=jnp.float32)

    y = (Y_diag + Y_off).reshape(B, S, nh, hd)
    return y.astype(x.dtype), final


# ---------------------------------------------------------------------------
# Mamba2 block.
# ---------------------------------------------------------------------------

def mamba_defs(cfg: ModelConfig) -> Dict:
    """Projections are kept as separate weights (not the reference impl's
    fused in_proj) so each output dim shards cleanly: d_inner over the
    "model" axis without slice-across-shard reshards (DESIGN.md §7)."""
    D, di, ns, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    w = cfg.ssm_conv_width
    return {
        "norm": norm_defs(cfg),
        "in_z": ParamDef((D, di), ("embed", "ssm_inner")),
        "in_x": ParamDef((D, di), ("embed", "ssm_inner")),
        "in_b": ParamDef((D, ns), ("embed", "state")),
        "in_c": ParamDef((D, ns), ("embed", "state")),
        "in_dt": ParamDef((D, nh), ("embed", "ssm_heads")),
        "conv_x": ParamDef((w, di), (None, "ssm_inner"), scale=0.1),
        "conv_xb": ParamDef((di,), ("ssm_inner",), init="zeros"),
        "conv_b": ParamDef((w, ns), (None, "state"), scale=0.1),
        "conv_bb": ParamDef((ns,), ("state",), init="zeros"),
        "conv_c": ParamDef((w, ns), (None, "state"), scale=0.1),
        "conv_cb": ParamDef((ns,), ("state",), init="zeros"),
        "A_log": ParamDef((nh,), ("ssm_heads",), init="ssm_a",
                          dtype=jnp.float32),
        "D": ParamDef((nh,), ("ssm_heads",), init="ones", dtype=jnp.float32),
        "dt_bias": ParamDef((nh,), ("ssm_heads",), init="ssm_dt",
                            dtype=jnp.float32),
        "gate_norm": ParamDef((di,), ("ssm_inner",), init="ones"),
        "out_proj": ParamDef((di, D), ("ssm_inner", "embed")),
    }


def _project(p: Dict, h: jax.Array, cfg: ModelConfig):
    """h -> (z, x, B, C, dt) via the five separate projections."""
    ax = axes_tree(mamba_defs(cfg))
    z = dense(h, p["in_z"], axes=ax["in_z"])
    xs = dense(h, p["in_x"], axes=ax["in_x"])
    Bm = dense(h, p["in_b"], axes=ax["in_b"])
    Cm = dense(h, p["in_c"], axes=ax["in_c"])
    dt = dense(h, p["in_dt"], axes=ax["in_dt"])
    return z, xs, Bm, Cm, dt


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv, width w.shape[0]: (B, S, ch) -> (B, S, ch)."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    S = x.shape[1]
    windows = jnp.stack([pad[:, k:k + S] for k in range(width)])  # (w,B,S,ch)
    out = jnp.einsum("wbsc,wc->bsc", windows, w.astype(windows.dtype)) + b
    return jax.nn.silu(out)


def mamba_forward(p: Dict, x: jax.Array, cfg: ModelConfig,
                  return_cache: bool = False):
    """Block forward. With ``return_cache`` also emits the decode state
    (conv window tail + final SSM state) computed in the same pass."""
    B, S, D = x.shape
    di, ns, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    h = norm(x, p["norm"], cfg)
    z, xs, Bm, Cm, dt = _project(p, h, cfg)

    w = cfg.ssm_conv_width
    conv_tail = {
        "conv_x": xs[:, -(w - 1):].astype(jnp.bfloat16),
        "conv_b": Bm[:, -(w - 1):].astype(jnp.bfloat16),
        "conv_c": Cm[:, -(w - 1):].astype(jnp.bfloat16),
    }
    xs = _causal_conv(xs, p["conv_x"], p["conv_xb"])
    Bm = _causal_conv(Bm, p["conv_b"], p["conv_bb"])
    Cm = _causal_conv(Cm, p["conv_c"], p["conv_cb"])

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B, S, nh)
    A = -jnp.exp(p["A_log"])                                      # (nh,)

    # Pad sequence to a chunk multiple (pads contribute x=0, discarded).
    chunk = min(cfg.ssm_chunk, max(16, S))
    pad = (-S) % chunk
    xh = xs.reshape(B, S, nh, hd)
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bp = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cp = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    else:
        dtp, Bp, Cp = dt, Bm, Cm

    y, final_state = ssd_chunked(
        (xh.astype(jnp.float32) * dtp[..., None]).astype(xh.dtype),
        dtp * A, Bp, Cp, chunk)
    y = y[:, :S]
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh[:, :S]
    y = y.reshape(B, S, di)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"])
    out = dense(y, p["out_proj"], axes=mamba_defs(cfg)["out_proj"].axes)
    if return_cache:
        return out, {**conv_tail, "ssm": final_state}
    return out


# ---------------------------------------------------------------------------
# O(1) recurrent decode step.
# ---------------------------------------------------------------------------

def mamba_cache_defs(cfg: ModelConfig, batch: int) -> Dict:
    di, ns, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    w = cfg.ssm_conv_width
    return {
        "conv_x": jax.ShapeDtypeStruct((batch, w - 1, di), jnp.bfloat16),
        "conv_b": jax.ShapeDtypeStruct((batch, w - 1, ns), jnp.bfloat16),
        "conv_c": jax.ShapeDtypeStruct((batch, w - 1, ns), jnp.bfloat16),
        "ssm": jax.ShapeDtypeStruct((batch, nh, hd, ns), jnp.float32),
    }


def _conv_step(x_t: jax.Array, state: jax.Array, w: jax.Array, b: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """One-token depthwise conv: state (B, w-1, ch), x_t (B, ch)."""
    window = jnp.concatenate([state.astype(x_t.dtype), x_t[:, None]], axis=1)
    out = jnp.einsum("bwc,wc->bc", window, w.astype(window.dtype)) + b
    return jax.nn.silu(out), window[:, 1:].astype(state.dtype)


def mamba_decode(p: Dict, x: jax.Array, cache: Dict, cfg: ModelConfig
                 ) -> Tuple[jax.Array, Dict]:
    B, _, D = x.shape
    di, ns, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    h = norm(x, p["norm"], cfg)
    z, xs, Bm, Cm, dt = _project(p, h, cfg)
    z, xs, Bm, Cm, dt = (t[:, 0] for t in (z, xs, Bm, Cm, dt))

    xs, new_cx = _conv_step(xs, cache["conv_x"], p["conv_x"], p["conv_xb"])
    Bm, new_cb = _conv_step(Bm, cache["conv_b"], p["conv_b"], p["conv_bb"])
    Cm, new_cc = _conv_step(Cm, cache["conv_c"], p["conv_c"], p["conv_cb"])

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B, nh)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * A)                                       # (B, nh)
    xh = xs.reshape(B, nh, hd).astype(jnp.float32) * dt[..., None]
    upd = jnp.einsum("bhp,bn->bhpn", xh, Bm.astype(jnp.float32))
    state = cache["ssm"] * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, Cm.astype(jnp.float32))
    y = y + p["D"][None, :, None] * xs.reshape(B, nh, hd).astype(jnp.float32)
    y = y.reshape(B, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"])
    out = dense(y, p["out_proj"], axes=mamba_defs(cfg)["out_proj"].axes)
    return out[:, None], \
        {"conv_x": new_cx, "conv_b": new_cb, "conv_c": new_cc, "ssm": state}
