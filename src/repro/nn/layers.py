"""Building blocks + parameter-definition machinery.

Params are plain nested dicts of arrays.  Every parameter is declared as a
``ParamDef`` carrying its *logical axis names* — the t5x-style indirection the
distributed layer uses to map params onto the mesh (DESIGN.md §7).  The same
def tree yields:

  * ``init_tree``      — materialized params (smoke tests, examples, training)
  * ``abstract_tree``  — ShapeDtypeStructs (multi-pod dry-run, no allocation)
  * ``axes_tree``      — logical axes (sharding rules)

Dense contractions go through ``repro.kernels.ops.matmul`` — the tritonBLAS
selector chooses the kernel tiling at trace time (zero autotuning).  GSPMD
cannot partition a Pallas kernel, so when a mesh of several devices is
installed (``repro.meshctx``) the kernel calls run under ``shard_map`` on
per-chip shapes: the GEMMs by the weight's layout, attention over heads.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import meshctx
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.nn import attention as attn_lib
from repro.nn.config import ModelConfig


class ParamDef(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == ndim
    init: str = "normal"              # normal | zeros | ones | ssm_a | ssm_dt
    dtype: Any = jnp.bfloat16
    scale: float = 0.02


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_map_defs(fn: Callable[[ParamDef], Any], defs):
    return jax.tree_util.tree_map(fn, defs, is_leaf=is_def)


def init_tree(rng: jax.Array, defs) -> Dict:
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_def)
    rngs = jax.random.split(rng, len(leaves))

    def make(d: ParamDef, key):
        if d.init == "zeros":
            return jnp.zeros(d.shape, d.dtype)
        if d.init == "ones":
            return jnp.ones(d.shape, d.dtype)
        if d.init == "ssm_a":     # -exp(U[log 1, log 16]) init for A_log
            u = jax.random.uniform(key, d.shape, jnp.float32)
            return jnp.log(1.0 + u * 15.0).astype(d.dtype)
        if d.init == "ssm_dt":    # dt bias in [1e-3, 1e-1] (softplus-inverse)
            u = jax.random.uniform(key, d.shape, jnp.float32,
                                   minval=-4.6, maxval=-2.3)
            return u.astype(d.dtype)
        return (jax.random.normal(key, d.shape, jnp.float32)
                * d.scale).astype(d.dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [make(d, k) for d, k in zip(leaves, rngs)])


def abstract_tree(defs):
    return tree_map_defs(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs)


def axes_tree(defs):
    return tree_map_defs(lambda d: d.axes, defs)


# ---------------------------------------------------------------------------
# Primitive layers.
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w + b


def norm(x: jax.Array, p: Dict, cfg: ModelConfig) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def norm_defs(cfg: ModelConfig) -> Dict:
    d = {"scale": ParamDef((cfg.d_model,), ("embed",), init="ones")}
    if cfg.norm == "layernorm":
        d["bias"] = ParamDef((cfg.d_model,), ("embed",), init="zeros")
    return d


def _kernel_mesh() -> Optional[Mesh]:
    """The installed mesh when kernel calls need a shard_map boundary: a
    Pallas backend on more than one device.  The reference backend is plain
    jnp, which GSPMD partitions by itself."""
    mesh = meshctx.get_mesh()
    if mesh is None or mesh.size == 1 or kops.get_backend() == "reference":
        return None
    return mesh


def _batch_axes(mesh: Mesh, dim: int):
    """Mesh axes for an activation's leading (batch) dim: the data axes,
    when they divide it; otherwise None (replicated)."""
    axes = tuple(a for a in ("pod", "data") if mesh.shape.get(a, 1) > 1)
    if axes and dim % math.prod(mesh.shape[a] for a in axes) == 0:
        return axes
    return None


def dense(x: jax.Array, w: jax.Array, out_dtype=None, *, axes=None,
          epilogue=None, bias=None, gate=None,
          residual=None) -> jax.Array:
    """Selector-driven fused GEMM: epilogue(x (..., K) @ w (K, N)).

    The epilogue (bias / gelu / silu / swiglu-gate / residual) executes
    inside the kernel's flush step — one HBM round trip per layer instead of
    one per post-op (DESIGN.md §3).

    ``axes`` is the weight's logical axes (its ParamDef's).  Under a mesh
    the kernel sees the weight as ``param_shardings`` lays it out over the
    "model" axis: N split (the output stays split on N), K split (partial
    products are psum'd in f32 and the epilogue runs after the sum), or
    neither (replicated, as for ``axes=None``)."""
    out_dtype = out_dtype or x.dtype
    mesh = _kernel_mesh()
    if mesh is None:
        return kops.matmul(x, w, out_dtype=out_dtype, epilogue=epilogue,
                           bias=bias, gate=gate, residual=residual)
    # Imported here: repro.distributed imports the model, which imports this.
    from repro.distributed.sharding import model_dims
    k_ax, n_ax = model_dims(w.shape, axes, mesh)
    mid = (None,) * (x.ndim - 2)
    lead = _batch_axes(mesh, x.shape[0])
    out_spec = P(lead, *mid, n_ax)
    ep = kops.normalize_epilogue(epilogue, bias, gate, residual)
    extra = {name: (val, spec) for name, val, spec in (
        ("bias", bias, P(n_ax)), ("gate", gate, out_spec),
        ("residual", residual, out_spec)) if val is not None}

    def local(xl, wl, *ex):
        kw = dict(zip(extra, ex))
        if k_ax is None:
            return kops.matmul(xl, wl, out_dtype=out_dtype, epilogue=ep,
                               **kw)
        y = jax.lax.psum(kops.matmul(xl, wl, out_dtype=jnp.float32), k_ax)
        return kref.apply_epilogue_ref(y, ep, **kw).astype(out_dtype)

    # check_vma=False: a pallas_call's out_shape carries no varying-axes
    # annotation, so the checker would refuse the kernel.
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(lead, *mid, k_ax), P(k_ax, n_ax),
                  *(spec for _, spec in extra.values())),
        out_specs=out_spec, check_vma=False,
    )(x, w, *(val for val, _ in extra.values()))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal attention through the Pallas flash kernel; under an installed
    mesh it runs per chip over heads (kv heads repeated to the q heads when
    the "model" axis does not divide them)."""
    mesh = _kernel_mesh()
    if mesh is None:
        return kops.flash_attention(q, k, v, causal=True)
    tp = mesh.shape.get("model", 1)
    H, Hkv = q.shape[1], k.shape[1]
    heads = "model" if H % tp == 0 else None
    if heads and Hkv % tp:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    spec = P(_batch_axes(mesh, q.shape[0]), heads, None, None)
    return jax.shard_map(
        lambda q, k, v: kops.flash_attention(q, k, v, causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, S, d); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
        ang = ang[None, None]                       # (1, 1, S, half)
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs
        ang = ang[:, None]                          # (B, 1, S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention block (GQA + RoPE + KV cache).
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig) -> Dict:
    D = cfg.d_model
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "norm": norm_defs(cfg),
        "wq": ParamDef((D, H * hd), ("embed", "heads")),
        "wk": ParamDef((D, Hkv * hd), ("embed", "kv_heads")),
        "wv": ParamDef((D, Hkv * hd), ("embed", "kv_heads")),
        "wo": ParamDef((H * hd, D), ("heads", "embed")),
    }


def _repeat_kv_weight(w: jax.Array, hkv: int, hd: int, group: int
                      ) -> jax.Array:
    """(D, Hkv*hd) -> (D, H*hd) by repeating each kv head's columns.

    Repeating the WEIGHT (tiny) instead of the activation kills the
    per-layer K/V all-gather GSPMD inserts when Hkv < "model" axis size
    (Megatron KV duplication; EXPERIMENTS.md §Perf)."""
    D = w.shape[0]
    return jnp.repeat(w.reshape(D, hkv, hd), group, axis=1) \
        .reshape(D, hkv * group * hd)


def attn_forward(
    p: Dict,
    x: jax.Array,                    # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: jax.Array,            # (S,)
    residual: Optional[jax.Array] = None,   # fused into the wo GEMM's flush
) -> jax.Array:
    B, S, D = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ax = axes_tree(attn_defs(cfg))
    h = norm(x, p["norm"], cfg)
    q = dense(h, p["wq"], axes=ax["wq"]).reshape(B, S, H, hd) \
        .transpose(0, 2, 1, 3)
    group = H // Hkv
    if cfg.kv_repeat_weights and group > 1:
        wk = _repeat_kv_weight(p["wk"], Hkv, hd, group)
        wv = _repeat_kv_weight(p["wv"], Hkv, hd, group)
        k = dense(h, wk, axes=ax["wk"]).reshape(B, S, H, hd) \
            .transpose(0, 2, 1, 3)
        v = dense(h, wv, axes=ax["wv"]).reshape(B, S, H, hd) \
            .transpose(0, 2, 1, 3)
    else:
        k = dense(h, p["wk"], axes=ax["wk"]).reshape(B, S, Hkv, hd) \
            .transpose(0, 2, 1, 3)
        v = dense(h, p["wv"], axes=ax["wv"]).reshape(B, S, Hkv, hd) \
            .transpose(0, 2, 1, 3)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if kops.get_backend() == "pallas" and cfg.sliding_window == 0:
        out = flash_attention(q, k, v)
    else:
        out = attn_lib.chunked_attention(
            q, k, v, causal=True, sliding_window=cfg.sliding_window)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return dense(out, p["wo"], axes=ax["wo"], residual=residual)


def attn_decode(
    p: Dict,
    x: jax.Array,                    # (B, 1, D)
    cache: Dict,                     # {"k": (B,Hkv,S,d), "v": ...}
    cfg: ModelConfig,
    *,
    pos: jax.Array,                  # scalar int32 — index of this token
) -> Tuple[jax.Array, Dict]:
    B, _, D = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ax = axes_tree(attn_defs(cfg))
    h = norm(x, p["norm"], cfg)
    q = dense(h, p["wq"], axes=ax["wq"]).reshape(B, 1, H, hd) \
        .transpose(0, 2, 1, 3)
    k = dense(h, p["wk"], axes=ax["wk"]).reshape(B, 1, Hkv, hd) \
        .transpose(0, 2, 1, 3)
    v = dense(h, p["wv"], axes=ax["wv"]).reshape(B, 1, Hkv, hd) \
        .transpose(0, 2, 1, 3)
    if jnp.ndim(pos) == 0:
        posv = jnp.reshape(pos, (1,))
        q = rope(q, posv, cfg.rope_theta)
        k = rope(k, posv, cfg.rope_theta)
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos,
                                                      axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos,
                                                      axis=2)
    else:
        # Per-slot positions (continuous batching): rope per row, and each
        # row's new KV lands at that row's own cache offset.
        posv = jnp.reshape(pos, (B, 1))
        q = rope(q, posv, cfg.rope_theta)
        k = rope(k, posv, cfg.rope_theta)
        upd = jax.vmap(lambda c, blk, i:
                       jax.lax.dynamic_update_slice_in_dim(c, blk, i, axis=1))
        k_cache = upd(cache["k"], k, pos)
        v_cache = upd(cache["v"], v, pos)
    out = attn_lib.decode_attention(
        q, k_cache, v_cache, pos=pos, sliding_window=cfg.sliding_window)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * hd)
    return (dense(out, p["wo"], axes=ax["wo"]),
            {"k": k_cache, "v": v_cache})


# ---------------------------------------------------------------------------
# MLP.
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    if cfg.activation == "swiglu":
        return {
            "norm": norm_defs(cfg),
            "wg": ParamDef((D, F), ("embed", "mlp")),
            "wu": ParamDef((D, F), ("embed", "mlp")),
            "wd": ParamDef((F, D), ("mlp", "embed")),
        }
    return {
        "norm": norm_defs(cfg),
        "w1": ParamDef((D, F), ("embed", "mlp")),
        "w2": ParamDef((F, D), ("mlp", "embed")),
    }


def mlp_forward(p: Dict, x: jax.Array, cfg: ModelConfig,
                residual: Optional[jax.Array] = None) -> jax.Array:
    """Fused MLP: activations run in the GEMM epilogues, never as separate
    XLA elementwise passes; the block's residual add (when given) fuses into
    the down-projection's flush."""
    ax = axes_tree(mlp_defs(cfg))
    h = norm(x, p["norm"], cfg)
    if cfg.activation == "swiglu":
        u = dense(h, p["wu"], axes=ax["wu"])
        a = dense(h, p["wg"], axes=ax["wg"], epilogue="swiglu_gate",
                  gate=u)
        return dense(a, p["wd"], axes=ax["wd"], residual=residual)
    h1 = dense(h, p["w1"], axes=ax["w1"], epilogue="gelu")
    return dense(h1, p["w2"], axes=ax["w2"], residual=residual)
