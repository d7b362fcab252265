"""Decoder-LM assembly for all ten families: scan-over-layers + remat.

Layer parameters are stacked on a leading "layers" axis so XLA compiles ONE
layer body regardless of depth (compile-time and remat friendly; mandatory
for the 512-device dry-run).  The hybrid (zamba2) family is scanned in
groups of ``shared_attn_every`` mamba layers followed by one application of
the *shared* attention+MLP block (single weight set reused at every
application — the Zamba trick), with a ragged tail handled outside the scan.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import layers as L
from repro.nn import mamba2, moe
from repro.nn import scanning
from repro.nn.config import ModelConfig
from repro import meshctx as dist_ctx


def _sp(h, cfg):
    """Sequence-sharded residual stream at the scan boundary (SP stash)."""
    if cfg.sp_stash:
        h = dist_ctx.constrain(h, ("pod", "data"), "model", None)
    return h


# ---------------------------------------------------------------------------
# Parameter definitions.
# ---------------------------------------------------------------------------

def layer_defs(cfg: ModelConfig) -> Dict:
    if cfg.family in ("ssm", "hybrid"):
        return {"mamba": mamba2.mamba_defs(cfg)}
    if cfg.is_moe:
        return {"attn": L.attn_defs(cfg), "moe": moe.moe_defs(cfg)}
    return {"attn": L.attn_defs(cfg), "mlp": L.mlp_defs(cfg)}


def _stack(defs, n: int):
    return L.tree_map_defs(
        lambda d: L.ParamDef((n, *d.shape), ("layers", *d.axes),
                             d.init, d.dtype, d.scale), defs)


def model_defs(cfg: ModelConfig) -> Dict:
    D, V = cfg.d_model, cfg.vocab_size
    # NB: the d_model axis of embed/lm_head uses the "embed_novar" logical
    # axis (mapped to None even under FSDP): sharding it over "data" while
    # the batch is also data-sharded makes GSPMD all-reduce full (B,S,V)
    # f32 logits across "data" — a multi-GB collective per loss chunk
    # (found in the dry-run probes; EXPERIMENTS.md §Perf).
    defs: Dict[str, Any] = {
        "embed": L.ParamDef((V, D), ("vocab", "embed_novar"), scale=0.02),
        "layers": _stack(layer_defs(cfg), cfg.num_layers),
        "final_norm": L.norm_defs(cfg),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = L.ParamDef((D, V), ("embed_novar", "vocab"),
                                     scale=0.02)
    if cfg.family == "hybrid":
        defs["shared"] = {"attn": L.attn_defs(cfg), "mlp": L.mlp_defs(cfg)}
    return defs


def _hybrid_split(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_groups, group_size, tail) for the hybrid scan structure."""
    g = cfg.shared_attn_every
    n_groups, tail = divmod(cfg.num_layers, g)
    return n_groups, g, tail


def _tree_take(tree, lo, hi, reshape=None):
    def f(a):
        s = a[lo:hi]
        return s.reshape(reshape + s.shape[1:]) if reshape else s
    return jax.tree_util.tree_map(f, tree)


# ---------------------------------------------------------------------------
# Embedding & frontend stubs.
# ---------------------------------------------------------------------------

def embed_tokens(params: Dict, tokens: jax.Array, cfg: ModelConfig,
                 extras: Optional[Dict] = None) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    extras = extras or {}
    if cfg.frontend == "audio" and "frame_embed" in extras:
        # Stub audio conditioning: precomputed frame embeddings added in.
        x = x + extras["frame_embed"].astype(x.dtype)
    if cfg.frontend == "vision" and "patch_embed" in extras:
        # Stub anyres vision tower: patch embeddings occupy the first
        # frontend_tokens positions of the sequence.
        pe = extras["patch_embed"].astype(x.dtype)
        x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)
    return x


def lm_head_weight(params: Dict, cfg: ModelConfig) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


# ---------------------------------------------------------------------------
# Attention-layer helpers shared by forward/prefill.
# ---------------------------------------------------------------------------

def _kv_for_cache(attn_p, h, positions, cfg):
    B, S, _ = h.shape
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    ax = L.axes_tree(L.attn_defs(cfg))
    hn = L.norm(h, attn_p["norm"], cfg)
    k = L.dense(hn, attn_p["wk"], axes=ax["wk"]).reshape(B, S, Hkv, hd) \
        .transpose(0, 2, 1, 3)
    v = L.dense(hn, attn_p["wv"], axes=ax["wv"]).reshape(B, S, Hkv, hd) \
        .transpose(0, 2, 1, 3)
    k = L.rope(k, positions, cfg.rope_theta)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Forward (training) — no cache.
# ---------------------------------------------------------------------------

def forward_hidden(
    params: Dict,
    tokens: jax.Array,                   # (B, S)
    cfg: ModelConfig,
    *,
    extras: Optional[Dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (final_hidden (B,S,D), moe_aux_loss)."""
    x = embed_tokens(params, tokens, cfg, extras)
    S = tokens.shape[1]
    positions = jnp.arange(S)
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        def body(h, lp):
            h = _sp(h, cfg)
            return h + mamba2.mamba_forward(lp["mamba"], h, cfg), None
        body = jax.checkpoint(body) if cfg.remat else body
        x, _ = scanning.scan(body, x, params["layers"])
        return L.norm(x, params["final_norm"], cfg), aux0

    if cfg.family == "hybrid":
        x = _hybrid_stack(params, x, positions, cfg)
        return L.norm(x, params["final_norm"], cfg), aux0

    def body(carry, lp):
        h, aux = carry
        h = _sp(h, cfg)
        # Residual adds fuse into the wo / wd GEMM flushes (f32 accumulator).
        h = L.attn_forward(lp["attn"], h, cfg, positions=positions,
                           residual=h)
        if cfg.is_moe:
            y, a = moe.moe_forward(lp["moe"], h, cfg)
            h, aux = h + y, aux + a
        else:
            h = L.mlp_forward(lp["mlp"], h, cfg, residual=h)
        return (h, aux), None

    body = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = scanning.scan(body, (x, aux0), params["layers"])
    return L.norm(x, params["final_norm"], cfg), aux


def _hybrid_stack(params, x, positions, cfg):
    n_groups, g, tail = _hybrid_split(cfg)
    shared = params["shared"]

    def mamba_body(h, lp):
        return h + mamba2.mamba_forward(lp["mamba"], h, cfg), None

    def group_body(h, gp):
        h = _sp(h, cfg)
        h, _ = scanning.scan(mamba_body, h, gp)
        h = L.attn_forward(shared["attn"], h, cfg, positions=positions,
                           residual=h)
        h = L.mlp_forward(shared["mlp"], h, cfg, residual=h)
        return h, None

    gb = jax.checkpoint(group_body) if cfg.remat else group_body
    head = _tree_take(params["layers"], 0, n_groups * g, (n_groups, g))
    x, _ = scanning.scan(gb, x, head)
    if tail:
        mb = jax.checkpoint(mamba_body) if cfg.remat else mamba_body
        x, _ = scanning.scan(mb, x,
                            _tree_take(params["layers"], n_groups * g,
                                       cfg.num_layers))
    return x


# ---------------------------------------------------------------------------
# Prefill — forward that also emits the decode cache (single pass).
# ---------------------------------------------------------------------------

def prefill_forward(
    params: Dict,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    extras: Optional[Dict] = None,
    last_pos: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Returns (last-position logits (B, V), decode cache).

    ``last_pos`` (B,) gathers each row's logits at its own final *real*
    position instead of column -1 — the ragged-admission path: prompts
    right-padded to a bucket edge still read out at their true last token
    (causal attention makes the padded tail invisible to that position)."""
    x = embed_tokens(params, tokens, cfg, extras)
    S = tokens.shape[1]
    positions = jnp.arange(S)

    if cfg.family == "ssm":
        def body(h, lp):
            y, c = mamba2.mamba_forward(lp["mamba"], h, cfg,
                                        return_cache=True)
            return h + y, c
        x, caches = scanning.scan(body, x, params["layers"])
        cache = {"mamba": caches}
    elif cfg.family == "hybrid":
        x, cache = _hybrid_prefill(params, x, positions, cfg)
    else:
        def body(carry, lp):
            h = carry
            kv = _kv_for_cache(lp["attn"], h, positions, cfg)
            h = L.attn_forward(lp["attn"], h, cfg, positions=positions,
                               residual=h)
            if cfg.is_moe:
                y, _ = moe.moe_forward(lp["moe"], h, cfg)
                h = h + y
            else:
                h = L.mlp_forward(lp["mlp"], h, cfg, residual=h)
            return h, kv
        x, cache = scanning.scan(body, x, params["layers"])

    x = L.norm(x, params["final_norm"], cfg)
    last = (x[:, -1] if last_pos is None
            else x[jnp.arange(x.shape[0]), last_pos])
    logits = jnp.matmul(last, lm_head_weight(params, cfg),
                        preferred_element_type=jnp.float32)
    return logits, cache


def _hybrid_prefill(params, x, positions, cfg):
    n_groups, g, tail = _hybrid_split(cfg)
    shared = params["shared"]

    def mamba_body(h, lp):
        y, c = mamba2.mamba_forward(lp["mamba"], h, cfg, return_cache=True)
        return h + y, c

    def group_body(h, gp):
        h, mc = scanning.scan(mamba_body, h, gp)
        kv = _kv_for_cache(shared["attn"], h, positions, cfg)
        h = L.attn_forward(shared["attn"], h, cfg, positions=positions,
                           residual=h)
        h = L.mlp_forward(shared["mlp"], h, cfg, residual=h)
        return h, (mc, kv)

    head = _tree_take(params["layers"], 0, n_groups * g, (n_groups, g))
    x, (head_mc, attn_kv) = scanning.scan(group_body, x, head)
    head_mc = jax.tree_util.tree_map(
        lambda a: a.reshape(n_groups * g, *a.shape[2:]), head_mc)
    if tail:
        x, tail_mc = scanning.scan(
            mamba_body, x,
            _tree_take(params["layers"], n_groups * g, cfg.num_layers))
        mc = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], 0), head_mc, tail_mc)
    else:
        mc = head_mc
    return x, {"mamba": mc, "attn": attn_kv}


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy — never materializes (B, S, V) at once).
# ---------------------------------------------------------------------------

def lm_loss(
    params: Dict,
    batch: Dict,
    cfg: ModelConfig,
    *,
    loss_chunk: int = 1024,
    aux_weight: float = 0.01,
) -> jax.Array:
    tokens = batch["tokens"]
    extras = {k: v for k, v in batch.items() if k != "tokens"}
    hidden, aux = forward_hidden(params, tokens, cfg, extras=extras)
    B, S, D = hidden.shape
    w = lm_head_weight(params, cfg)
    h = hidden[:, :-1]
    t = tokens[:, 1:]
    n = S - 1
    c = min(loss_chunk, n)
    pad = (-n) % c
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        t = jnp.pad(t, ((0, 0), (0, pad)))
    nc = (n + pad) // c
    h = jnp.moveaxis(h.reshape(B, nc, c, D), 1, 0)      # (nc, B, c, D)
    t = jnp.moveaxis(t.reshape(B, nc, c), 1, 0)         # (nc, B, c)
    valid = (jnp.arange(nc * c).reshape(nc, c)[:, None, :]
             < n) & jnp.ones((nc, B, c), bool)

    def chunk_nll(carry, inp):
        hc, tc, vc = inp
        logits = jnp.matmul(hc, w, preferred_element_type=jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = jnp.where(vc, logz - gold, 0.0)
        return carry + jnp.sum(nll), None

    total, _ = scanning.scan(chunk_nll, jnp.zeros((), jnp.float32),
                            (h, t, valid))
    loss = total / (B * n)
    return loss + aux_weight * aux


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------

def init_cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """ShapeDtypeStruct tree for the decode cache (dry-run: no allocation)."""
    Lc = cfg.num_layers
    if cfg.family == "ssm":
        per = mamba2.mamba_cache_defs(cfg, batch)
        return {"mamba": jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((Lc, *s.shape), s.dtype), per)}
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim

    def kv(n):
        return {
            "k": jax.ShapeDtypeStruct((n, batch, Hkv, max_len, hd),
                                      jnp.bfloat16),
            "v": jax.ShapeDtypeStruct((n, batch, Hkv, max_len, hd),
                                      jnp.bfloat16),
        }

    if cfg.family == "hybrid":
        n_groups, _, _ = _hybrid_split(cfg)
        per = mamba2.mamba_cache_defs(cfg, batch)
        return {
            "mamba": jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((Lc, *s.shape), s.dtype), per),
            "attn": kv(n_groups),
        }
    return kv(Lc)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        init_cache_specs(cfg, batch, max_len))


def decode_step(
    params: Dict,
    cache: Dict,
    tokens: jax.Array,        # (B,) int32 — the newly sampled tokens
    pos: jax.Array,           # scalar int32 — their position
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict]:
    """One serving step: logits for the next token + updated cache."""
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]   # (B, 1, D)

    if cfg.family == "ssm":
        def body(h, xs):
            lp, c = xs
            y, nc = mamba2.mamba_decode(lp["mamba"], h, c, cfg)
            return h + y, nc
        x, new_m = scanning.scan(body, x, (params["layers"], cache["mamba"]))
        new_cache = {"mamba": new_m}
    elif cfg.family == "hybrid":
        x, new_cache = _hybrid_decode(params, x, cache, pos, cfg)
    else:
        def body(h, xs):
            lp, c = xs
            y, nc = L.attn_decode(lp["attn"], h, c, cfg, pos=pos)
            h = h + y
            if cfg.is_moe:
                h = h + moe.moe_decode(lp["moe"], h, cfg)
            else:
                h = h + L.mlp_forward(lp["mlp"], h, cfg)
            return h, nc
        x, new_cache = scanning.scan(body, x, (params["layers"], cache))

    x = L.norm(x, params["final_norm"], cfg)
    logits = jnp.matmul(x[:, 0], lm_head_weight(params, cfg),
                        preferred_element_type=jnp.float32)
    return logits, new_cache


def _hybrid_decode(params, x, cache, pos, cfg):
    n_groups, g, tail = _hybrid_split(cfg)
    shared = params["shared"]

    head_p = _tree_take(params["layers"], 0, n_groups * g, (n_groups, g))
    head_c = _tree_take(cache["mamba"], 0, n_groups * g, (n_groups, g))

    def mamba_body(h, xs):
        lp, c = xs
        y, nc = mamba2.mamba_decode(lp["mamba"], h, c, cfg)
        return h + y, nc

    def group_body(h, xs):
        gp, gc, ac = xs
        h, nmc = scanning.scan(mamba_body, h, (gp, gc))
        y, nac = L.attn_decode(shared["attn"], h, ac, cfg, pos=pos)
        h = h + y
        h = h + L.mlp_forward(shared["mlp"], h, cfg)
        return h, (nmc, nac)

    x, (new_head_c, new_attn_c) = scanning.scan(
        group_body, x, (head_p, head_c, cache["attn"]))
    new_head_c = jax.tree_util.tree_map(
        lambda a: a.reshape(n_groups * g, *a.shape[2:]), new_head_c)
    if tail:
        tail_p = _tree_take(params["layers"], n_groups * g, cfg.num_layers)
        tail_c = _tree_take(cache["mamba"], n_groups * g, cfg.num_layers)
        x, new_tail_c = scanning.scan(mamba_body, x, (tail_p, tail_c))
        new_m = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            new_head_c, new_tail_c)
    else:
        new_m = new_head_c
    return x, {"mamba": new_m, "attn": new_attn_c}
