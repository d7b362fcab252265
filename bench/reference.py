"""The plain reference of a dense decoder, its weights, and its control.

Nothing here imports the program under test.  The benchmark makes the
weights itself, from the seed, in the layout the program takes (a nested
dict of bfloat16 arrays, layers stacked on a leading axis), hands them to
the program, and runs this reference on the same arrays once the window
has closed.

The reference is the published block as the configuration file states it,
in float32 with every matrix product at ``Precision.HIGHEST``: pre-norm
(RMSNorm, or LayerNorm with a bias), grouped-query attention with rotary
embeddings (rotate-half pairing, every channel rotated), causal softmax,
SwiGLU MLP (``silu(x Wg) * (x Wu)``), residual adds, a final norm, and the
head (the embedding, transposed, when tied).

The control is the same code with every dense product's operands rounded
to float8 (e4m3, per-row scale for activations, per-column for weights)
and accumulated in float32: the next precision below the bfloat16 the
configuration states.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    norm: str                 # "rmsnorm" | "layernorm"
    norm_eps: float


def dims_of(cfg: Dict) -> Dims:
    """The sizes the reference needs, from a configuration file (the keys
    of the model's published config.json, as run)."""
    if cfg.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("the reference rotates every channel; "
                         f"partial_rotary_factor {cfg['partial_rotary_factor']}")
    if cfg.get("hidden_act") != "silu":
        raise ValueError(f"MLP activation {cfg.get('hidden_act')!r}: the "
                         f"reference has SwiGLU (silu) only")
    heads = cfg["num_attention_heads"]
    norm = cfg["norm"]
    eps = cfg["rms_norm_eps"] if norm == "rmsnorm" else cfg["norm_eps"]
    return Dims(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=heads, kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        tied=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]), norm=norm, norm_eps=float(eps))


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------

def param_shapes(d: Dims) -> Dict:
    """The layout the program takes: {path: shape}, all bfloat16."""
    L, D, F = d.layers, d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    norm = {"scale": (D,)} if d.norm == "rmsnorm" else {"scale": (D,),
                                                        "bias": (D,)}
    stacked = lambda t: {k: (L, *v) for k, v in t.items()}  # noqa: E731
    tree = {
        "embed": (d.vocab, D),
        "layers": {
            "attn": {"norm": stacked(norm), "wq": (L, D, q),
                     "wk": (L, D, kv), "wv": (L, D, kv), "wo": (L, q, D)},
            "mlp": {"norm": stacked(norm), "wg": (L, D, F),
                    "wu": (L, D, F), "wd": (L, F, D)},
        },
        "final_norm": dict(norm),
    }
    if not d.tied:
        tree["lm_head"] = (D, d.vocab)
    return tree


def _seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the driver's exceed 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(d: Dims, seed: int, shardings=None) -> Dict:
    """Seeded weights, made on the device in one jitted call, bfloat16 (the
    type they are served in): each matrix N(0, 1 / fan_in) over the
    dimension it contracts (the embedding over d_model, as the tied head
    uses it), so activations and logits keep unit scale at any width and
    attention is not flat; norm scales 1 + N(0, 0.1), norm biases
    N(0, 0.02)."""
    shapes = param_shapes(d)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = str(getattr(path[-1], "key", path[-1]))
            z = jax.random.normal(k, shape, jnp.float32)
            if name == "scale":
                v = 1.0 + 0.1 * z
            elif name == "bias":
                v = 0.02 * z
            else:
                fan_in = shape[-1] if name == "embed" else shape[-2]
                v = z / np.sqrt(fan_in)
            out.append(v.astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(make, out_shardings=shardings)
    return fn(_seed_key(seed))


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------

def _round_fp8(x: jax.Array, axis: int) -> jax.Array:
    """x rounded to float8 e4m3 under a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
    return (x / scale).astype(_FP8).astype(jnp.float32) * scale


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _round_fp8(x, -1), _round_fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x: jax.Array, p: Dict, d: Dims) -> jax.Array:
    scale = p["scale"].astype(jnp.float32)
    if d.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + d.norm_eps) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + d.norm_eps) * scale
            + p["bias"].astype(jnp.float32))


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (S, heads, hd); position of row s is s."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x: jax.Array, lp: Dict, d: Dims, fp8: bool) -> jax.Array:
    S = x.shape[0]
    H, Hkv, hd = d.heads, d.kv_heads, d.head_dim
    a = lp["attn"]
    h = _norm(x, a["norm"], d)
    q = _rope(_mm(h, a["wq"], fp8).reshape(S, H, hd), d.rope_theta)
    k = _rope(_mm(h, a["wk"], fp8).reshape(S, Hkv, hd), d.rope_theta)
    v = _mm(h, a["wv"], fp8).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(S, H * hd), a["wo"], fp8)
    m = lp["mlp"]
    h = _norm(x, m["norm"], d)
    g = jax.nn.silu(_mm(h, m["wg"], fp8)) * _mm(h, m["wu"], fp8)
    return x + _mm(g, m["wd"], fp8)


def _hidden(params: Dict, ids: jax.Array, d: Dims, fp8: bool) -> jax.Array:
    """Final-normed hidden states (S, D) of one token sequence."""
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(lambda c, lp: (_block(c, lp, d, fp8), None), x,
                        params["layers"])
    return _norm(x, params["final_norm"], d)


def _head(params: Dict, d: Dims) -> jax.Array:
    return params["embed"].T if d.tied else params["lm_head"]


@partial(jax.jit, static_argnames=("d", "control"))
def logit_gaps(params: Dict, ids: jax.Array, pos: jax.Array,
               target: jax.Array, d: Dims, control: bool = False
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """For each request r and each of its positions ``pos[r, t]``: how far
    the reference's logit of ``target[r, t]`` lies below the reference's
    best logit there.  With ``control``, also the gap of the token the
    float8 control puts first at that position.

    ids: (R, S) token ids, each row causal (padding after the last real
    token changes nothing before it); pos, target: (R, T)."""
    head = _head(params, d)

    def one(args):
        row, p, t = args
        h = _hidden(params, row, d, False)[p]
        logits = _mm(h, head, False)
        best = jnp.max(logits, axis=-1)
        gap = best - jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        hc = _hidden(params, row, d, True)[p]
        first = jnp.argmax(_mm(hc, head, True), axis=-1)
        cgap = best - jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
        return gap, cgap

    return jax.lax.map(one, (ids, pos, target))


def pack(samples, seq_len: int, max_out: int):
    """Pad sampled requests (prompt ids, served tokens) to the shapes the
    reference is compiled for.  Token i of a request is the model's output
    at position ``len(prompt) - 1 + i``.  Returns ids (R, seq_len), pos and
    target (R, max_out), and mask (R, max_out)."""
    R = len(samples)
    ids = np.zeros((R, seq_len), np.int32)
    pos = np.zeros((R, max_out), np.int32)
    target = np.zeros((R, max_out), np.int32)
    mask = np.zeros((R, max_out), bool)
    for r, (prompt, toks) in enumerate(samples):
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        if seq.size > seq_len or toks.size > max_out:
            raise ValueError(f"request {r}: {seq.size} positions, "
                             f"{toks.size} tokens; compiled for {seq_len}, "
                             f"{max_out}")
        ids[r, :seq.size] = seq
        n = toks.size
        pos[r, :n] = prompt.size - 1 + np.arange(n)
        target[r, :n] = toks
        mask[r, :n] = True
    return ids, pos, target, mask
