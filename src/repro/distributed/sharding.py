"""Logical-axis -> mesh-axis sharding rules (t5x-style), with auto-drop.

Every parameter declares logical axis names (layers.ParamDef); this module
maps them onto the production mesh ("pod", "data", "model").  Two safety
mechanisms make one rule table serve all ten architectures:

* divisibility auto-drop: a mapping is applied only if the dim divides by
  the mesh-axis product (e.g. mixtral's 8 experts don't divide the 16-way
  "model" axis -> the experts dim stays replicated and per-expert d_ff
  picks the axis up instead);
* first-come-first-served axes: within one array each mesh axis is used at
  most once, scanning dims left to right (e.g. qwen3 experts take "model",
  so per-expert mlp stays unsharded).

FSDP (cfg.fsdp) adds "embed" -> "data": every weight then carries a second
shard axis, and XLA SPMD inserts the ZeRO-3-style all-gathers at use sites.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.config import ModelConfig, ShapeSpec
from repro.nn.model import Model

BATCH_AXES = ("pod", "data")
SEQ_AXES = ("pod", "data", "model")    # KV-seq fallback for tiny batches


# Logical axes split over the tensor-parallel ("model") mesh axis.  No
# config changes them: FSDP only adds "data" rules (rules_for).
TP_RULES: Dict[str, Any] = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
    "expert_mlp": "model",
}


def rules_for(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        **TP_RULES,
        "state": None,
        "embed": "data" if cfg.fsdp else None,
        "embed_novar": None,          # embed/lm_head d_model: never FSDP
        # Expert axes mirror the dense rules.  Two measured dead ends
        # (EXPERIMENTS.md §Perf it. 9): F->("model","data") turns wd into
        # 256-way partial sums (4x worse); D->None un-FSDPs 268 GB of
        # mixtral expert weights (OOM).  The real fix is a dedicated EP
        # mesh axis + all-to-all dispatch (designed, not yet implemented).
        "expert_embed": "data" if cfg.fsdp else None,
        "layers": None,
        "experts_in": None,
    }


def spec_for(shape: Sequence[int], axes: Optional[Sequence[Optional[str]]],
             rules: Dict[str, Any], mesh: Mesh) -> P:
    axes = axes if axes is not None else [None] * len(shape)
    used: set = set()
    parts = []
    for dim, name in zip(shape, axes):
        target = rules.get(name) if name else None
        if target is None:
            parts.append(None)
            continue
        cand = target if isinstance(target, tuple) else (target,)
        sel = [a for a in cand if a in mesh.shape and a not in used]
        total = int(np.prod([mesh.shape[a] for a in sel])) if sel else 1
        if sel and dim % total == 0:
            parts.append(tuple(sel) if len(sel) > 1 else sel[0])
            used.update(sel)
        else:
            parts.append(None)
    return P(*parts)


def model_dims(shape: Sequence[int],
               axes: Optional[Sequence[Optional[str]]],
               mesh: Mesh) -> Tuple[Optional[str], ...]:
    """Per dim of a weight: "model" where ``param_shardings`` splits it over
    the tensor-parallel axis, else None.  The rules that only place "data"
    take no mesh axis the TP rules want, so TP_RULES alone decide this."""
    return tuple(spec_for(shape, axes, TP_RULES, mesh))


def _named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# Trees of shardings for params / optimizer / batches / caches.
# ---------------------------------------------------------------------------

def param_shardings(model: Model, mesh: Mesh) -> Any:
    rules = rules_for(model.cfg)
    abst = model.abstract_params()
    axes = model.param_axes()

    def one(a, ax):
        return _named(mesh, spec_for(a.shape, ax, rules, mesh))

    return jax.tree_util.tree_map(one, abst, axes)


def opt_shardings(param_sh: Any, mesh: Mesh) -> Any:
    """Adam m/v mirror the param shardings; the count scalar is replicated."""
    from repro.optim.adamw import OptState
    return OptState(m=param_sh, v=param_sh, count=_named(mesh, P()))


def batch_shardings(specs: Dict, mesh: Mesh) -> Dict:
    """tokens (B, S) / frame_embed (B, S, D) / patch_embed (B, P, D) /
    decode tokens (B,) / pos scalar."""
    out = {}
    for name, s in specs.items():
        if s.ndim == 0:
            out[name] = _named(mesh, P())
            continue
        batch_axes = [a for a in BATCH_AXES if a in mesh.shape]
        total = int(np.prod([mesh.shape[a] for a in batch_axes])) or 1
        first = tuple(batch_axes) if batch_axes and s.shape[0] % total == 0 \
            else None
        parts = [first] + [None] * (s.ndim - 1)
        out[name] = _named(mesh, P(*parts))
    return out


def cache_shardings(cache_specs: Any, mesh: Mesh, cfg: ModelConfig) -> Any:
    """Decode-cache layout: batch over ("pod","data"); KV sequence over
    "model" (flash-decode); with tiny batches the sequence dim absorbs the
    idle batch axes too (long_500k: S over ("pod","data","model"))."""
    def one(path, s):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        used: set = set()
        batch_axes = [a for a in BATCH_AXES if a in mesh.shape]
        bt = int(np.prod([mesh.shape[a] for a in batch_axes])) or 1

        if name.endswith("k") or name.endswith("v"):
            # (L, B, Hkv, S, d)
            _, B, Hkv, S, _ = s.shape
            parts: list = [None] * 5
            if batch_axes and B % bt == 0:
                parts[1] = tuple(batch_axes)
                used.update(batch_axes)
            seq_axes = [a for a in SEQ_AXES
                        if a in mesh.shape and a not in used]
            st = int(np.prod([mesh.shape[a] for a in seq_axes])) or 1
            if seq_axes and S % st == 0:
                parts[3] = tuple(seq_axes) if len(seq_axes) > 1 \
                    else seq_axes[0]
            return _named(mesh, P(*parts))

        # mamba caches: (L, B, ...) — batch + channel/head dims.
        parts = [None] * s.ndim
        B = s.shape[1]
        if batch_axes and B % bt == 0:
            parts[1] = tuple(batch_axes)
            used.update(batch_axes)
        if "model" in mesh.shape:
            m = mesh.shape["model"]
            # shard the widest remaining dim that divides
            order = sorted(range(2, s.ndim), key=lambda i: -s.shape[i])
            for i in order:
                if s.shape[i] % m == 0:
                    parts[i] = "model"
                    break
        return _named(mesh, P(*parts))

    paths = jax.tree_util.tree_flatten_with_path(cache_specs)
    leaves = [one(p, s) for p, s in paths[0]]
    return jax.tree_util.tree_unflatten(paths[1], leaves)


def replicated(mesh: Mesh) -> NamedSharding:
    return _named(mesh, P())
