"""The system under test, as the benchmark drives it: the program's model
built from a configuration file, its mesh and weight layout, its bucket
plan, and its ``ServingEngine`` on the ``pallas`` backend.

Only this module (and the per-layer readers, through the records it
passes on) imports the program.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax

from bench.reference import Dims


def model_config(name: str, d: Dims, dtype: str):
    """The program's ``ModelConfig`` for a configuration file's sizes."""
    from repro.nn.config import ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=d.layers, d_model=d.d_model,
        num_heads=d.heads, num_kv_heads=d.kv_heads, head_dim=d.head_dim,
        d_ff=d.d_ff, vocab_size=d.vocab, rope_theta=d.rope_theta,
        norm=d.norm, activation="swiglu", tie_embeddings=d.tied,
        dtype=dtype)


def check_norm_eps(d: Dims) -> None:
    """The program's norms have fixed epsilons; a file that states another
    one describes a model the program does not run."""
    want = {"rmsnorm": 1e-6, "layernorm": 1e-5}[d.norm]
    if d.norm_eps != want:
        raise ValueError(f"{d.norm} epsilon {d.norm_eps}: the program "
                         f"runs {want}")


def build(name: str, d: Dims, dtype: str, tp: int):
    """(Model, mesh, param shardings) for ``tp``-way tensor parallelism
    over the attached devices."""
    from repro.distributed import param_shardings
    from repro.launch.mesh import make_local_mesh
    from repro.nn.model import Model
    check_norm_eps(d)
    model = Model(model_config(name, d, dtype))
    mesh = make_local_mesh(tp=tp)
    return model, mesh, param_shardings(model, mesh)


def check_layout(model, params: Dict) -> None:
    """The benchmark's weights must be exactly the tree the program
    declares: same paths, shapes and dtypes."""
    want = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype),
                                  model.abstract_params())
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"program {want}\nbenchmark {got}")


def plan(model, prompt_lens: List[int], buckets: int):
    """The program's model-priced bucket plan over the mix's lengths."""
    from repro.core.bucketing import plan_buckets, step_gemms
    from repro.kernels import ops
    cfg = model.cfg
    return plan_buckets(
        prompt_lens,
        gemms=step_gemms(cfg.d_model, cfg.d_ff,
                         kv_dim=cfg.num_kv_heads * cfg.head_dim,
                         vocab=cfg.vocab_size, swiglu=True),
        hw=ops.get_default_hardware(), max_buckets=buckets)


def engine(model, params, mesh, *, max_batch: int, max_len: int, plan,
           sync_every: int, hook, seed: int):
    from repro.launch.engine import ServingEngine
    return ServingEngine(model, params, max_batch=max_batch,
                         max_len=max_len, plan=plan, temperature=0.0,
                         seed=seed, sync_every=sync_every,
                         decode_fault=hook, quiet=True, mesh=mesh)


def backend() -> str:
    from repro.kernels import ops
    return ops.get_backend()


def set_backend(name: Optional[str]) -> None:
    from repro.kernels import ops
    ops.set_backend(name)


def compile_cache() -> str:
    from repro.launch.compile_cache import setup_compile_cache
    return setup_compile_cache()
