"""Worker script run in a subprocess with 8 fake CPU devices.

Each check exercises the distribution layer on a real (2, 4) mesh:
sharded train steps, the kernels' shard_map boundary in nn.layers, serving
at tp=4, compressed DP psum, elastic checkpoint restore onto a different
mesh shape.  Invoked by
tests/test_distributed.py; prints CHECK_OK markers the test asserts on.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.registry import get_config            # noqa: E402
from repro.distributed import (batch_shardings,           # noqa: E402
                               opt_shardings, param_shardings, replicated,
                               spec_for, rules_for)
from repro.kernels import ref, set_backend                # noqa: E402
from repro.launch.mesh import make_local_mesh             # noqa: E402
from repro.meshctx import use_mesh                        # noqa: E402
from repro.nn import layers as L                          # noqa: E402
from repro.launch.steps import (TrainState,               # noqa: E402
                                make_train_step)
from repro.nn.model import Model                          # noqa: E402
from repro.optim import AdamW, compressed_psum            # noqa: E402


def check_sharded_train_step():
    mesh = make_local_mesh(tp=4)                          # (2, 4) mesh
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    # widen smoke dims so the 4-way model axis divides everything
    import dataclasses
    cfg = dataclasses.replace(cfg, d_model=64, num_heads=4, num_kv_heads=4,
                              d_ff=256, vocab_size=512, fsdp=True)
    model = Model(cfg)
    opt = AdamW(lr=1e-2)
    p_sh = param_shardings(model, mesh)
    state_sh = TrainState(params=p_sh, opt=opt_shardings(p_sh, mesh),
                          step=replicated(mesh))
    params = jax.jit(model.init, out_shardings=p_sh)(jax.random.PRNGKey(0))
    state = TrainState(params=params, opt=opt.init(params),
                       step=jnp.zeros((), jnp.int32))
    B, S = 4, 32
    specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    b_sh = batch_shardings(specs, mesh)
    step = jax.jit(make_train_step(model, opt),
                   in_shardings=(state_sh, b_sh),
                   out_shardings=(state_sh, replicated(mesh)),
                   donate_argnums=(0,))
    batch = {"tokens": jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 512),
        b_sh["tokens"])}
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    # params must actually be sharded over the model axis
    leaf = state.params["layers"]["mlp"]["wg"]
    assert len(leaf.sharding.spec) >= 1
    print("CHECK_OK sharded_train_step")


def check_tp_dense():
    """Pallas kernels (interpret mode) under an installed (2, 4) mesh run
    inside shard_map on per-chip shapes and match the reference: column-
    and row-sharded GEMMs (the row psum precedes the fused epilogue), a
    replicated weight, and flash attention over heads, with and without
    kv heads the model axis divides."""
    mesh = make_local_mesh(tp=4)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((4, 8, 256)), jnp.float32)
    y = np.asarray(x @ w)
    col, row = ("embed", "mlp"), ("mlp", "embed")
    cases = [(col, {"residual": r}, y + np.asarray(r)),
             (row, {"residual": r}, y + np.asarray(r)),
             (row, {"epilogue": "gelu"}, np.asarray(jax.nn.gelu(x @ w))),
             (None, {}, y)]
    set_backend("pallas_interpret")
    try:
        with use_mesh(mesh):
            for axes, kw, want in cases:
                got = jax.jit(lambda x, w: L.dense(x, w, axes=axes, **kw)
                              )(x, w)
                np.testing.assert_allclose(np.asarray(got), want,
                                           rtol=1e-4, atol=1e-3)
            hlo = jax.jit(lambda x, w: L.dense(x, w, axes=row)) \
                .lower(x, w).compile().as_text()
            assert "all-reduce" in hlo
            for hkv in (4, 2):
                q = jnp.asarray(rng.standard_normal((2, 8, 128, 64)),
                                jnp.float32)
                k, v = (jnp.asarray(rng.standard_normal((2, hkv, 128, 64)),
                                    jnp.float32) for _ in range(2))
                got = jax.jit(L.flash_attention)(q, k, v)
                want = ref.attention_ref(q, k, v, causal=True)
                np.testing.assert_allclose(np.asarray(got),
                                           np.asarray(want),
                                           rtol=1e-4, atol=1e-4)
    finally:
        set_backend(None)
    print("CHECK_OK tp_dense")


def check_tp_serving():
    """run_serving at tp=4 on the (2, 4) mesh with interpret-mode kernels:
    the engine keeps its cache on the mesh, every request completes, and
    probe logits match the reference backend on the same weights and mesh."""
    import argparse
    from repro.launch.engine import ServingEngine
    from repro.launch.serve import run_serving
    args = argparse.Namespace(
        arch="minitron-8b", smoke=True, batch=4, prompt_len=16, gen=4,
        temperature=0.0, tp=4, seed=0, ragged=True, requests=6, quiet=True)
    set_backend("pallas_interpret")
    try:
        out = run_serving(args)
        eng = out["engine"]
        assert dict(eng.mesh.shape) == {"data": 2, "model": 4}
        assert all(len(r.tokens) == 4 for r in out["results"].values())
        prompts = out["prompts"][:4]          # one per slot
        kernel = eng.probe(prompts)
        set_backend("reference")
        ref_eng = ServingEngine(eng.model, eng.params, max_batch=4,
                                max_len=eng.max_len, plan=eng.plan,
                                mesh=eng.mesh, quiet=True)
        want = ref_eng.probe(prompts,
                             next_tokens=np.argmax(kernel[0], axis=-1))
    finally:
        set_backend(None)
    for got, ref_logits in zip(kernel, want):
        err = (np.max(np.abs(got - ref_logits), axis=-1)
               / np.max(np.abs(ref_logits), axis=-1))
        assert err.shape == (4,) and np.all(err < 2e-2), err
    print("CHECK_OK tp_serving")


def check_compressed_psum():
    mesh = make_local_mesh(tp=1)                          # (8, 1)
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((8, 64)), dtype=jnp.float32)
    err = jnp.zeros((8, 64), jnp.float32)

    def f(gl, el):
        mean, new_err = compressed_psum(gl, el, "data")
        return mean, new_err

    # Replication check off: the all_gather+local-reduce result is replicated
    # by construction, but jax cannot prove invariance across "data".
    mean, new_err = jax.shard_map(
        f, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(None), P("data")), check_vma=False)(g, err)
    # Each device's row of `mean` is the mean over devices within int8 error.
    want = np.asarray(jnp.mean(g, axis=0))
    got = np.asarray(mean)[0]
    amax = float(jnp.max(jnp.abs(g)))
    assert np.max(np.abs(got - want)) <= amax / 127.0 + 1e-5
    print("CHECK_OK compressed_psum")


def check_elastic_restore():
    import tempfile
    from repro.checkpoint import restore, save
    mesh_a = make_local_mesh(tp=4)
    mesh_b = make_local_mesh(tp=2)                        # different mesh!
    cfg = get_config("mamba2-370m", smoke=True)
    import dataclasses
    cfg = dataclasses.replace(cfg, d_model=64, vocab_size=512)
    model = Model(cfg)
    p_sh_a = param_shardings(model, mesh_a)
    params = jax.jit(model.init, out_shardings=p_sh_a)(jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        save(d, 5, params)
        p_sh_b = param_shardings(model, mesh_b)
        step, back = restore(d, model.abstract_params(), shardings=p_sh_b)
        assert step == 5
        a = np.asarray(jax.device_get(params["embed"]))
        b = np.asarray(jax.device_get(back["embed"]))
        np.testing.assert_array_equal(a, b)
    print("CHECK_OK elastic_restore")


def check_spec_divisibility_drop():
    mesh = make_local_mesh(tp=4)
    rules = rules_for(get_config("mixtral-8x22b"))
    # experts=3 does not divide 4 -> dropped; mlp picks up "model"
    spec = spec_for((3, 64, 256), ("experts", "embed", "mlp"), rules, mesh)
    assert spec[0] is None and spec[2] == "model", spec
    # experts=8 divides 4 -> kept; mlp then blocked (axis used)
    spec = spec_for((8, 64, 256), ("experts", "embed", "mlp"), rules, mesh)
    assert spec[0] == "model" and spec[2] is None, spec
    print("CHECK_OK spec_divisibility_drop")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.device_count()
    check_spec_divisibility_drop()
    check_tp_dense()
    check_tp_serving()
    check_compressed_psum()
    check_elastic_restore()
    check_sharded_train_step()
    print("ALL_DISTRIBUTED_OK")
