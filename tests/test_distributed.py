"""Distribution-layer tests, run in a subprocess with 8 fake CPU devices
(XLA device count locks at first jax init, so the main pytest process
must stay at 1 device)."""
import os
import subprocess
import sys

import pytest


@pytest.mark.timeout(600)
def test_distributed_suite_on_8_fake_devices():
    worker = os.path.join(os.path.dirname(__file__), "distributed_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # the worker sets its own
    proc = subprocess.run(
        [sys.executable, worker], env=env, capture_output=True, text=True,
        timeout=560)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    for marker in ("spec_divisibility_drop", "tp_dense", "tp_serving", "compressed_psum",
                   "elastic_restore", "sharded_train_step"):
        assert f"CHECK_OK {marker}" in out, out[-4000:]
    assert "ALL_DISTRIBUTED_OK" in out


@pytest.mark.parametrize("fsdp", [False, True])
def test_kernel_boundary_splits_weights_as_param_shardings(fsdp):
    """``nn.layers.dense`` finds a weight's "model" split through
    ``model_dims``; for every registered arch's weights, with and without
    FSDP, that is the split ``param_shardings``'s rules give them, so the
    shard_map boundary never reshards a weight."""
    import dataclasses
    import jax
    from jax.sharding import AbstractMesh
    from repro.configs.registry import ARCH_IDS, get_config
    from repro.distributed.sharding import model_dims, rules_for, spec_for
    from repro.nn.model import Model
    mesh = AbstractMesh((2, 4), ("data", "model"))
    for arch in ARCH_IDS:
        cfg = dataclasses.replace(get_config(arch), fsdp=fsdp)
        model = Model(cfg)
        rules = rules_for(cfg)
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(
                lambda a, ax: (a.shape, ax), model.abstract_params(),
                model.param_axes()),
            is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple))
        for shape, ax in leaves:
            full = spec_for(shape, ax, rules, mesh)
            want = tuple(p if p == "model" else None for p in full)
            assert model_dims(shape, ax, mesh) == want, (arch, shape, ax)
