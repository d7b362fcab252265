"""End-to-end behaviour tests: the training and serving drivers as a user
would run them (CLI mains), plus dry-run cell machinery on tiny configs."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _run(args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.timeout(600)
def test_train_driver_end_to_end(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    log = str(tmp_path / "metrics.jsonl")
    p = _run(["-m", "repro.launch.train", "--arch", "phi4-mini-3.8b",
              "--smoke", "--steps", "30", "--batch", "4", "--seq", "64",
              "--lr", "1e-2", "--ckpt-dir", ckpt, "--ckpt-every", "10",
              "--log", log])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    recs = [json.loads(l) for l in open(log)]
    assert len(recs) == 30
    first, last = recs[0]["loss"], recs[-1]["loss"]
    assert last < first, (first, last)           # it learns
    assert os.path.isdir(os.path.join(ckpt, "step_000000030"))

    # restart from checkpoint: picks up at step 30, runs 10 more
    p2 = _run(["-m", "repro.launch.train", "--arch", "phi4-mini-3.8b",
               "--smoke", "--steps", "40", "--batch", "4", "--seq", "64",
               "--lr", "1e-2", "--ckpt-dir", ckpt, "--log", log])
    assert p2.returncode == 0, p2.stdout[-3000:] + p2.stderr[-3000:]
    assert "restored checkpoint at step 30" in p2.stdout
    recs = [json.loads(l) for l in open(log)]
    assert recs[-1]["step"] == 40


@pytest.mark.timeout(600)
def test_serve_driver_end_to_end():
    p = _run(["-m", "repro.launch.serve", "--arch", "mamba2-370m",
              "--smoke", "--batch", "2", "--prompt-len", "16",
              "--gen", "8"])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "decoded 7 steps" in p.stdout


@pytest.mark.timeout(600)
def test_dryrun_cell_on_tiny_mesh(tmp_path):
    """The dry-run machinery itself (lower+compile+roofline) on 8 fake
    devices with a smoke config — exercises the exact code path of the
    512-device run without its compile cost."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import dataclasses, json
import jax
from repro.configs.registry import get_config, get_shape
from repro.launch import dryrun
from repro.launch.mesh import make_local_mesh
from repro.nn.model import Model
from repro.kernels import set_backend
from repro.core.roofline import cost_analysis_terms, parse_collective_bytes
set_backend("reference")
cfg = get_config("phi4-mini-3.8b", smoke=True)
cfg = dataclasses.replace(cfg, d_model=64, num_heads=4, num_kv_heads=4,
                          d_ff=256, vocab_size=512)
model = Model(cfg)
mesh = make_local_mesh(tp=4)
shape = dataclasses.replace(get_shape("train_4k"), seq_len=128,
                            global_batch=4)
jitted, args = dryrun._lower_cell(model, cfg, shape, mesh)
compiled = jitted.lower(*args).compile()
fl, by = cost_analysis_terms(compiled)
colls = parse_collective_bytes(compiled.as_text())
assert fl > 0 and by > 0, (fl, by)
assert colls["total"] > 0, colls      # sharded grads MUST produce collectives
ma = compiled.memory_analysis()
assert ma.temp_size_in_bytes > 0
print("DRYRUN_CELL_OK", fl, colls["total"])
"""
    p = _run(["-c", code])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "DRYRUN_CELL_OK" in p.stdout


@pytest.mark.timeout(300)
def test_collective_parser_units():
    from repro.core.roofline import parse_collective_bytes
    hlo = """
  %all-reduce.1 = f32[256,1024]{1,0} all-reduce(%dot), channel_id=1
  %ag = bf16[64,32]{1,0} all-gather(%x), dimensions={0}
  %rs.2 = f32[16]{0} reduce-scatter(%y)
  %cp = (f32[8]{0}, f32[8]{0}) collective-permute-start(%z)
  %name-with-all-reduce-inside = f32[4]{0} add(%a, %b)
"""
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"] == 256 * 1024 * 4
    assert out["all-gather"] == 64 * 32 * 2
    assert out["reduce-scatter"] == 16 * 4
    assert out["collective-permute"] == 8 * 4 * 2
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


@pytest.mark.timeout(300)
def test_chip_smoke_refuses_a_host_without_tpu():
    """chip_smoke.py must fail, and print no ok line, where JAX finds no
    TPU: no CPU, interpret-mode or reference path may pass for the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert '"ok": true' not in p.stdout
    assert "no TPU" in p.stderr


def test_compile_cache_follows_env_else_fixed_checkout_path(tmp_path,
                                                           monkeypatch):
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # left alone
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_cache")
        assert setup_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
