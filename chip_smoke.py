"""Bring-up smoke run of the serving path on a TPU.

    python chip_smoke.py              # one chip: phi4-mini-3.8b at full width
    python chip_smoke.py --chips 4    # four chips: minitron-8b, full, tp=4

It serves one request queue through the normal entry point
(``repro.launch.serve.run_serving`` and its ``ServingEngine``) at a
registered model's published widths, with random weights from ``SEED``,
and checks what comes out:

* every request returns ``--gen`` tokens inside the vocabulary, and a warm
  rerun of the same queue returns the same tokens;
* the decode step launches Pallas kernels (``tpu_custom_call`` in its
  lowering), and no kernel launch fell back: ``DegradedModeWarning`` is an
  error, and the ``fallback_rungs`` metric and ``fallback:*`` selections
  must stay at zero;
* the prefill and decode logits of ``max_batch`` requests, one in each
  slot of the decode step at its own position, agree with the same bf16
  weights run through the reference (pure-jnp) backend on the same mesh.

It runs only on a TPU with the Pallas backend: no CPU, interpret-mode or
reference path can make it pass.  Everything runs in this one process.  The
timings it prints are observations of one run, not benchmark numbers.  The
last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# chips -> (arch, tensor-parallel degree).  minitron-8b's 19.8 GB of bf16
# weights do not fit one 16 GB chip; at tp=4 each chip holds ~4.9 GB.
PHASES = {1: ("phi4-mini-3.8b", 1), 4: ("minitron-8b", 4)}
SEED = 0
SERVE_ARGS = ["--batch", "8", "--ragged", "--requests", "16",
              "--prompt-len", "512", "--gen", "32", "--temperature", "0",
              "--seed", str(SEED)]
KERNEL_MARKER = "tpu_custom_call"
# Largest admitted max|kernel - reference| / max|reference| over a logit
# vector.  Both backends run the same bf16 weights with f32 accumulation;
# they differ in rounding order (fused epilogues, the attention kernel), and
# bf16 activations carry those differences through every layer, so the gap
# grows with depth: about 5e-2 at 32 layers on a v5e, where the kernel path
# is no further from an f32 run than the reference path is (PERF.md).
# tests/test_smoke_tolerance.py plants kernel faults at smoke size on the
# CPU and holds each above this limit.
LOGIT_RTOL = 1e-1


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """max|got - want| / max|want| over each row's logits."""
    return (np.max(np.abs(got - want), axis=-1)
            / np.max(np.abs(want), axis=-1))


def probe_set(engine, prompts) -> list:
    """Indices of ``max_batch`` prompts, taken bucket by bucket from the
    fullest: every slot of the probe's decode step holds a request, and the
    reference engine compiles as few prefills as can be."""
    edge = engine.plan.bucket_for if engine.plan else len
    by_edge = {}
    for rid, p in enumerate(prompts):
        by_edge.setdefault(edge(len(p)), []).append(rid)
    groups = sorted(by_edge.values(), key=len, reverse=True)
    return [rid for g in groups for rid in g][:engine.max_batch]


def compare_backends(engine, prompts):
    """Probe ``engine`` (built under a kernel backend) with ``prompts``,
    one per slot, then the same weights, plan and mesh under the reference
    backend, feeding both decode steps the kernel run's greedy tokens so a
    near-tie cannot send them different inputs.  Returns the kernel probe
    and the relative errors (prefill (n,), decode (n,)).  Leaves the
    backend on auto."""
    from repro.kernels import ops
    from repro.launch.engine import ServingEngine
    kernel = engine.probe(prompts)
    ops.set_backend("reference")
    try:
        ref_engine = ServingEngine(
            engine.model, engine.params, max_batch=engine.max_batch,
            max_len=engine.max_len, plan=engine.plan, mesh=engine.mesh,
            quiet=True)
        check(KERNEL_MARKER not in ref_engine.lower_decode().as_text(),
              "the reference engine's decode step launches a kernel")
        reference = ref_engine.probe(
            prompts, next_tokens=np.argmax(kernel[0], axis=-1))
    finally:
        ops.set_backend(None)
    return kernel, tuple(rel_err(k, r) for k, r in zip(kernel, reference))


def run_phase(arch: str, tp: int, *, say) -> None:
    """Serve, rerun warm, inspect the decode step, probe against the
    reference backend.  Raises SmokeFailure on any failed check."""
    import jax
    from repro.core.selector import add_selection_hook, remove_selection_hook
    from repro.core.topology import DegradedModeWarning
    from repro.launch.serve import build_parser, run_serving
    from repro.obs import metrics as obs_metrics

    args = build_parser().parse_args(
        ["--arch", arch, "--tp", str(tp), *SERVE_ARGS, "--quiet"])

    compile_s = []

    def on_event(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(secs)

    fallbacks = []

    def on_selection(_sel, source: str) -> None:
        if source.startswith("fallback:"):
            fallbacks.append(source)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    add_selection_hook(on_selection)
    prev_metrics = obs_metrics.enable_metrics(True)
    obs_metrics.get_registry().clear()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", DegradedModeWarning)
            t0 = time.perf_counter()
            out = run_serving(args)
            t_cold = time.perf_counter() - t0
            cold_compiles = list(compile_s)
            engine, prompts = out["engine"], out["prompts"]
            vocab = engine.model.cfg.vocab_size

            rows = [out["results"][r].tokens for r in sorted(out["results"])]
            check(len(rows) == args.requests,
                  f"{len(rows)} of {args.requests} requests returned")
            for rid, row in enumerate(rows):
                check(row.shape == (args.gen,),
                      f"request {rid} returned {row.shape[0]} tokens, "
                      f"not {args.gen}")
                check(bool(np.all((row >= 0) & (row < vocab))),
                      f"request {rid} returned tokens outside [0, {vocab})")
            say(f"{arch}: served {len(rows)} requests x {args.gen} tokens, "
                f"all inside vocab {vocab}")

            # Warm rerun of the same queue: same programs, so no compiles
            # and the same greedy tokens.
            del compile_s[:]
            for p in prompts:
                engine.submit(p, max_new_tokens=args.gen)
            warm = engine.run()
            warm_rows = [warm["results"][r].tokens
                         for r in sorted(warm["results"])]
            check(all(np.array_equal(a, b) for a, b in zip(rows, warm_rows)),
                  "warm rerun of the same queue returned other tokens")
            say(f"compile: {sum(cold_compiles):.1f} s in "
                f"{len(cold_compiles)} backend compiles during the cold run "
                f"({t_cold:.1f} s wall); {len(compile_s)} compiles during "
                f"the warm rerun")
            # The engine's run wall time ends in block_until_ready; the
            # prefills of admitted requests fall inside it.
            wall = warm["t_decode_s"]
            say(f"warm rerun: {warm['tokens_emitted']} tokens for "
                f"{len(prompts)} requests (prompts of "
                f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens) "
                f"in {wall:.3f} s wall, {warm['tokens_emitted'] / wall:.1f} "
                f"tok/s end to end; {warm['steps']} decode steps at batch "
                f"{args.batch}, {wall / warm['steps'] * 1e3:.2f} ms/step "
                f"with the {len(prompts)} prefills included")

            hlo = engine.lower_decode().as_text()
            n_kernels = hlo.count(KERNEL_MARKER)
            say(f"decode step: {n_kernels} {KERNEL_MARKER} in its lowering")
            check(n_kernels > 0, "the decode step launches no Pallas kernel")

            rids = probe_set(engine, prompts)
            batch = [prompts[r] for r in rids]
            _, (prefill, decode) = compare_backends(engine, batch)
            # Warm now; probe() returns host arrays, so its wall time is
            # synchronized.
            t0 = time.perf_counter()
            engine.probe(batch)
            lens = [len(p) for p in batch]
            say(f"requests {rids} in slots 0-{len(rids) - 1} (prompts of "
                f"{min(lens)}-{max(lens)} tokens): {len(rids)} prefills and "
                f"one decode step in {(time.perf_counter() - t0) * 1e3:.2f} "
                f"ms, synchronized")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        remove_selection_hook(on_selection)

    rungs = sum(m.value for m in obs_metrics.get_registry().metrics()
                if m.name == "fallback_rungs")
    obs_metrics.enable_metrics(prev_metrics)
    say(f"fallback rungs: {rungs}; fallback selections: {len(fallbacks)}")
    check(rungs == 0 and not fallbacks,
          f"kernel launches fell back: {rungs} rungs, {fallbacks}")

    say(f"logits vs reference backend, max|diff|/max|ref| per request "
        f"{rids}: prefill " + " ".join(f"{e:.3e}" for e in prefill)
        + "; decode " + " ".join(f"{e:.3e}" for e in decode)
        + f" (tolerance {LOGIT_RTOL:.0e})")
    worst = float(max(prefill.max(), decode.max()))
    check(worst <= LOGIT_RTOL,
          f"logit error {worst:.3e} above tolerance {LOGIT_RTOL:.0e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1: phi4-mini-3.8b on one chip; 4: minitron-8b at "
                         "tp=4 on a four-chip host (that phase only)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()

    import jax
    from repro.kernels import ops

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']}; compile cache {cache_dir}")
    try:
        check(device["platform"] == "tpu",
              f"no TPU: JAX runs on {device['platform']!r}")
        check(ops.get_backend() == "pallas",
              f"kernel backend is {ops.get_backend()!r}, not 'pallas'")
        check(device["count"] == args.chips,
              f"this phase needs {args.chips} chip(s); JAX sees "
              f"{device['count']}")
        try:
            hw = ops.get_default_hardware()     # keyed by device_kind
        except KeyError as e:
            raise SmokeFailure(str(e)) from None
        say(f"serving against the {hw.name} preset")
        arch, tp = PHASES[args.chips]
        run_phase(arch, tp, say=say)
        mem = dev.memory_stats() or {}
        say(f"peak_bytes_in_use (device 0): "
            f"{mem.get('peak_bytes_in_use', 'not reported')}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
