"""Serving driver: continuous-batching engine over ragged or uniform
requests.

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --smoke --batch 4 --prompt-len 32 --gen 32

    # ragged prompts admitted into model-priced buckets:
    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --smoke --batch 4 --prompt-len 32 --gen 32 --ragged --requests 12

Requests flow through :class:`repro.launch.engine.ServingEngine`: a FIFO
queue admits prompts into free decode slots (per-request prefill, generic
slot insert), ragged lengths are right-padded to the edges of a
model-priced :class:`~repro.core.bucketing.BucketPlan` (attention
families; exact by causality), finished sequences free their slot
mid-decode, and every bucket edge's step GEMMs are warm-selected in one
batched call before serving.  The decode loop is host-round-trip free:
tokens stay on device until one end-of-run stack, RNG keys are pre-split
per global step, and the status line prints the median step time (from
the engine's ready stamps at each sync) beside the median host dispatch.

Set ``REPRO_SELECTION_CACHE=/path/to/selections.json`` to persist GEMM
config selections across server processes: a warm restart replays every
previously selected shape from disk with zero cold-path scoring.

Fail-soft serving (DESIGN.md §9) is unchanged from the engine's side:
``--topology`` loads a calibrated-topology artifact through the *guarded*
loader (corrupt artifacts quarantine, serving continues on the stock
preset); prefill and every decode step are transient-retried; a
:class:`~repro.runtime.fault_tolerance.PreemptionGuard` drains the batch
cleanly on SIGTERM/SIGINT.  ``run_serving`` is the library entry point the
fault-injection suite drives directly (``decode_fault`` hook); ``main``
is the CLI shim.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Callable, Dict, Optional

import numpy as np

import jax

from repro.configs.registry import ARCH_IDS, get_config
from repro.core.bucketing import plan_buckets, step_gemms
from repro.core.selector import (get_residual_corrector,
                                 load_selection_cache, select_gemm_config,
                                 set_residual_corrector)
from repro.core.simulator import simulate_gemm
from repro.core.topology import load_calibrated_topology_guarded
from repro.distributed import param_shardings
from repro.kernels import ops
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.engine import ServingEngine
from repro.launch.mesh import make_local_mesh
from repro.nn.frontends import synth_frontend_inputs
from repro.nn.model import Model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.drift import DriftMonitor, set_drift_monitor
from repro.obs.perfetto import export_chrome_trace


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (max concurrent sequences)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--topology", default=None, metavar="PATH",
                    help="calibrated-topology artifact to select against "
                         "(guarded load: corrupt artifacts quarantine and "
                         "fall back to the stock preset)")
    ap.add_argument("--residual", default=None, metavar="PATH",
                    help="residual-corrector artifact (repro/residual/v1) "
                         "to re-price top-ranked candidates with (guarded "
                         "load: corrupt artifacts quarantine, stale "
                         "fingerprints are ignored; serving falls back to "
                         "the pure analytical model)")
    ap.add_argument("--ragged", action="store_true",
                    help="draw ragged prompt lengths in "
                         "[prompt-len/2, prompt-len] and admit them into "
                         "model-priced buckets (attention families)")
    ap.add_argument("--requests", type=int, default=None,
                    help="number of requests to serve "
                         "(default: --batch; ragged default: 2x)")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode steps between device syncs (the "
                         "granularity of the step-time stamps)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress stdout status lines (they still flow "
                         "through the trace/metrics layer)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="enable telemetry and write trace.json (Perfetto), "
                         "metrics.prom, metrics.jsonl and drift.jsonl "
                         "under DIR")
    return ap


def run_serving(args: argparse.Namespace, *,
                decode_fault: Optional[Callable[..., None]] = None,
                ) -> Dict:
    """Serve one request queue end to end; returns the serving stats.

    ``decode_fault(step, guard)``, when given, runs at the top of every
    decode step's retried body — *before* the donated-cache decode
    executes, so a raise is retried against an intact cache.  This is the
    fault-injection suite's hook (``repro.calib.faults.decode_injector``);
    production never sets it.

    Returns a dict with ``tokens`` (uniform mode: the (batch, steps+1)
    generated array including the prefill token; ragged mode: a list of
    per-request arrays), ``drained`` (True when a preemption request
    stopped decode early), ``steps`` (decode steps completed), ``retries``
    (transient retries absorbed), timings (``t_admit_s``, the host time
    spent admitting; ``t_decode_s``), engine stats (``pad_fraction``,
    ``bucket_hits``, ``tokens_per_s``), the topology served
    against (plus ``degraded`` when the artifact was rejected), the
    request ``prompts`` in rid order, and the ``engine`` itself (its
    programs stay compiled for probes and reruns).

    ``--quiet`` suppresses the stdout status lines (they still flow
    through the trace layer as events); ``--trace-dir DIR`` installs the
    telemetry subsystem for the run and writes ``trace.json`` (Perfetto,
    with the decode-step GEMMs' simulator timelines), ``metrics.prom``,
    ``metrics.jsonl`` and ``drift.jsonl`` under DIR.  The stats dict is
    identical either way.
    """
    quiet = bool(getattr(args, "quiet", False))
    trace_dir = getattr(args, "trace_dir", None)

    def _say(msg: str) -> None:
        obs_trace.event("status", cat="serve", track="serve",
                        args={"msg": msg})
        if not quiet:
            print(msg)

    prev_tracer = prev_mon = drift_mon = None
    prev_metrics = False
    # _run_serving installs the --residual corrector after the topology is
    # known; restore whatever was there before, success or raise.
    prev_res = get_residual_corrector()
    if trace_dir:
        prev_tracer = obs_trace.set_tracer(obs_trace.Tracer())
        prev_metrics = obs_metrics.enable_metrics(True)
        obs_metrics.get_registry().clear()
        drift_mon = DriftMonitor(path=os.path.join(trace_dir,
                                                   "drift.jsonl"))
        prev_mon = set_drift_monitor(drift_mon)
    try:
        out = _run_serving(args, decode_fault=decode_fault, say=_say,
                           quiet=quiet)
        if trace_dir:
            _export_telemetry(trace_dir, args)
        return out
    finally:
        set_residual_corrector(prev_res)
        if trace_dir:
            obs_trace.set_tracer(prev_tracer)
            set_drift_monitor(prev_mon)
            drift_mon.close()
            obs_metrics.enable_metrics(prev_metrics)


def _export_telemetry(trace_dir: str, args: argparse.Namespace) -> None:
    """Write the run's telemetry artifacts: the Perfetto trace (measured
    tracer spans + the decode-step GEMMs' modeled simulator timelines),
    the Prometheus textfile, and a metrics JSONL snapshot.  The drift
    JSONL streams during the run (``DriftMonitor``)."""
    cfg = get_config(args.arch, smoke=args.smoke)
    hw = ops.get_default_hardware()
    sim_timelines = []
    if cfg.family != "ssm":
        gemms = step_gemms(cfg.d_model, cfg.d_ff,
                           kv_dim=cfg.num_kv_heads * cfg.head_dim,
                           vocab=cfg.vocab_size,
                           swiglu=cfg.activation == "swiglu")[:3]
        for (n, k) in gemms:
            sel = select_gemm_config(args.batch, n, k, hw=hw)
            ev: list = []
            simulate_gemm(sel.problem, sel.config, hw, events=ev)
            sim_timelines.append((f"gemm {args.batch}x{n}x{k}", ev))
    tracer = obs_trace.get_tracer()
    export_chrome_trace(os.path.join(trace_dir, "trace.json"),
                        tracer.spans if tracer is not None else [],
                        sim_timelines)
    reg = obs_metrics.get_registry()
    reg.write_prometheus(os.path.join(trace_dir, "metrics.prom"))
    reg.write_jsonl(os.path.join(trace_dir, "metrics.jsonl"),
                    kind="serving", arch=args.arch)


def _run_serving(args: argparse.Namespace, *,
                 decode_fault: Optional[Callable[..., None]],
                 say: Callable[[str], None], quiet: bool) -> Dict:
    n_warm = load_selection_cache()            # $REPRO_SELECTION_CACHE
    if n_warm:
        say(f"[selector] warm-started {n_warm} persisted GEMM selections")

    stock = ops.get_default_hardware()
    topo_info: Dict = {"topology": stock.name, "degraded": None}
    if getattr(args, "topology", None):
        topo, prov = load_calibrated_topology_guarded(args.topology, stock)
        ops.set_default_hardware(topo)
        topo_info = {"topology": topo.name,
                     "degraded": prov.get("degraded"),
                     "quarantined": prov.get("quarantined")}
        if prov.get("degraded"):
            say(f"[serve] topology artifact rejected "
                f"({prov['degraded']}); serving on stock "
                f"preset {topo.name}")
        else:
            say(f"[serve] serving against calibrated topology "
                f"{topo.name}")

    res_info: Dict = {"residual": None, "residual_degraded": None}
    if getattr(args, "residual", None):
        # Guarded load against the topology actually served (which the
        # --topology block above may have just swapped in); run_serving's
        # finally restores the previous corrector.
        from repro.calib.residual import load_residual_guarded
        corr, rprov = load_residual_guarded(
            args.residual, expect=ops.get_default_hardware())
        if corr is None:
            res_info["residual_degraded"] = rprov.get("degraded")
            say(f"[serve] residual artifact rejected "
                f"({rprov.get('degraded')}); serving on the pure "
                f"analytical model")
        else:
            set_residual_corrector(corr)
            res_info["residual"] = corr.content_fingerprint()
            say(f"[serve] residual corrector active (digest "
                f"{corr.content_fingerprint()}, top-{corr.top_f} "
                f"re-pricing, fit on {corr.provenance.get('n_rows', '?')} "
                f"drift rows)")

    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg)
    max_len = args.prompt_len + args.gen
    ragged = bool(getattr(args, "ragged", False))
    n_req = getattr(args, "requests", None) or (
        2 * args.batch if ragged else args.batch)

    rng = jax.random.PRNGKey(args.seed)
    mesh = make_local_mesh(tp=args.tp)
    p_sh = param_shardings(model, mesh)
    params = jax.jit(model.init, out_shardings=p_sh)(rng)

    # Request prompts: uniform rows of prompt-len, or ragged truncations.
    prompts = np.asarray(jax.random.randint(
        rng, (n_req, args.prompt_len), 0, cfg.vocab_size), np.int32)
    extras = synth_frontend_inputs(cfg, rng, n_req, args.prompt_len)
    if ragged:
        lo = max(args.prompt_len // 2, 4)
        lens = np.random.default_rng(args.seed).integers(
            lo, args.prompt_len + 1, size=n_req).tolist()
    else:
        lens = [args.prompt_len] * n_req

    plan = None
    if ragged and cfg.family not in ("ssm", "hybrid"):
        plan = plan_buckets(
            lens,
            gemms=step_gemms(cfg.d_model, cfg.d_ff,
                             kv_dim=cfg.num_kv_heads * cfg.head_dim,
                             vocab=cfg.vocab_size,
                             swiglu=cfg.activation == "swiglu"),
            hw=ops.get_default_hardware(), max_buckets=4)
        say(f"[serve] priced bucket edges: {list(plan.edges)} "
            f"(modeled step {plan.modeled_total_s * 1e3:.2f}ms, "
            f"pad {plan.pad_fraction * 100:.1f}%)")

    engine = ServingEngine(
        model, params, max_batch=args.batch, max_len=max_len, plan=plan,
        temperature=args.temperature, seed=args.seed,
        sync_every=getattr(args, "sync_every", 8),
        decode_fault=decode_fault, quiet=quiet,
        mesh=mesh)

    def _extras(i):
        if not extras:
            return None
        return jax.tree_util.tree_map(lambda x: x[i:i + 1], extras)

    for i in range(n_req):
        engine.submit(prompts[i, :lens[i]], max_new_tokens=args.gen,
                      extras=_extras(i))

    t0 = time.time()
    warmed = engine.warm_start()
    if warmed:
        say(f"[serve] warm-started {warmed} serving GEMM shapes in one "
            f"batched selection pass ({(time.time() - t0) * 1e3:.0f}ms)")

    stats = engine.run()
    results = stats["results"]
    n_steps = stats["steps"]

    rows = [results[r].tokens for r in sorted(results)]
    if (not ragged and n_req == args.batch
            and len({len(r) for r in rows}) <= 1):
        # Uniform mode: all requests admitted together and same length —
        # the legacy (batch, steps+1) matrix, prefill token first.
        tokens = (np.stack(rows) if rows else np.zeros((0, 0), np.int32))
    else:
        tokens = rows

    toks_per_s = stats["tokens_per_s"]
    say(f"arch={cfg.name} batch={args.batch} requests={n_req} "
        f"prefill {args.prompt_len} tok; admitting took "
        f"{stats['t_admit_s'] * 1e3:.0f}ms of host time; "
        f"decoded {n_steps} steps at {toks_per_s:.1f} tok/s total")
    say(f"[serve] step {median_step_s(stats) * 1e3:.2f}ms "
        f"(median between syncs) vs dispatch "
        f"{np.median(stats['step_dispatch_s'] or [0.0]) * 1e3:.2f}ms "
        f"(median); "
        f"padding {stats['pad_fraction'] * 100:.1f}%; "
        f"bucket hits {stats['bucket_hits']}")
    show = tokens if ragged else tokens[:2]
    say("sample generations (first 2 rows, first 16 tokens):")
    for row in list(show)[:2]:
        say(f"   {np.asarray(row)[:16].tolist()}")
    return {
        "tokens": tokens,
        "steps": n_steps,
        "drained": stats["drained"],
        "retries": stats["retries"],
        "t_admit_s": stats["t_admit_s"],
        "t_decode_s": stats["t_decode_s"],
        "tokens_per_s": toks_per_s,
        "pad_fraction": stats["pad_fraction"],
        "bucket_hits": stats["bucket_hits"],
        "residual_active": stats["residual_active"],
        "results": results,
        "prompts": [prompts[i, :lens[i]] for i in range(n_req)],
        "engine": engine,
        **topo_info,
        **res_info,
    }


def median_step_s(stats: Dict) -> float:
    """Median seconds per decode step between consecutive sync stamps
    (``step_ready_s``); 0.0 with fewer than two."""
    r = stats["step_ready_s"]
    per = [(t1 - t0) / (n1 - n0) for (n0, t0), (n1, t1) in zip(r, r[1:])]
    return float(np.median(per)) if per else 0.0


def main() -> int:
    args = build_parser().parse_args()
    setup_compile_cache()
    run_serving(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
