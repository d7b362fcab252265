"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window's opening): the
weights are made on the device from the seed; the program's bucket plan is
priced over the lengths the mix offers; one request per bucket edge warms
every prefill shape, the slot insert and the decode step; then requests
arrive at the cell's rate for the mix's ``fill_seconds``.  The window then
measures for ``--seconds``, nothing compiling inside it.  After the window
closes, device memory is read, the engine is freed, and a sample of the
requests finished in the window is held against the plain reference
(``bench/reference.py``).

With ``--trace 1`` the same run is traced by the JAX profiler for the
first ``TRACE_SECONDS`` of the window, and the result carries the cell's
per-layer metrics over that part, the device's busy time and the
breakdown.

The last line of stdout is the result (JSON); the numbers compared, each
beside its limit, are the last lines of stderr and the result's last key.
Exits non-zero, with no result, when JAX finds no TPU or not the chips the
cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import loadgen, reference, registry, stats, system  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

SAMPLE = 4          # requests held against the reference in each run
TRACE_SECONDS = 10  # the traced part of a --trace 1 run's window


class NoAccelerator(RuntimeError):
    pass


@dataclass
class Record:
    """What the per-layer readers read: the run's own stamps and counts,
    the sizes, the peaks, and (traced runs) the reduced device trace."""
    dims: reference.Dims
    chips: int
    peaks: object
    served: List[stats.Served]
    steps: List[stats.Step]
    t_open: float
    t_close: float
    compiles: List[float] = field(default_factory=list)   # host times
    trace: Optional[object] = None


def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX runs on {devs[0].platform!r}")
    if require_tpu and len(devs) != chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s); JAX sees "
                            f"{len(devs)}")
    return devs


def pick_sample(served: List[stats.Served], seed: int, t_open: float,
                t_close: float, n: int = SAMPLE) -> List[stats.Served]:
    """Requests finished in the window: the longest, and n - 1 more drawn
    from the seed."""
    done = [r for r in served if r.finished and r.delivered.size
            and t_open < r.delivered[-1] <= t_close]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.tokens.size,
                                       r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check_outputs(served, sample, prompts, params, dims, seq_len, max_out,
                  limit, vocab, control: bool = False) -> Dict:
    """The numbers compared, each with its limit.  With ``control``, also
    the float8 control's widest gap on the same positions (for setting the
    limit; benchmark runs do not compute it)."""
    wrong = sum(1 for r in served if r.finished
                and (r.tokens.size != r.output_len
                     or r.tokens.min() < 0 or r.tokens.max() >= vocab))
    checks = {"wrong_length_or_id": {"value": wrong, "limit": 0}}
    if not sample:
        checks["requests_compared"] = {"value": 0, "limit": 1}
        return checks
    ids, pos, target, mask = reference.pack(
        [(prompts[r.index], r.tokens) for r in sample], seq_len, max_out)
    gap, cgap = reference.logit_gaps(params, ids, pos, target, dims,
                                     control=control)
    gap = np.where(mask, np.asarray(gap, np.float64), 0.0)
    checks["max_logit_gap"] = {"value": float(gap.max()), "limit": limit}
    if control:
        cgap = np.where(mask, np.asarray(cgap, np.float64), 0.0)
        checks["control_max_logit_gap"] = {"value": float(cgap.max()),
                                           "limit": limit}
    checks["tokens_compared"] = {"value": int(mask.sum()), "limit": 1}
    return checks


def passed(checks: Dict) -> bool:
    """A check passes at or under its limit; the counts compared against
    a limit of at least 1 must reach it."""
    ok = True
    for name, c in checks.items():
        if name in ("requests_compared", "tokens_compared"):
            ok &= c["value"] >= c["limit"]
        elif name.startswith("control_"):
            continue
        else:
            ok &= c["limit"] is not None and c["value"] <= c["limit"]
    return bool(ok)


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             *, require_tpu: bool = True, backend: Optional[str] = "pallas",
             t_start: float = T_START,
             prepare_engine: Optional[Callable] = None,
             control: bool = False,
             log: Callable[[str], None] = lambda m: None) -> Dict:
    """One run of ``cell``; returns the result dict (the printed line).
    ``backend`` None leaves the kernel backend as it is;
    ``prepare_engine(engine)`` may replace parts of the engine before
    warm-up (the planted faults of the tests)."""
    import jax
    cache_dir = None
    if require_tpu:
        cache_dir = system.compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = check_devices(cell.chips, require_tpu)
    if backend is not None:
        system.set_backend(backend)
    log(f"devices {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"backend {system.backend()}; compile cache {cache_dir}")

    conf, mix = cell.config, cell.traffic
    serving = conf["serving"]
    dims = reference.dims_of(conf)
    planned = loadgen.schedule(mix, seed, seconds)
    lens, max_out = loadgen.all_lengths(mix, seconds)

    compile_at: List[float] = []

    def on_event(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_at.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        t = time.perf_counter()
        model, mesh, p_sh = system.build(cell.config_name, dims,
                                         conf["torch_dtype"], serving["tp"])
        params = reference.make_params(dims, seed, p_sh)
        jax.block_until_ready(params)
        system.check_layout(model, params)
        t_weights = time.perf_counter() - t

        t = time.perf_counter()
        plan = system.plan(model, lens, serving["buckets"])
        max_len = max(plan.edges) + max_out
        hook = {"fn": None}
        engine = system.engine(
            model, params, mesh, max_batch=serving["max_batch"],
            max_len=max_len, plan=plan, sync_every=serving["sync_every"],
            hook=lambda step, guard: (hook["fn"] and hook["fn"](step, guard)),
            seed=seed)
        if prepare_engine is not None:
            prepare_engine(engine)
        # Every program the window can run: each bucket edge's prefill, once
        # with a prompt that fills it (no padding) and once with a shorter
        # one (padded, read out at its last real token), and an admission
        # into every slot (the engine sets each slot's first token with its
        # own small program).
        warm_rng = np.random.default_rng([seed, 4])
        warm = [n for edge in plan.edges for n in (edge, edge - 1)]
        warm += [min(plan.edges) - 1] * (serving["max_batch"] - len(warm))
        for n in warm:
            engine.submit(warm_rng.integers(0, dims.vocab, n),
                          max_new_tokens=2)
        engine.warm_start()
        engine.run()
        t_warm = time.perf_counter() - t
        n_setup_compiles = len(compile_at)

        prompts = [loadgen.prompt_tokens(seed, i, p.prompt_len, dims.vocab)
                   for i, p in enumerate(planned)]
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        t0 = time.perf_counter()
        t_open = t0 + mix["fill_seconds"]
        t_close = t_open + seconds
        traced = {}

        def tick(now: float) -> None:
            """The traced run: the profiler from the window's opening for
            TRACE_SECONDS (no Python-function tracing: the device, the
            runtime and the harness's own spans)."""
            if "start" not in traced and now >= t_open:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                traced["start"] = time.perf_counter()
            elif ("stop" not in traced and "start" in traced
                  and now >= traced["start"] + TRACE_SECONDS):
                traced["stop"] = now
                jax.profiler.stop_trace()

        from bench.driver import OpenLoop
        loop = OpenLoop(engine, planned, prompts, t0, t_close,
                        on_tick=tick if trace else None)
        hook["fn"] = loop.hook
        loop.run()
        if trace and "stop" not in traced:
            traced["stop"] = time.perf_counter()
            jax.profiler.stop_trace()
        setup_s = t_open - t_start
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)

    mem = [d.memory_stats() or {} for d in devs[:cell.chips]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    e2e = stats.end_to_end(loop.served, loop.steps, t_open, t_close)
    d = e2e["diag"]
    lag = np.asarray(loop.submit_lag or [0.0])
    log(f"setup: weights {t_weights:.3f} s, plan+compile+warm-up "
        f"{t_warm:.3f} s ({n_setup_compiles} compiles), fill "
        f"{mix['fill_seconds']} s; bucket edges {list(plan.edges)}, "
        f"max_len {max_len}")
    thin = (f"; THIN TAIL, fewer than {stats.MIN_BEYOND}" if d["tail_thin"]
            else "")
    print(f"window: {d['steps']} steps, {d['stalled_steps']} stalled "
          f"(held a prefill); {d['gaps_beyond_tail']} gaps beyond itl "
          f"p{stats.TAIL}, in {d['steps_beyond_tail']} distinct steps "
          f"({d['stalled_beyond_tail']} of them stalled{thin}); "
          f"{d['gaps']} gaps, {d['tokens']} tokens, {d['requests_due']} "
          f"requests due, {d['requests_unserved_at_close']} unserved at "
          f"close; generator late p50 {np.median(lag) * 1e3:.3f} ms, max "
          f"{lag.max() * 1e3:.3f} ms", flush=True)

    del engine, loop.engine
    gc.collect()
    t = time.perf_counter()
    sample = pick_sample(loop.served, seed, t_open, t_close)
    checks = check_outputs(loop.served, sample, prompts, params, dims, max_len,
                           max_out, conf.get("logit_gap_limit"), dims.vocab,
                           control=control)
    log(f"reference check of {len(sample)} requests "
        f"({checks.get('tokens_compared', {}).get('value', 0)} tokens) in "
        f"{time.perf_counter() - t:.3f} s")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips if require_tpu else len(devs),
              "memory_peak_bytes": peak}
    metrics: Dict[str, Dict] = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        values = dict(e2e["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": passed(checks),
              "attempted": d["requests_due"], "failed": 0,
              "metrics": metrics, "device": device, "window": d}
    if trace:
        from bench import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, cell.chips,
                                      traced["stop"] - traced["start"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec = Record(dims=dims, chips=cell.chips,
                     peaks=peaks_for(devs[0].device_kind),
                     served=loop.served, steps=loop.steps,
                     t_open=traced["start"], t_close=traced["stop"],
                     compiles=compile_at[n_setup_compiles:], trace=red)
        for m in cell.per_layer:
            v = registry.reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    cell = registry.cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          log=log)
    except NoAccelerator as e:
        log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
