import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
``.lower().compile()`` must succeed on the 16x16 single-pod mesh AND the
2x16x16 multi-pod mesh for every applicable cell;
``compiled.memory_analysis()`` proves per-device fit and
``compiled.cost_analysis()`` + the HLO collective parse feed §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch phi4-mini-3.8b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--both-meshes]
"""
import argparse    # noqa: E402
import dataclasses  # noqa: E402
import json        # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import numpy as np                # noqa: E402
import jax                        # noqa: E402
import jax.numpy as jnp           # noqa: E402

from repro.configs.registry import (  # noqa: E402
    ARCH_IDS, all_cells, get_config, get_shape)
from repro.core.hardware import TPU_V5E  # noqa: E402
from repro.core.roofline import (     # noqa: E402
    cost_analysis_terms, parse_collective_bytes, roofline)
from repro.core.topology import (     # noqa: E402
    HardwareSpec, topology_fingerprint)
from repro.distributed import (       # noqa: E402
    batch_shardings, cache_shardings, opt_shardings, param_shardings,
    replicated)
from repro.kernels import set_backend  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (       # noqa: E402
    TrainState, abstract_train_state, make_prefill_step, make_serve_step,
    make_train_step)
from repro.nn.model import Model       # noqa: E402
from repro.optim import AdamW          # noqa: E402


def _mem_stats(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            "code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
    except Exception as e:                               # noqa: BLE001
        return {"error": repr(e)}


# ---------------------------------------------------------------------------
# Cost probes.
#
# XLA cost_analysis counts a `while` body once, so scanned modules
# under-report FLOPs/bytes.  We compile reduced (L, S) variants with every
# scan UNROLLED (repro.nn.scanning) — there cost_analysis is exact — and
# reconstruct the full cell through the exact structural model
#     f(L, S) = a0 + a1*S + L*(b0 + b1*S + b2*S^2)
# (embedding/loss terms linear in S; per-layer work with linear and, for
# attention, quadratic S terms; optimizer work per layer S-independent).
# Six probes (2 depths x 3 sequence points) solve it exactly.
# ---------------------------------------------------------------------------

_PROBE_S = {"train": (512, 1024, 2048),
            "prefill": (512, 1024, 2048),
            "decode": (2048, 4096, 8192)}


def _probe_depths(cfg):
    """Two reduced-depth variants + the linear depth variable (layers, or
    groups for the hybrid family) with its full-scale value."""
    if cfg.family == "hybrid":
        g = cfg.shared_attn_every
        tail = cfg.num_layers % g
        mk = lambda k: dataclasses.replace(  # noqa: E731
            cfg, num_layers=k * g + tail)
        full_x = (cfg.num_layers - tail) // g
    else:
        mk = lambda k: dataclasses.replace(cfg, num_layers=k)  # noqa: E731
        full_x = cfg.num_layers
    return [(2, mk(2)), (4, mk(4))], full_x


def _fit_and_eval(samples, X_full, S_full):
    """samples: {(x, s): value}. Fit f = a0+a1*s+x*(b0+b1*s+b2*s^2)."""
    xs = sorted({x for x, _ in samples})
    ss = sorted({s for _, s in samples})
    x1, x2 = xs
    dL = {s: (samples[(x2, s)] - samples[(x1, s)]) / (x2 - x1) for s in ss}
    A = np.array([[1.0, s, s * s] for s in ss])
    b = np.linalg.solve(A, np.array([dL[s] for s in ss]))
    a_vals = np.array([samples[(x1, s)] - x1 * dL[s] for s in ss])
    a_coef, _res, _rk, _sv = np.linalg.lstsq(
        np.array([[1.0, s] for s in ss]), a_vals, rcond=None)
    return float(a_coef[0] + a_coef[1] * S_full
                 + X_full * (b[0] + b[1] * S_full + b[2] * S_full ** 2))


def _lower_cell(model, cfg, shape, mesh, microbatches: int = 1):
    """Build (jitted, args) for one cell — shared by full run and probes."""
    from repro import meshctx
    meshctx.set_mesh(mesh)        # enables cfg.sp_stash constraints
    p_sh = param_shardings(model, mesh)
    in_specs = model.input_specs(shape)
    if shape.kind == "train":
        opt = AdamW()
        step_fn = make_train_step(model, opt, microbatches=microbatches)
        state = abstract_train_state(model, opt)
        state_sh = TrainState(params=p_sh, opt=opt_shardings(p_sh, mesh),
                              step=replicated(mesh))
        b_sh = batch_shardings(in_specs, mesh)
        jitted = jax.jit(step_fn, in_shardings=(state_sh, b_sh),
                         out_shardings=(state_sh, replicated(mesh)),
                         donate_argnums=(0,))
        return jitted, (state, in_specs)
    if shape.kind == "prefill":
        step_fn = make_prefill_step(model)
        b_sh = batch_shardings(in_specs, mesh)
        cache_abs = model.cache_specs(shape.global_batch, shape.seq_len)
        c_sh = cache_shardings(cache_abs, mesh, cfg)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, b_sh),
                         out_shardings=(replicated(mesh), c_sh))
        return jitted, (model.abstract_params(), in_specs)
    step_fn = make_serve_step(model)
    cache_abs = model.cache_specs(shape.global_batch, shape.seq_len)
    c_sh = cache_shardings(cache_abs, mesh, cfg)
    b_sh = batch_shardings(in_specs, mesh)
    jitted = jax.jit(step_fn,
                     in_shardings=(p_sh, c_sh, b_sh["tokens"], b_sh["pos"]),
                     out_shardings=(replicated(mesh), c_sh),
                     donate_argnums=(1,))
    return jitted, (model.abstract_params(), cache_abs,
                    in_specs["tokens"], in_specs["pos"])


def run_probes(arch: str, shape_name: str, multi_pod: bool,
               verbose: bool = True, microbatches: int = 1,
               sp_stash: bool = False,
               kv_repeat_weights: bool = False,
               moe_dense_decode: bool = False,
               moe_local_dispatch: bool = False) -> dict:
    """Reconstruct exact per-device flops/bytes/collective-bytes via
    unrolled reduced-(L,S) compiles + structural extrapolation."""
    from repro.nn import scanning
    base_cfg = get_config(arch)
    if sp_stash:
        base_cfg = dataclasses.replace(base_cfg, sp_stash=True)
    if kv_repeat_weights:
        base_cfg = dataclasses.replace(base_cfg, kv_repeat_weights=True)
    if moe_dense_decode:
        base_cfg = dataclasses.replace(base_cfg, moe_dense_decode=True)
    if moe_local_dispatch:
        base_cfg = dataclasses.replace(base_cfg, moe_local_dispatch=True)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_backend("reference")
    depths, X_full = _probe_depths(base_cfg)
    s_points = _PROBE_S[shape.kind]

    flops_s, bytes_s, coll_s = {}, {}, {}
    scanning.set_unroll(True)
    try:
        for x, cfgv in depths:
            for s in s_points:
                shp = dataclasses.replace(shape, seq_len=s)
                model = Model(cfgv)
                jitted, args = _lower_cell(model, cfgv, shp, mesh,
                                           microbatches=microbatches)
                compiled = jitted.lower(*args).compile()
                fl, by = cost_analysis_terms(compiled)
                co = parse_collective_bytes(compiled.as_text())
                flops_s[(x, s)] = fl
                bytes_s[(x, s)] = by
                coll_s[(x, s)] = co["total"]
                if verbose:
                    print(f"    probe x={x} S={s}: flops={fl:.3e} "
                          f"bytes={by:.3e} coll={co['total']:.3e}")
    finally:
        scanning.set_unroll(False)
    S_full = shape.seq_len
    return {
        "flops": _fit_and_eval(flops_s, X_full, S_full),
        "bytes": _fit_and_eval(bytes_s, X_full, S_full),
        "collective_bytes": _fit_and_eval(coll_s, X_full, S_full),
        "probe_points": {f"x{x}_s{s}": {"flops": flops_s[(x, s)],
                                        "bytes": bytes_s[(x, s)],
                                        "coll": coll_s[(x, s)]}
                         for (x, s) in flops_s},
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "experiments/dryrun", verbose: bool = True,
             with_probes: bool = False, microbatches: int = 1,
             sp_stash: bool = False,
             kv_repeat_weights: bool = False,
             moe_dense_decode: bool = False,
             moe_local_dispatch: bool = False,
             hw: HardwareSpec = TPU_V5E) -> dict:
    cfg = get_config(arch)
    if sp_stash:
        cfg = dataclasses.replace(cfg, sp_stash=True)
    if kv_repeat_weights:
        cfg = dataclasses.replace(cfg, kv_repeat_weights=True)
    if moe_dense_decode:
        cfg = dataclasses.replace(cfg, moe_dense_decode=True)
    if moe_local_dispatch:
        cfg = dataclasses.replace(cfg, moe_local_dispatch=True)
    shape = get_shape(shape_name)
    model = Model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    # Mosaic cannot lower for the CPU platform: the dry-run uses the
    # reference backend, whose FLOP/byte profile matches the kernels.
    set_backend("reference")

    if microbatches == 0:          # 0 => analytic auto-selection
        from repro.launch.memory import select_microbatches
        microbatches = select_microbatches(cfg, shape, dict(mesh.shape))
    t0 = time.time()
    jitted, args = _lower_cell(model, cfg, shape, mesh,
                               microbatches=microbatches)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    flops, bytes_ = cost_analysis_terms(compiled)
    colls = parse_collective_bytes(compiled.as_text())
    from repro.launch.memory import (estimate_cell_memory,
                                     estimate_step_hbm_bytes)
    mem_analytic = estimate_cell_memory(cfg, shape, dict(mesh.shape))
    hbm_analytic = estimate_step_hbm_bytes(cfg, shape, dict(mesh.shape),
                                           microbatches=microbatches)
    # The serving topology the roofline terms below are priced against
    # (the same ``hw`` handed to ``roofline``) — recorded per artifact so
    # benchmarks/roofline_table can derive per-level port columns without
    # guessing the preset, and so passing a calibrated topology through
    # ``run_cell(hw=...)`` is visible in the artifact itself.
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": chips, "kind": shape.kind,
        "topology": {
            "name": hw.name,
            "fingerprint": topology_fingerprint(hw),
            "levels": [{"name": lvl.name, "bandwidth": lvl.bandwidth,
                        "capacity": lvl.capacity, "scope": lvl.scope}
                       for lvl in hw.levels],
        },
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "microbatches": microbatches,
        "sp_stash": sp_stash,
        "kv_repeat_weights": kv_repeat_weights,
        "moe_dense_decode": moe_dense_decode,
        "moe_local_dispatch": moe_local_dispatch,
        "memory": _mem_stats(compiled),
        "memory_analytic_gib": {k: round(v, 3) if isinstance(v, float)
                                else v for k, v in mem_analytic.items()},
        "hbm_bytes_analytic": {k: float(v) for k, v in hbm_analytic.items()},
        "cost_module": {"flops": flops, "bytes": bytes_,
                        "note": "scan bodies counted once by XLA"},
        "collectives_module": {k: v for k, v in colls.items() if v},
        "params": model.param_count(),
    }
    # Reconstructed exact per-device costs (probe extrapolation).
    if with_probes:
        probes = run_probes(arch, shape_name, multi_pod, verbose=verbose,
                            microbatches=microbatches, sp_stash=sp_stash,
                            kv_repeat_weights=kv_repeat_weights,
                            moe_dense_decode=moe_dense_decode,
                            moe_local_dispatch=moe_local_dispatch)
        record["cost_reconstructed"] = {k: probes[k] for k in
                                        ("flops", "bytes",
                                         "collective_bytes")}
        record["probe_points"] = probes["probe_points"]
        rep = roofline(
            arch=arch, shape_name=shape_name, mesh=mesh_name, chips=chips,
            hlo_flops=probes["flops"], hlo_bytes=hbm_analytic["total"],
            collectives={"total": probes["collective_bytes"],
                         "all-reduce": probes["collective_bytes"]},
            model_flops=model.model_flops(shape), hw=hw)
        record["roofline"] = rep.as_dict()
    else:
        rep = roofline(arch=arch, shape_name=shape_name, mesh=mesh_name,
                       chips=chips, hlo_flops=flops,
                       hlo_bytes=hbm_analytic["total"], collectives=colls,
                       model_flops=model.model_flops(shape), hw=hw)
        record["roofline"] = rep.as_dict()

    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if verbose:
        mem = record["memory"]
        fl = record.get("cost_reconstructed", record["cost_module"])["flops"]
        print(f"[OK] {arch} x {shape_name} x {mesh_name}: "
              f"compile {t_compile:.1f}s  "
              f"args {mem.get('argument_bytes', 0)/2**30:.2f}GiB/dev  "
              f"temp {mem.get('temp_bytes', 0)/2**30:.2f}GiB/dev  "
              f"flops/dev {fl:.3e}  bound={rep.bottleneck}")
        print(f"     memory_analysis: {mem}")
        print(f"     cost_analysis(module): flops={flops:.4e} "
              f"bytes={bytes_:.4e}  collectives={record['collectives_module']}")
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + ["all"],
                    help="architecture id (or 'all')")
    ap.add_argument("--shape", default=None,
                    help="shape cell name (omit for all applicable)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all applicable (arch x shape) cells")
    ap.add_argument("--with-probes", action="store_true",
                    help="also reconstruct exact costs via unrolled probes")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation factor for train cells "
                         "(0 = analytic auto-selection from memory model)")
    ap.add_argument("--sp-stash", action="store_true",
                    help="sequence-shard the residual stream at scan "
                         "boundaries (SP remat stash)")
    ap.add_argument("--kv-repeat-weights", action="store_true",
                    help="Megatron KV-weight duplication (TP > Hkv)")
    ap.add_argument("--moe-dense-decode", action="store_true",
                    help="decode MoE: all local experts, no weight gather")
    ap.add_argument("--moe-local-dispatch", action="store_true",
                    help="MoE dispatch packed within each data shard")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all or args.arch == "all":
        cells = [(a, s) for a, s, ok, _ in all_cells() if ok]
    else:
        assert args.arch, "--arch or --all required"
        if args.shape:
            cells = [(args.arch, args.shape)]
        else:
            cells = [(a, s) for a, s, ok, _ in all_cells()
                     if ok and a == args.arch]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, out_dir=args.out,
                         with_probes=args.with_probes,
                         microbatches=args.microbatches,
                         sp_stash=args.sp_stash,
                         kv_repeat_weights=args.kv_repeat_weights,
                         moe_dense_decode=args.moe_dense_decode,
                         moe_local_dispatch=args.moe_local_dispatch)
            except Exception as e:                     # noqa: BLE001
                failures.append((arch, shape, mp, repr(e)))
                print(f"[FAIL] {arch} x {shape} x "
                      f"{'multi' if mp else 'single'}: {e}")
                traceback.print_exc()
    print(f"\n{len(cells)*len(meshes)-len(failures)} passed, "
          f"{len(failures)} failed")
    for f in failures:
        print("  FAIL:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
