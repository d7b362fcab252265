"""The ISSUE 6 fault matrix: every injected fault class must leave the
pipeline producing numerically correct output via the documented fallback
ladder (DESIGN.md §9), with every downgrade observable through selection
hooks and the same seed reproducing the same fault sequence.

Fault classes covered: probe timeout/hang (watchdog), NaN / sign-flipped /
outlier measurements (probe guards + robust fit + oracle guards), tampered
and truncated calibrated-topology artifacts (quarantine), corrupt selection
cache (mid-write truncation and parseable-but-illegal entries), kernel
compile/placement failures (fallback ladder), and mid-decode transients +
preemption drain (degraded serving).

The CI ``chaos`` job runs this file across all five presets with
``REPRO_CHAOS_SEEDS`` widening the seeded sweep.
"""
import json
import math
import os
import warnings
from dataclasses import replace as _dc_replace

import numpy as np
import pytest

import jax.numpy as jnp

import repro.core.selector as selmod
from repro.calib import (FaultPlan, FaultyDevice, InjectedCompileError,
                         InjectedTransientError, VirtualDevice,
                         corrupt_cache_entry, decode_injector, fit_topology,
                         launch_injector, oracle_best, run_probes,
                         scripted_injector, tamper_artifact_fingerprint,
                         truncate_file)
from repro.calib.probes import probe_latency
from repro.core import (PRESETS, TPU_V5E, DegradedModeWarning, GemmProblem,
                        TileConfig, add_selection_hook, calibrated_topology_json,
                        candidate_tiles, clear_selection_cache, fits_placement,
                        get_hardware, load_calibrated_topology_guarded,
                        load_selection_cache, remove_selection_hook,
                        safe_config, select_gemm_config,
                        unload_selection_cache, validate_selection)
from repro.core.selector import fallback_ladder, rank_candidates
from repro.kernels import ops

CHAOS_SEEDS = range(int(os.environ.get("REPRO_CHAOS_SEEDS", "2")))


@pytest.fixture
def hooked():
    """Record every selection-hook emission for the duration of a test."""
    events = []

    def hook(sel, source):
        events.append((source, sel.config))

    add_selection_hook(hook)
    yield events
    remove_selection_hook(hook)


@pytest.fixture
def injector():
    """Install a launch fault injector; always restore the previous one."""
    installed = []

    def install(fn):
        installed.append(ops.set_launch_fault_injector(fn))
        return fn

    yield install
    while installed:
        ops.set_launch_fault_injector(installed.pop())


def _matmul_vs_reference(hw, *, seed=0, M=128, N=128, K=256):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((M, K)), dtype=jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype=jnp.float32)
    got = np.asarray(ops.matmul(a, b, out_dtype=jnp.float32, hw=hw,
                                backend="pallas_interpret"))
    want = np.asarray(ops.matmul(a, b, out_dtype=jnp.float32, hw=hw,
                                 backend="reference"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4 * np.sqrt(K))
    return got


# ---------------------------------------------------------------------------
# FaultPlan: seeded determinism
# ---------------------------------------------------------------------------


def _probe_workload(plan):
    dev = FaultyDevice(VirtualDevice(TPU_V5E), plan)
    for i in range(12):
        dev.stream_time(float(1 << (20 + i % 3)), 1 << 20, 16)
        dev.compute_time("bfloat16", 256 + i)
        dev.wave_time(8 + i, 64, "bfloat16")
    return list(plan.log)


def test_fault_plan_same_seed_same_fault_sequence():
    mk = lambda s: FaultPlan(seed=s, probe_nan=0.25, probe_outlier=0.2,
                             probe_signflip=0.15)
    plan = mk(7)
    log1 = _probe_workload(plan)
    assert log1, "rates this high must fire at least once in 36 calls"
    plan.reset()
    assert plan.log == [] and _probe_workload(plan) == log1
    assert _probe_workload(mk(7)) == log1       # fresh plan, same seed
    assert _probe_workload(mk(8)) != log1       # different seed


def test_faulty_device_fault_shapes():
    """Each kind corrupts the honest value the documented way."""
    honest = VirtualDevice(TPU_V5E)
    truth = honest.stream_time(1 << 20, 1 << 20, 16)

    def one(kind):
        plan = FaultPlan(seed=0, outlier_factor=40.0, **{kind: 1.0})
        return FaultyDevice(VirtualDevice(TPU_V5E), plan) \
            .stream_time(1 << 20, 1 << 20, 16)

    assert math.isnan(one("probe_nan"))
    assert one("probe_signflip") == pytest.approx(-truth)
    assert one("probe_outlier") == pytest.approx(40.0 * truth)


# ---------------------------------------------------------------------------
# Probe watchdog + degraded-mode fit
# ---------------------------------------------------------------------------


def test_watchdog_drops_hanging_probe_samples():
    plan = FaultPlan(seed=0, probe_timeout=1.0, hang_s=0.25)
    dev = FaultyDevice(VirtualDevice(TPU_V5E), plan)
    sweep = probe_latency(dev, TPU_V5E, deadline_s=0.02)
    assert sweep.samples == ()                  # every sample hung -> dropped
    assert sweep.params["n_dropped"] == 6
    # Without a deadline the hang is simply waited out (no watchdog).
    plan.reset()
    sweep2 = probe_latency(dev, TPU_V5E, targets=(1e-6,), deadline_s=None)
    assert len(sweep2.samples) == 1


def test_degraded_fit_keeps_preset_constants_under_total_probe_loss():
    """All probes hang: allow_degraded keeps every preset constant and
    records why, instead of aborting calibration."""
    plan = FaultPlan(seed=1, probe_timeout=1.0, hang_s=0.1)
    dev = FaultyDevice(VirtualDevice(TPU_V5E), plan)
    with pytest.raises((ValueError, IndexError, KeyError)):
        fit_topology(TPU_V5E, dev, dtypes=("bfloat16",), deadline_s=0.02)
    plan.reset()
    res = fit_topology(TPU_V5E, dev, dtypes=("bfloat16",), deadline_s=0.02,
                       allow_degraded=True)
    assert res.fitted == {}                     # nothing could be fitted
    assert "kernel_launch" in res.degraded
    assert res.topology.kernel_launch == TPU_V5E.kernel_launch
    assert res.topology.peak_flops == TPU_V5E.peak_flops
    assert "degraded" in res.provenance()


def test_fit_accurate_under_nan_and_signflip_poison():
    """NaN and sign-flipped measurements are dropped at the probe layer,
    so the fit sees only honest samples and must land on the planted
    truth."""
    plan = FaultPlan(seed=5, probe_nan=0.15, probe_signflip=0.1)
    dev = FaultyDevice(VirtualDevice(TPU_V5E), plan)
    res = fit_topology(TPU_V5E, dev, dtypes=("bfloat16",),
                       allow_degraded=True)
    errs = res.compare_to(TPU_V5E)
    fitted_errs = {k: errs[k] for k in res.fitted}
    assert fitted_errs, "a fault rate this low must leave fittable sweeps"
    for k, e in fitted_errs.items():
        assert e < 0.1, f"{k} off by {e:.3f} after dropping poison"


def test_fit_completes_under_outliers():
    """Outliers pass the probe guards (plausible values are the robust
    fit's problem): calibration must complete in degraded mode with every
    constant valid — a 40x outlier in a 4-point sweep is past Theil-Sen's
    breakdown, so accuracy there is not promised, only sanity."""
    plan = FaultPlan(seed=6, probe_outlier=0.15, outlier_factor=40.0)
    dev = FaultyDevice(VirtualDevice(TPU_V5E), plan)
    res = fit_topology(TPU_V5E, dev, dtypes=("bfloat16",),
                       allow_degraded=True)
    t = res.topology
    assert t.kernel_launch >= 0.0 and t.dma_fixed >= 0.0
    for lvl in t.levels:
        assert math.isfinite(lvl.bandwidth) and lvl.bandwidth > 0.0
    for v in t.peak_flops.values():
        assert math.isfinite(v) and v > 0.0


def test_oracle_skips_poisoned_gemm_measurements():
    """A sign-flipped (negative) timing would WIN the argmin; the oracle
    must skip non-finite/non-positive measurements."""
    p = GemmProblem(M=256, N=256, K=256)
    cands = candidate_tiles(p, TPU_V5E)[:10]
    plan = FaultPlan(seed=2, probe_signflip=0.3, probe_nan=0.2)
    dev = FaultyDevice(VirtualDevice(TPU_V5E), plan)
    best_t, best_s, _ = oracle_best(p, TPU_V5E, dev, cands, prune=False)
    assert best_t is not None
    assert np.isfinite(best_s) and best_s > 0.0


# ---------------------------------------------------------------------------
# Calibrated-topology artifacts: quarantine + degraded serving constants
# ---------------------------------------------------------------------------


def _write_artifact(tmp_path, residuals=None):
    path = str(tmp_path / "topo.json")
    with open(path, "w") as f:
        f.write(calibrated_topology_json(
            get_hardware("tpu_v5p"),
            {"residuals": residuals or {"kernel_launch": 0.01}}))
    return path


def test_tampered_artifact_quarantines_and_falls_back(tmp_path):
    path = _write_artifact(tmp_path)
    tamper_artifact_fingerprint(path)
    with pytest.warns(DegradedModeWarning, match="quarantined"):
        topo, prov = load_calibrated_topology_guarded(path, TPU_V5E)
    assert topo is TPU_V5E
    assert "fingerprint" in prov["degraded"]
    assert prov["quarantined"] == path + ".quarantined"
    assert not os.path.exists(path)             # moved aside, not deleted
    assert os.path.exists(prov["quarantined"])  # evidence preserved


def test_truncated_artifact_quarantines_and_falls_back(tmp_path):
    path = _write_artifact(tmp_path)
    truncate_file(path, frac=0.5)               # mid-write crash remnant
    with pytest.warns(DegradedModeWarning):
        topo, prov = load_calibrated_topology_guarded(path, TPU_V5E)
    assert topo is TPU_V5E and prov["degraded"]
    assert os.path.exists(path + ".quarantined")


def test_out_of_tolerance_residuals_quarantine(tmp_path):
    path = _write_artifact(tmp_path, residuals={"dma_fixed": 0.9})
    with pytest.warns(DegradedModeWarning, match="residual"):
        topo, prov = load_calibrated_topology_guarded(
            path, TPU_V5E, max_residual=0.5)
    assert topo is TPU_V5E and "dma_fixed" in prov["degraded"]


def test_healthy_artifact_loads_clean(tmp_path):
    path = _write_artifact(tmp_path)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DegradedModeWarning)
        topo, prov = load_calibrated_topology_guarded(path, TPU_V5E)
    assert topo.name == "tpu_v5p" and "degraded" not in prov
    assert os.path.exists(path)                 # not quarantined


def test_missing_artifact_degrades_without_quarantine(tmp_path):
    with pytest.warns(DegradedModeWarning, match="unreadable"):
        topo, prov = load_calibrated_topology_guarded(
            str(tmp_path / "nope.json"), TPU_V5E)
    assert topo is TPU_V5E and prov["quarantined"] is None


# ---------------------------------------------------------------------------
# Selection cache corruption
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_path(tmp_path, monkeypatch):
    path = str(tmp_path / "selections.json")
    monkeypatch.setenv("REPRO_SELECTION_CACHE", path)
    load_selection_cache(path)
    clear_selection_cache()
    yield path
    monkeypatch.delenv("REPRO_SELECTION_CACHE")
    unload_selection_cache()
    clear_selection_cache()


def test_midwrite_truncated_cache_recovers(cache_path):
    select_gemm_config(1024, 1024, 1024)
    truncate_file(cache_path, frac=0.3)
    assert load_selection_cache(cache_path) == 0      # unreadable -> empty
    clear_selection_cache()
    sel = select_gemm_config(1024, 1024, 1024)        # re-selects cleanly
    assert fits_placement(sel.config, "bfloat16", TPU_V5E)


def test_tampered_cache_entry_falls_through_to_cold(cache_path, hooked):
    baseline = select_gemm_config(1024, 1024, 1024)
    assert corrupt_cache_entry(cache_path, bm=12288) == 1   # non-pow2, huge
    clear_selection_cache()
    load_selection_cache(cache_path)
    sel = select_gemm_config(1024, 1024, 1024)
    # The illegal rehydrated entry must NOT be served: cold re-scoring
    # reproduces the legal argmin instead.
    assert hooked[-1][0] == "cold"
    assert sel.config == baseline.config
    assert validate_selection(sel.problem, sel.config, TPU_V5E) is None


# ---------------------------------------------------------------------------
# Guarded launch: validation + fallback ladder
# ---------------------------------------------------------------------------


def test_validate_selection_catches_corrupt_configs():
    p = GemmProblem(M=512, N=512, K=512)
    ok = select_gemm_config(512, 512, 512).config
    assert validate_selection(p, ok, TPU_V5E) is None
    bad_pow2 = _dc_replace(ok, bm=12288)
    assert "power of two" in validate_selection(p, bad_pow2, TPU_V5E)
    bad_fit = _dc_replace(ok, bm=8192, bn=8192, bk=8192)
    assert "budget" in validate_selection(p, bad_fit, TPU_V5E)
    bad_align = _dc_replace(ok, bn=32)          # lane width is 128
    assert "misaligned" in validate_selection(p, bad_align, TPU_V5E)


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_safe_config_is_safe_everywhere(preset):
    hw = get_hardware(preset)
    p = GemmProblem(M=384, N=384, K=384)
    t = safe_config(p, hw)
    assert validate_selection(p, t, hw) is None


def test_fallback_ladder_is_deterministic_and_fresh():
    p = GemmProblem(M=512, N=512, K=512)
    primary = select_gemm_config(512, 512, 512).config
    rungs = list(fallback_ladder(p, TPU_V5E, primary))
    assert [r for _, r in rungs] in (["next", "safe"], ["next"])
    for sel, _ in rungs:
        assert sel.config != primary
    assert rungs == list(fallback_ladder(p, TPU_V5E, primary))
    # "next" really is the best-ranked non-primary candidate.
    ranked = [t for t, _ in rank_candidates(p, TPU_V5E)]
    assert rungs[0][0].config == next(t for t in ranked if t != primary)


def test_compile_failure_steps_to_next_ranked(hooked, injector):
    injector(scripted_injector([InjectedCompileError("lowering failed")]))
    with pytest.warns(DegradedModeWarning):
        _matmul_vs_reference(TPU_V5E, seed=10)
    falls = [s for s, _ in hooked if s.startswith("fallback")]
    assert falls == ["fallback:next"]


def test_two_compile_failures_step_to_safe(hooked, injector):
    injector(scripted_injector([InjectedCompileError("x"),
                                InjectedCompileError("y")]))
    with pytest.warns(DegradedModeWarning):
        _matmul_vs_reference(TPU_V5E, seed=11)
    falls = [s for s, _ in hooked if s.startswith("fallback")]
    assert falls == ["fallback:next", "fallback:safe"]


def test_total_launch_failure_serves_reference(hooked, injector):
    injector(scripted_injector([InjectedCompileError(f"rung {i}")
                                for i in range(8)]))
    with pytest.warns(DegradedModeWarning):
        _matmul_vs_reference(TPU_V5E, seed=12)
    falls = [s for s, _ in hooked if s.startswith("fallback")]
    assert falls[-1] == "fallback:reference"


def test_transient_launch_fault_retries_in_place(hooked, injector):
    injector(scripted_injector(
        [InjectedTransientError("transient: DMA hiccup")]))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DegradedModeWarning)
        _matmul_vs_reference(TPU_V5E, seed=13)
    assert not [s for s, _ in hooked if s.startswith("fallback")]


def test_explicit_config_never_silently_swapped(injector):
    """A user-passed config is a contract: transients retry, deterministic
    failures propagate — no ladder."""
    cfg = TileConfig(bm=128, bn=128, bk=128, split_k=1, group_m=1,
                     schedule="data_parallel")
    a = jnp.ones((128, 128), jnp.float32)
    injector(scripted_injector([InjectedCompileError("lowering failed")]))
    with pytest.raises(InjectedCompileError):
        ops.matmul(a, a, config=cfg, backend="pallas_interpret")
    ops.set_launch_fault_injector(
        scripted_injector([InjectedTransientError("transient: x")]))
    out = ops.matmul(a, a, config=cfg, backend="pallas_interpret")
    assert np.asarray(out).shape == (128, 128)


@pytest.mark.parametrize("err", [TypeError("traced a bad shape"),
                                 NotImplementedError("no rule")],
                         ids=["TypeError", "NotImplementedError"])
def test_program_error_is_not_a_launch_failure(hooked, injector, err):
    """A tracing or transformation error would fail on every rung alike:
    the ladder re-raises it instead of serving the reference kernel."""
    injector(scripted_injector([err]))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DegradedModeWarning)
        with pytest.raises(type(err)):
            _matmul_vs_reference(TPU_V5E, seed=15)
    assert not [s for s, _ in hooked if s.startswith("fallback")]


def test_poisoned_memo_is_revalidated_before_launch(hooked, injector):
    """A memo entry poisoned into a placement-busting config (a buggy hook,
    a cosmic-ray cache) is caught by pre-launch validation and the ladder
    serves a correct result."""
    clear_selection_cache()
    sel = select_gemm_config(128, 128, 256, in_dtype="float32",
                             out_dtype="float32")
    (key,) = selmod._CACHE
    poisoned = _dc_replace(sel, config=_dc_replace(
        sel.config, bm=8192, bn=8192, bk=8192))
    selmod._CACHE[key] = poisoned
    try:
        with pytest.warns(DegradedModeWarning, match="rejected"):
            _matmul_vs_reference(TPU_V5E, seed=14)
        assert [s for s, _ in hooked if s.startswith("fallback")]
    finally:
        clear_selection_cache()


# ---------------------------------------------------------------------------
# Chaos sweep: seeded fault plans x all presets (the CI chaos job widens
# CHAOS_SEEDS).  Whatever faults fire, the result must match the reference
# and the fault sequence must replay identically under the same seed.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset", sorted(PRESETS))
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_chaos_matmul_correct_under_any_seed(preset, seed, injector):
    hw = get_hardware(preset)
    plan = FaultPlan(seed=seed, launch_compile=0.4, launch_transient=0.3)
    injector(launch_injector(plan))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DegradedModeWarning)
        out1 = _matmul_vs_reference(hw, seed=seed)
        log1 = list(plan.log)
        plan.reset()
        out2 = _matmul_vs_reference(hw, seed=seed)
    assert plan.log == log1                     # same seed, same faults
    np.testing.assert_array_equal(out1, out2)


# ---------------------------------------------------------------------------
# Degraded serving: transient decode fault + preemption drain + quarantined
# topology artifact, in one end-to-end pass against the clean run.
# ---------------------------------------------------------------------------


def _serve_args(**over):
    import argparse
    base = dict(arch="mamba2-370m", smoke=True, batch=2, prompt_len=16,
                gen=8, temperature=0.0, tp=1, seed=0, topology=None)
    base.update(over)
    return argparse.Namespace(**base)


def test_degraded_serving_matches_clean_prefix(tmp_path):
    from repro.launch.serve import run_serving

    clean = run_serving(_serve_args())
    assert clean["steps"] == 7 and not clean["drained"]

    art = _write_artifact(tmp_path)
    tamper_artifact_fingerprint(art)

    fired = []

    def fault(step, guard):
        if step == 2 and not fired:
            fired.append(step)
            raise InjectedTransientError("transient: injected decode fault")
        if step == 5:
            guard.request_stop()

    try:
        with pytest.warns(DegradedModeWarning):
            faulted = run_serving(_serve_args(topology=art),
                                  decode_fault=fault)
    finally:
        ops.set_default_hardware(None)

    assert faulted["degraded"]                  # artifact was quarantined
    assert faulted["retries"] == 1 and fired == [2]
    assert faulted["drained"] and faulted["steps"] == 6
    # Greedy decoding: the degraded run's tokens are a prefix of the clean
    # run's — transients and the drain changed nothing numerically.
    np.testing.assert_array_equal(
        faulted["tokens"], clean["tokens"][:, :faulted["steps"] + 1])


def test_serving_decode_injector_plan_is_deterministic():
    """decode_injector draws reproduce under reset — the serving chaos
    path inherits FaultPlan's determinism."""
    plan = FaultPlan(seed=9, decode_transient=0.5)
    inj = decode_injector(plan)
    seq1 = []
    for i in range(10):
        try:
            inj(i, None)
            seq1.append(False)
        except InjectedTransientError:
            seq1.append(True)
    plan.reset()
    seq2 = []
    for i in range(10):
        try:
            inj(i, None)
            seq2.append(False)
        except InjectedTransientError:
            seq2.append(True)
    assert seq1 == seq2 and any(seq1)
