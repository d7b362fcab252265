"""Telemetry subsystem (src/repro/obs/, DESIGN.md §11).

The load-bearing claims:

* OFF BY DEFAULT, FOR FREE — with no tracer installed and metrics
  disabled, the instrumented hot paths allocate zero Span objects, touch
  no files, and the serving engine's public stats are unchanged.
* DETERMINISTIC WHEN ON — an injected fixed clock yields a byte-identical
  trace; span ids sort in emission order.
* OBSERVES, NEVER PERTURBS — tracing an engine run changes no generated
  token and no non-timing stat; capturing simulator events changes no
  priced latency bit.
* ROUND-TRIPS — trace JSON parses back to identical spans; the Perfetto
  export is loadable Chrome-trace JSON; drift JSONL is parseable and its
  rolling fidelity gauge is 1.0 exactly when predicted == measured.
"""
import json
import os

import numpy as np
import pytest

import jax

from repro.calib.device import VirtualDevice
from repro.calib.faults import FaultPlan, FaultyDevice
from repro.configs.registry import get_config
from repro.core.bucketing import plan_buckets, step_gemms
from repro.core.hardware import PRESETS
from repro.core.selector import (add_selection_hook, remove_selection_hook,
                                 select_gemm_config)
from repro.core.simulator import simulate_gemm
from repro.kernels import ops
from repro.launch.engine import ServingEngine
from repro.nn.model import Model
from repro.obs import drift as obs_drift
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.drift import DriftMonitor, fidelity_of
from repro.obs.metrics import JsonlSink, MetricsRegistry
from repro.obs.perfetto import export_chrome_trace
from repro.runtime.metrics import MetricLogger


@pytest.fixture
def clean_obs():
    """Guarantee pristine disabled telemetry before AND after each test."""
    prev_tracer = obs_trace.set_tracer(None)
    prev_metrics = obs_metrics.enable_metrics(False)
    prev_monitor = obs_drift.set_drift_monitor(None)
    saved = obs_metrics.get_registry().snapshot()
    obs_metrics.get_registry().clear()
    yield
    obs_trace.set_tracer(prev_tracer)
    obs_metrics.enable_metrics(prev_metrics)
    obs_drift.set_drift_monitor(prev_monitor)
    obs_metrics.get_registry().clear()
    del saved


def fixed_clock(times):
    it = iter(times)
    last = [0.0]

    def clock():
        try:
            last[0] = next(it)
        except StopIteration:
            pass
        return last[0]
    return clock


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------

def test_trace_roundtrip_identical_spans(clean_obs):
    tr = obs_trace.Tracer(clock=fixed_clock([0.0, 1.0, 2.0, 3.0, 4.0]))
    with tr.span("outer", cat="test", track="t0", args={"k": 1}):
        tr.event("instant", cat="test", track="t0", args={"x": [1, 2]})
    tr.counter("queue_depth", 3.0)
    tr.complete("sim", "simulator", "core0", 0.5, 0.75, {"wave": 0})
    text = tr.to_json()
    back = obs_trace.Tracer.from_json(text)
    assert back == tr.spans
    assert [s.kind for s in tr.spans] == ["span", "event", "counter", "span"]
    # sids are emission-ordered and sorted_spans is stable on start ties
    assert [s.sid for s in obs_trace.sorted_spans(tr.spans)] == [0, 3, 1, 2]


def test_trace_rejects_foreign_schema(clean_obs):
    with pytest.raises(ValueError, match="schema"):
        obs_trace.Tracer.from_json(json.dumps({"schema": "x", "spans": []}))


def test_trace_deterministic_under_fixed_clock(clean_obs):
    def emit():
        tr = obs_trace.Tracer(clock=fixed_clock([0.0, 0.5, 1.0, 1.5]))
        with tr.span("a", cat="c", track="t", args={"n": 7}):
            tr.event("b", cat="c", track="t")
        return tr.to_json()
    assert emit() == emit()
    spans = obs_trace.Tracer.from_json(emit())
    assert spans[0].start == 0.0 and spans[0].end == 1.0
    assert spans[1].start == spans[1].end == 0.5


def test_disabled_path_allocates_nothing(clean_obs, tmp_path):
    assert not obs_trace.tracing_enabled()
    before = obs_trace.Span.allocated
    for _ in range(100):
        with obs_trace.span("hot", cat="x", track="t") as s:
            assert s is None
        obs_trace.event("e", cat="x")
        obs_trace.counter("c", 1.0)
    assert obs_trace.Span.allocated == before          # zero Span objects
    assert obs_trace.span("again") is obs_trace.NULL_SPAN  # shared singleton
    # A profiler-backed tracer that is not installed changes nothing.
    obs_trace.Tracer(profiler=True)
    with obs_trace.span("hot", cat="x", track="t") as s:
        assert s is None
    assert obs_trace.Span.allocated == before
    # Disabled metrics helpers: global registry stays empty.
    obs_metrics.inc("nope")
    obs_metrics.set_gauge("nope_g", 1.0)
    obs_metrics.observe("nope_h", 0.5)
    assert obs_metrics.get_registry().snapshot() == {}
    assert list(tmp_path.iterdir()) == []              # and no files appear


# ---------------------------------------------------------------------------
# Metrics registry + exporters
# ---------------------------------------------------------------------------

def test_registry_instruments_and_snapshot(clean_obs):
    reg = MetricsRegistry()
    reg.counter("hits", labels={"source": "memo"}).inc(3)
    reg.counter("hits", labels={"source": "cold"}).inc()
    reg.gauge("depth").set(7.5)
    h = reg.histogram("lat", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap['hits{source="memo"}'] == 3
    assert snap['hits{source="cold"}'] == 1
    assert snap["depth"] == 7.5
    assert snap["lat"]["count"] == 3 and snap["lat"]["sum"] == 5.55
    assert snap["lat"]["buckets"] == {"0.1": 1, "1.0": 1, "+Inf": 1}
    # one name = one type, forever
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("hits")


def test_prometheus_textfile_format(clean_obs, tmp_path):
    reg = MetricsRegistry()
    reg.counter("sel_total", labels={"source": "cold"}).inc(2)
    reg.gauge("fidelity").set(0.97)
    h = reg.histogram("step_s", bounds=(0.5, 1.0))
    h.observe(0.2)
    h.observe(2.0)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE sel_total counter" in lines
    assert 'sel_total{source="cold"} 2' in lines
    assert "fidelity 0.97" in lines
    # histogram buckets are CUMULATIVE and end at +Inf == count
    assert 'step_s_bucket{le="0.5"} 1' in lines
    assert 'step_s_bucket{le="1.0"} 1' in lines
    assert 'step_s_bucket{le="+Inf"} 2' in lines
    assert "step_s_count 2" in lines
    path = tmp_path / "m.prom"
    reg.write_prometheus(str(path))
    assert path.read_text() == text
    assert not os.path.exists(str(path) + ".tmp")     # atomic replace


def test_registry_merge_semantics(clean_obs):
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(5)
    a.gauge("g").set(1.0)
    b.gauge("g").set(9.0)
    a.histogram("h", bounds=(1.0,)).observe(0.5)
    b.histogram("h", bounds=(1.0,)).observe(2.0)
    a.merge(b)
    snap = a.snapshot()
    assert snap["n"] == 7                    # counters add
    assert snap["g"] == 9.0                  # gauges take the newer value
    assert snap["h"]["count"] == 2           # histograms add bucket-wise
    assert snap["h"]["buckets"] == {"1.0": 1, "+Inf": 1}


def test_jsonl_sink_and_registry_jsonl(clean_obs, tmp_path):
    path = str(tmp_path / "sub" / "m.jsonl")
    with JsonlSink(path) as sink:             # creates parent dirs
        sink.write({"a": 1})
    reg = MetricsRegistry()
    reg.counter("k").inc()
    reg.write_jsonl(path, kind="test")        # appends
    recs = [json.loads(l) for l in open(path) if l.strip()]
    assert recs[0] == {"a": 1}
    assert recs[1]["kind"] == "test" and recs[1]["metrics"]["k"] == 1


# ---------------------------------------------------------------------------
# MetricLogger shim (runtime/metrics.py): byte-compatible legacy API
# ---------------------------------------------------------------------------

def test_metric_logger_shim_regression(clean_obs, tmp_path):
    path = str(tmp_path / "log" / "steps.jsonl")
    with MetricLogger(path, window=2) as log:     # now a context manager
        r0 = log.log(0, loss=1.5, step_time=0.5, note=object())
        r1 = log.log(1, loss=1.25, step_time=0.5)
        r2 = log.log(2, loss=1.0, step_time=0.5)
    # The original record schema, bit for bit: floats coerced, unfloatable
    # values stringified, steps_per_s over the rolling window.
    assert r0["step"] == 0 and r0["loss"] == 1.5
    assert isinstance(r0["note"], str)
    assert r0["steps_per_s"] == pytest.approx(1 / 0.5)
    assert r2["steps_per_s"] == pytest.approx(2 / 1.0)   # window=2
    recs = [json.loads(l) for l in open(path) if l.strip()]
    assert [r["step"] for r in recs] == [0, 1, 2]
    assert recs[1] == {k: v for k, v in r1.items()}
    log.close()                                           # idempotent
    # pathless logger still computes records, writes nothing
    nolog = MetricLogger()
    rec = nolog.log(5, x=2)
    assert rec["x"] == 2.0 and list(tmp_path.glob("*.jsonl")) == []


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------

def test_fidelity_of_edge_cases(clean_obs):
    assert fidelity_of(1.0, 1.0) == 1.0
    assert fidelity_of(2.0, 1.0) == 0.5
    assert fidelity_of(1.0, 40.0) == pytest.approx(1 / 40)
    assert fidelity_of(0.0, 1.0) == 0.0
    assert fidelity_of(-1.0, 1.0) == 0.0
    assert fidelity_of(float("nan"), 1.0) == 0.0
    assert fidelity_of(1.0, float("inf")) == 0.0


def test_drift_monitor_rolling_gauge_and_jsonl(clean_obs, tmp_path):
    path = str(tmp_path / "drift.jsonl")
    reg = MetricsRegistry()
    with DriftMonitor(path=path, window=8, registry=reg) as mon:
        assert mon.fidelity() == 1.0                     # empty window
        assert mon.record(site="gemm", shape=(64, 64, 64),
                          predicted_s=1e-3, measured_s=1e-3) == 1.0
        assert reg.gauge("drift_fidelity").value == 1.0
        mon.record(site="gemm", shape=(64, 64, 64),
                   predicted_s=1e-3, measured_s=4e-2)    # 40x outlier
        assert reg.gauge("drift_fidelity").value == pytest.approx(
            (1.0 + 1 / 40) / 2)
        assert reg.counter("drift_records_total").value == 2
    recs = [json.loads(l) for l in open(path) if l.strip()]
    assert all(r["schema"] == "repro/drift/v1" for r in recs)
    assert [r["seq"] for r in recs] == [1, 2]
    assert recs[0]["fidelity"] == 1.0
    assert recs[1]["rolling_fidelity"] == pytest.approx((1.0 + 1 / 40) / 2)
    assert "time" not in recs[0]            # byte-deterministic by default


def test_drift_on_virtual_device(clean_obs, tmp_path):
    """predicted == simulated -> fidelity exactly 1.0; the analytical
    prediction itself stays >= 0.95 on a compute-bound shape; a
    FaultyDevice outlier measurement visibly dents the gauge."""
    hw = PRESETS["tpu_v5e"]
    dev = VirtualDevice(hw)
    sel = select_gemm_config(4096, 4096, 4096, hw=hw)
    sim_s = dev.gemm_time(sel.problem, sel.config)
    reg = MetricsRegistry()
    mon = DriftMonitor(path=str(tmp_path / "d.jsonl"), window=16,
                       registry=reg)
    # The simulator measured against its own pricing: exact agreement.
    f = mon.record(site="gemm", shape=(4096, 4096, 4096), topo=hw.name,
                   predicted_s=sim_s, measured_s=sim_s)
    assert f == 1.0 and mon.fidelity() == 1.0
    # The analytical model vs the event simulator (the paper's >=95% claim
    # on compute-bound shapes) — recorded through record_selection.
    f2 = mon.record_selection(sel, sim_s, topo=hw.name)
    assert f2 >= 0.95
    assert reg.gauge("drift_fidelity").value >= 0.95
    before = reg.gauge("drift_fidelity").value
    # FaultyDevice: probe_outlier=1.0 multiplies every measurement by 40x.
    faulty = FaultyDevice(VirtualDevice(hw), FaultPlan(probe_outlier=1.0))
    bad_s = faulty.gemm_time(sel.problem, sel.config)
    assert bad_s == pytest.approx(sim_s * 40.0)
    mon.record_selection(sel, bad_s, topo=hw.name)
    after = reg.gauge("drift_fidelity").value
    assert after < before and after < 0.95
    mon.close()
    recs = [json.loads(l) for l in open(tmp_path / "d.jsonl")]
    assert recs[-1]["config"]["bm"] == sel.config.bm
    assert recs[-1]["topo"] == hw.name


def test_record_selection_defaults_to_topology_fingerprint(clean_obs,
                                                           tmp_path):
    """Regression: the ``topo`` column used to default to the preset NAME
    (``sel.hardware``), which survives recalibration unchanged and cannot
    be validated — poisoning the residual corrector's training set.  It
    must default to the selection's topology fingerprint, and stay empty
    for legacy selection objects predating the field."""
    from repro.core import topology_fingerprint
    hw = PRESETS["tpu_v5e"]
    sel = select_gemm_config(256, 512, 512, hw=hw)
    assert sel.topo_fingerprint == topology_fingerprint(hw)
    path = str(tmp_path / "d.jsonl")
    with DriftMonitor(path=path, registry=obs_metrics.MetricsRegistry()) \
            as mon:
        mon.record_selection(sel, 1e-3)                # no explicit topo
        mon.record_selection(sel, 1e-3, topo="custom") # explicit still wins

        class _Legacy:                                 # pre-fingerprint sel
            problem, config, predicted = sel.problem, sel.config, \
                sel.predicted
        mon.record_selection(_Legacy(), 1e-3)
    recs = [json.loads(l) for l in open(path) if l.strip()]
    assert recs[0]["topo"] == topology_fingerprint(hw)
    assert recs[0]["topo"] != hw.name
    assert recs[1]["topo"] == "custom"
    assert recs[2]["topo"] == ""


def test_record_step_drift_noop_without_monitor(clean_obs):
    assert obs_drift.get_drift_monitor() is None
    obs_drift.record_step_drift(site="decode_step", shape=(4,),
                                predicted_s=1.0, measured_s=1.0)
    assert obs_metrics.get_registry().snapshot() == {}


# ---------------------------------------------------------------------------
# Instrumented call sites
# ---------------------------------------------------------------------------

def test_selection_emits_span_and_counter(clean_obs):
    tr = obs_trace.Tracer()
    obs_trace.set_tracer(tr)
    obs_metrics.enable_metrics(True)
    sel = select_gemm_config(384, 512, 640, hw=PRESETS["tpu_v5e"])
    evs = [s for s in tr.spans if s.name == "select_gemm_config"]
    assert len(evs) == 1
    args = evs[0].args
    assert args["shape"] == [384, 512, 640, 1]
    assert args["config"]["bm"] == sel.config.bm
    assert args["predicted_s"] == sel.predicted.total
    assert args["n_candidates"] == sel.n_candidates
    assert set(args["level_seconds"]) == set(args["level_bytes"])
    snap = obs_metrics.get_registry().snapshot()
    assert sum(v for k, v in snap.items()
               if k.startswith("selections_total")) >= 1


def test_raising_hook_bumps_error_counter_once_per_call(clean_obs):
    obs_metrics.enable_metrics(True)

    def bad_hook(sel, source):
        raise RuntimeError("boom")

    add_selection_hook(bad_hook)
    try:
        def n_errors():
            return obs_metrics.get_registry().counter(
                "selection_hook_errors", labels={"hook": "bad_hook"}).value
        with pytest.warns(RuntimeWarning, match="hook skipped") as w:
            select_gemm_config(96, 128, 160, hw=PRESETS["tpu_v5e"])
        assert n_errors() == 1                   # exactly once per call
        assert any("bad_hook" in str(x.message) for x in w)
        with pytest.warns(RuntimeWarning, match="hook skipped"):
            select_gemm_config(96, 128, 160, hw=PRESETS["tpu_v5e"])
        assert n_errors() == 2
    finally:
        remove_selection_hook(bad_hook)


def test_plan_buckets_span_and_gauges(clean_obs):
    tr = obs_trace.Tracer()
    obs_trace.set_tracer(tr)
    obs_metrics.enable_metrics(True)
    plan = plan_buckets([5, 9, 13, 7],
                        gemms=[(512, 512), (512, 2048)],
                        hw=PRESETS["tpu_v5e"], max_buckets=2)
    sp = [s for s in tr.spans if s.name == "plan_buckets"]
    assert len(sp) == 1 and sp[0].kind == "span"
    assert sp[0].args["edges"] == list(plan.edges)
    assert sp[0].args["pad_fraction"] == plan.pad_fraction
    snap = obs_metrics.get_registry().snapshot()
    assert snap["bucket_plan_pad_fraction"] == plan.pad_fraction


# ---------------------------------------------------------------------------
# Simulator event capture + Perfetto export
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset", ["tpu_v5e", "gpu_h100_like"])
def test_simulator_events_do_not_change_pricing(clean_obs, preset):
    hw = PRESETS[preset]
    sel = select_gemm_config(384, 512, 768, hw=hw)
    base = simulate_gemm(sel.problem, sel.config, hw)
    events = []
    traced = simulate_gemm(sel.problem, sel.config, hw, events=events)
    assert traced.time == base.time                     # bit-identical
    assert traced.hbm_bytes == base.hbm_bytes
    assert len(events) > 0
    for track, name, t0, t1, args in events:
        assert isinstance(track, str) and isinstance(name, str)
        assert 0.0 <= t0 <= t1 <= base.time + 1e-12
        assert args is None or isinstance(args, dict)


def test_perfetto_export_loadable(clean_obs, tmp_path):
    tr = obs_trace.Tracer(clock=fixed_clock([0.0, 1e-3, 2e-3]))
    with tr.span("prefill", cat="engine", track="engine"):
        tr.event("select_gemm_config", cat="selection", track="selection")
    hw = PRESETS["tpu_v5e"]
    sel = select_gemm_config(256, 256, 256, hw=hw)
    ev = []
    simulate_gemm(sel.problem, sel.config, hw, events=ev)
    path = str(tmp_path / "trace.json")
    doc = export_chrome_trace(path, spans=tr.spans,
                              sim_timelines=[("gemm", ev)])
    on_disk = json.load(open(path))
    assert on_disk == doc
    evs = doc["traceEvents"]
    # Chrome-trace invariants: metadata names, pids 1 (measured) and
    # 2 (modeled), X events carry ts+dur in microseconds.
    pids = {e["pid"] for e in evs}
    assert pids == {1, 2}
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all("ts" in e and "dur" in e for e in xs)
    assert any(e["name"].startswith("gemm:") for e in xs if e["pid"] == 2)
    assert [e for e in evs if e["ph"] == "i"]           # the instant


# ---------------------------------------------------------------------------
# Engine: tracing observes, never perturbs
# ---------------------------------------------------------------------------

def _tiny_engine_setup():
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lens = [5, 9, 7]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=l).astype(np.int32)
               for l in lens]
    plan = plan_buckets(
        lens, gemms=step_gemms(cfg.d_model, cfg.d_ff,
                               kv_dim=cfg.num_kv_heads * cfg.head_dim,
                               vocab=cfg.vocab_size,
                               swiglu=cfg.activation == "swiglu"),
        hw=ops.get_default_hardware(), max_buckets=2)
    return model, params, prompts, plan


def test_engine_tracing_identical_output(clean_obs):
    model, params, prompts, plan = _tiny_engine_setup()
    hooked = []

    def run_once():
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            plan=plan, temperature=0.0, seed=0,
                            sync_every=1, quiet=True,
                            decode_fault=lambda step, g: hooked.append(step))
        for p in prompts:
            eng.submit(p, max_new_tokens=3)
        eng.warm_start()
        return eng.run()

    before = obs_trace.Span.allocated
    off = run_once()
    assert obs_trace.Span.allocated == before     # no tracer: no Span built
    tr = obs_trace.Tracer()
    obs_trace.set_tracer(tr)
    obs_metrics.enable_metrics(True)
    on = run_once()
    obs_trace.set_tracer(None)
    # Identical tokens and identical non-timing stats.
    for i in off["results"]:
        assert np.array_equal(off["results"][i].tokens,
                              on["results"][i].tokens)
    for key in ("steps", "drained", "retries", "bucket_hits",
                "pad_fraction", "tokens_emitted", "queued_left"):
        assert off[key] == on[key], key
    # The traced run produced the span taxonomy DESIGN.md §11 documents,
    # and nothing under the names they replaced.
    eng = [s for s in tr.spans if s.track == "engine"]
    names = {s.name for s in eng}
    assert {"warm_start", "init", "admit", "upload", "hook",
            "decode_dispatch", "sync", "bookkeeping", "collect"} <= names
    assert not names & {"prefill", "decode_step", "straggler_flag"}
    assert all(s.kind == "span" for s in eng if s.name != "status")
    steps = on["steps"]

    def of(name):
        return [s for s in eng if s.name == name]
    admits = of("admit")
    assert len(admits) == len(prompts)
    assert sorted(s.args["rid"] for s in admits) == list(range(len(prompts)))
    for s in admits:
        res = on["results"][s.args["rid"]]
        assert s.args["prompt_len"] == res.prompt_len
        assert s.args["padded_len"] == res.padded_len
        assert s.args["queue_wait_ms"] == pytest.approx(
            (res.t_admit - res.t_submit) * 1e3)
        assert 0 < res.t_submit <= res.t_admit
    # One dispatch, one upload, one hook and one sync per step (sync_every
    # 1), each carrying its step; the hook runs inside the dispatch.
    for name in ("decode_dispatch", "upload", "hook", "sync"):
        assert [s.args["step"] for s in of(name)] == list(range(steps)), name
    for d, h in zip(of("decode_dispatch"), of("hook")):
        assert d.start <= h.start <= h.end <= d.end
    assert all(s.args["active"] >= 1 for s in of("decode_dispatch"))
    # A step's sync ends before the next step's upload starts.
    for sy, up in zip(of("sync"), of("upload")[1:]):
        assert sy.end <= up.start
    assert len(of("init")) == len(of("collect")) == 1
    # Every step's ready stamp, in order.
    assert [n for n, _ in on["step_ready_s"]] == list(range(1, steps + 1))
    assert len(on["step_dispatch_s"]) == steps
    assert on["tokens_per_s"] == pytest.approx(
        on["tokens_emitted"] / on["t_decode_s"])
    # Engine counters were merge-published into the global registry.
    snap = obs_metrics.get_registry().snapshot()
    assert snap["engine_steps"] == on["steps"]
    assert snap["engine_tokens_emitted"] == on["tokens_emitted"]
    assert "engine_straggler_flags" not in snap


def test_engine_syncs_every_window_and_the_tail(clean_obs):
    """With sync_every=4 the engine blocks at each fourth step and at run
    end: one ``sync`` span and one ready stamp per window."""
    model, params, prompts, plan = _tiny_engine_setup()
    tr = obs_trace.Tracer()
    obs_trace.set_tracer(tr)
    eng = ServingEngine(model, params, max_batch=2, max_len=64, plan=plan,
                        sync_every=4, quiet=True)
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    stats = eng.run()
    obs_trace.set_tracer(None)
    steps = stats["steps"]
    marks = list(range(4, steps + 1, 4))
    if steps % 4:
        marks.append(steps)
    assert [n for n, _ in stats["step_ready_s"]] == marks
    syncs = [s for s in tr.spans if s.name == "sync"]
    assert [s.args["step"] + 1 for s in syncs] == marks
    stamps = [t for _, t in stats["step_ready_s"]]
    assert stamps == sorted(stamps)


def test_obs_imports_nothing_of_core_or_launch():
    """DESIGN.md §11's import rule, in a fresh interpreter: importing
    ``repro.obs`` (and building a profiler tracer) pulls in no module of
    ``repro.core`` or ``repro.launch``."""
    import subprocess
    import sys
    code = ("import sys; import repro.obs; "
            "repro.obs.Tracer(profiler=True); "
            "print([m for m in sys.modules "
            "if m.startswith(('repro.core', 'repro.launch'))])")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True,
                         env=dict(os.environ, PYTHONPATH=src,
                                  JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "[]"


def test_profiler_tracer_puts_spans_in_the_profiler_trace(clean_obs,
                                                          tmp_path):
    """``Tracer(profiler=True)``: each span is also a profiler annotation
    named ``<track>.<name>`` carrying its args as event stats; the
    in-memory span list is unchanged."""
    import glob
    from jax.profiler import ProfileData
    tr = obs_trace.Tracer(profiler=True)
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("admit", cat="engine", track="engine",
                 args={"rid": 7, "queue_wait_ms": 1.5}):
        tr.event("inner", track="engine")
    jax.profiler.stop_trace()
    assert [s.name for s in tr.spans] == ["admit", "inner"]
    assert tr.spans[0].end is not None
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [dict(e.stats) for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events if e.name == "engine.admit"]
    assert len(found) == 1
    assert found[0]["rid"] == 7 and found[0]["queue_wait_ms"] == 1.5


def test_obs_report_skips_truncated_jsonl_tail(clean_obs, tmp_path):
    """Regression: a serving process killed mid-append leaves a truncated
    trailing JSONL line; ``tools/obs_report.py`` used to die on it with a
    JSONDecodeError.  It must summarize the records that DID land and note
    how many lines it skipped."""
    from tools.obs_report import build_report, summarize_drift
    obs = tmp_path / "obs"
    obs.mkdir()
    drift = obs / "drift.jsonl"
    with DriftMonitor(path=str(drift),
                      registry=obs_metrics.MetricsRegistry()) as mon:
        mon.record(site="gemm", shape=(64, 64, 64),
                   predicted_s=1e-3, measured_s=1e-3)
        mon.record(site="gemm", shape=(64, 64, 64),
                   predicted_s=1e-3, measured_s=2e-3)
    with open(drift, "a") as f:
        f.write('{"schema": "repro/drift/v1", "seq": 3, "site": "ge')
    reg = MetricsRegistry()
    reg.counter("engine_steps").inc(4)
    reg.write_jsonl(str(obs / "metrics.jsonl"), kind="final")
    with open(obs / "metrics.jsonl", "a") as f:
        f.write('{"kind": "final", "metr')
    report = build_report(str(obs))
    assert "## Drift — 2 records" in report
    assert "## Metrics" in report
    assert report.count("skipped 1 malformed line (truncated writer tail)") \
        == 2
    # a file reduced to ONLY a truncated line: note, no crash, no table
    lone = obs / "lone.jsonl"
    lone.write_text('{"schema": "repro/drift/v1"')
    lines = summarize_drift(str(lone))
    assert lines == ["_skipped 1 malformed line (truncated writer tail)_"]


def test_engine_quiet_suppresses_stdout(clean_obs, capsys):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_batch=1, max_len=32,
                        temperature=0.0, seed=0, quiet=True)
    eng.submit(np.arange(4, dtype=np.int32) % cfg.vocab_size,
               max_new_tokens=2)
    eng.run()
    assert capsys.readouterr().out == ""
