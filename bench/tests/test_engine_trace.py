"""The engine's spans in a profiler trace: alignment, gap names, the
per-step numbers, on a synthetic trace, the recorded one, and a tiny
engine traced on the CPU."""
import glob
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import engine_trace as et
from bench import trace_reduce as tr
from bench.tests.test_trace_reduce import GEMM

US = 1000          # ns per microsecond


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * US, duration_ns=dur_us * US,
              stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in
                                lines.items()])


def synthetic():
    """Device 0 runs decodes over [0, 80), [100, 180), [200, 280) and a
    small program at [400, 401) (run ids 1-4), all on the device's clock.
    The host's clock runs 5 us ahead, and programs 2 and 4 start the
    moment they are enqueued.  Host spans (host clock):

    * step 1: sync of step 0 [10, 87), bookkeeping [87, 88), an admission
      [88, 93) (queue wait 3 ms), upload [93, 96), dispatch [96, 105)
      holding the hook [96, 99) (the harness's [96.5, 98.5)), bookkeeping
      [105, 106), sync [106, 187);
    * step 2: bookkeeping [187, 188), upload [188, 191), dispatch
      [191, 204) holding the hook [191, 195) (the harness's
      [191.5, 194.5)), bookkeeping [204, 205), sync [205, 285);
    * nothing from 285 to the last program; ``bench.engine_run`` [0, 401)
      holds it all."""
    ops = [ev(GEMM, 0, 80), ev(GEMM, 100, 80), ev(GEMM, 200, 80),
           ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 400, 1)]
    mods = [ev("jit__lambda(1)", 0, 80, run_id=1),
            ev("jit__lambda(1)", 100, 80, run_id=2),
            ev("jit__lambda(1)", 200, 80, run_id=3),
            ev("jit_other(2)", 400, 1, run_id=4)]
    dev = plane("/device:TPU:0", **{tr.OPS_LINE: ops,
                                    tr.MODULES_LINE: mods})
    enq = [ev(et.ENQUEUE, h, 1, device_ordinal=0, run_id=r)
           for h, r in ((-10, 1), (105, 2), (204, 3), (405, 4))]
    spans = [
        ev("bench.engine_run", 0, 401),
        ev("engine.sync", 10, 77, step=0),
        ev("engine.bookkeeping", 87, 1, step=0),
        ev("engine.admit", 88, 5, rid=4, slot=1, queue_wait_ms=3.0),
        ev("engine.upload", 93, 3, step=1),
        ev("engine.decode_dispatch", 96, 9, step=1, active=2),
        ev("engine.hook", 96, 3, step=1),
        ev("bench.hook", 96.5, 2),
        ev("engine.bookkeeping", 105, 1, step=1),
        ev("engine.sync", 106, 81, step=1),
        ev("engine.bookkeeping", 187, 1, step=1),
        ev("engine.upload", 188, 3, step=2),
        ev("engine.decode_dispatch", 191, 13, step=2, active=2),
        ev("engine.hook", 191, 4, step=2),
        ev("bench.hook", 191.5, 3),
        ev("engine.bookkeeping", 204, 1, step=2),
        ev("engine.sync", 205, 80, step=2)]
    host = plane("/host:CPU", main=enq, python=spans)
    return [dev, host]


@pytest.fixture(scope="module")
def synth():
    return et.timeline(synthetic(), chips=1)


def test_shift_aligns_host_onto_device(synth):
    assert np.allclose(synth.pairs[0], [(0, -10e-6), (100e-6, 105e-6),
                                        (200e-6, 204e-6), (400e-6, 405e-6)])
    s = et.shift(synth)
    assert s == pytest.approx(-5e-6)
    assert all(d >= h + s for d, h in synth.pairs[0])
    # Without the shift, programs 2-4 would start before their enqueue.
    assert sum(d < h for d, h in synth.pairs[0]) == 3


def test_gap_naming(synth):
    """Each gap is named by the innermost span covering most of it; the
    one no span covers keeps the next program's name."""
    assert np.allclose(et.gaps(synth), [(80e-6, 100e-6), (180e-6, 200e-6),
                                        (280e-6, 400e-6)])
    cov = et.covered(synth.spans, 80e-6, 100e-6, et.shift(synth))
    assert cov == pytest.approx({
        "engine.sync": 2e-6, "engine.bookkeeping": 1e-6,
        "engine.admit": 5e-6, "engine.upload": 3e-6,
        "engine.decode_dispatch": 6e-6, "engine.hook": 1e-6,
        "bench.hook": 2e-6})
    gaps, share = et.named_gaps(synth)
    assert [(n, pytest.approx(t)) for n, t, _ in gaps] == [
        ("host: engine (other next)", 120e-6),
        ("engine.decode_dispatch", 20e-6),
        ("engine.decode_dispatch", 20e-6)]
    assert share == pytest.approx(40 / 160)


def test_per_step_numbers(synth):
    # Between syncs: (1 + 5 + 3 + 9 + 1) - 3 us, then (1 + 3 + 13 + 1) - 4.
    assert et.host_ms_per_step(synth) == pytest.approx([16e-3, 14e-3])
    assert et.admit_waits_ms(synth) == [3.0]
    hook = et.hook_after_sync_ms(synth)
    assert hook["stalled"] == pytest.approx([9.5e-3])
    assert hook["unstalled"] == pytest.approx([4.5e-3])
    s = et.summary(synth)
    assert s["engine_host_ms_per_step"] == pytest.approx(15e-3)
    assert s["admit_wait_p90_ms"] == pytest.approx(3.0)
    dev = s["devices"][0]
    assert dev["host_shift_ms"] == pytest.approx(-5e-3)
    assert dev["pairs"] == 4
    assert s["spans"]["engine.sync"] == 3


def test_timeline_round_trips(synth):
    again = et.Timeline.from_json(synth.to_json())
    assert et.summary(again) == et.summary(synth)


def test_untraced_engine_reads_nothing():
    """A trace with no engine spans (the tracer off) gives no per-step
    numbers, and its gaps keep the next program's name."""
    planes = synthetic()
    planes[1].lines = [planes[1].lines[0]]          # enqueues only
    s = et.summary(et.timeline(planes, chips=1))
    assert s["engine_host_ms_per_step"] is None
    assert s["admit_wait_p90_ms"] is None
    assert s["devices"][0]["named_idle_share"] == 0.0
    assert all(n.startswith("host: ") for n, _ in
               s["devices"][0]["idle_gaps"])


@pytest.fixture(scope="module")
def recorded_planes():
    from jax.profiler import ProfileData
    path = os.path.join(os.path.dirname(__file__), "data",
                        "phi4_decode_steps.xplane.pb")
    return list(ProfileData.from_file(path).planes)


def test_recorded_trace_alignment(recorded_planes):
    """phi4-mini on a TPU v5e: every one of the 44 program executions is
    paired with its enqueue; the shift is about -1.4 ms, and after it no
    program starts before its enqueue, nor before the
    ``TpuLoadedExecutable::ExecuteLaunch`` that precedes it (paired in
    order)."""
    tl = et.timeline(recorded_planes, chips=1)
    assert len(tl.pairs[0]) == 44
    s = et.shift(tl)
    assert -2e-3 < s < -1e-3
    assert all(d >= h + s for d, h in tl.pairs[0])
    assert sum(d < h for d, h in tl.pairs[0]) > 0     # before the shift
    host = next(p for p in recorded_planes if p.name == "/host:CPU")
    launch = sorted(e.start_ns * 1e-9 for ln in host.lines for e in ln.events
                    if e.name == "TpuLoadedExecutable::ExecuteLaunch")
    starts = [d for d, _ in tl.pairs[0]]
    assert len(launch) == len(starts)
    assert all(d >= h + s for d, h in zip(starts, launch))


def test_profiler_spans_of_a_tiny_engine(tmp_path):
    """A tiny engine traced on the CPU under ``Tracer(profiler=True)``:
    its phases are in the profiler's trace as ``engine.*`` events with
    their ``rid`` and ``step`` stats."""
    import jax
    from jax.profiler import ProfileData
    from repro.configs.registry import get_config
    from repro.launch.engine import ServingEngine
    from repro.nn.model import Model
    from repro.obs import trace as obs_trace

    cfg = get_config("phi4-mini-3.8b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        sync_every=1, quiet=True)
    rng = np.random.default_rng(0)
    for n in (5, 7, 6):
        eng.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=3)
    eng.run()                                     # compiles outside the trace
    for n in (5, 7, 6):
        eng.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=3)
    prev = obs_trace.set_tracer(obs_trace.Tracer(profiler=True))
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats = eng.run()
    finally:
        jax.profiler.stop_trace()
        obs_trace.set_tracer(prev)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = et.host_spans(ProfileData.from_file(path).planes)

    def of(name):
        return [s for s in spans if s.name == name]
    assert sorted(s.stats["rid"] for s in of("engine.admit")) == [3, 4, 5]
    assert all(s.stats["queue_wait_ms"] >= 0 for s in of("engine.admit"))
    steps = list(range(stats["steps"]))
    for name in ("engine.decode_dispatch", "engine.sync", "engine.upload"):
        assert [s.stats["step"] for s in of(name)] == steps, name
    assert len(of("engine.init")) == len(of("engine.collect")) == 1
    assert not of("engine.hook")                  # no hook installed
