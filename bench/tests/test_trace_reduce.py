"""Trace reduction: interval arithmetic, program labels, gaps, breakdown."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import trace_reduce as tr


GEMM = ('%closed_call.5 = bf16[16,1024]{1,0} custom-call(bf16[16,3072]{1,0} '
        '%a, bf16[3072,1024]{1,0} %b), custom_call_target="tpu_custom_call"')
FLASH = ('%closed_call.9 = bf16[1,24,512,128]{3,2,1,0} custom-call('
         'bf16[1,24,512,128]{3,2,1,0} %q, bf16[1,8,512,128]{3,2,1,0} %k, '
         'bf16[1,8,512,128]{3,2,1,0} %v), custom_call_target="tpu_custom_call"')


def ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in
                                lines.items()])


def synthetic():
    """Device 0: a prefill program [0, 40) us, idle [40, 50), a decode
    program [50, 100), idle [100, 130), decode [130, 160).  Host: the hook
    over [40, 50), waiting for an arrival over [100, 130)."""
    us = 1000
    ops = [ev(GEMM, 0, 10 * us), ev(FLASH, 10 * us, 20 * us),
           ev("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 30 * us, 10 * us),
           ev("%while.2 = (s32[]) while(s32[] %t)", 50 * us, 50 * us),
           ev(GEMM, 50 * us, 30 * us),
           ev("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)", 80 * us, 20 * us),
           ev(GEMM, 130 * us, 10 * us),
           ev("%all-reduce.4 = f32[8]{0} all-reduce(f32[8]{0} %p)",
              140 * us, 20 * us),
           ev("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)", 150 * us, 5 * us)]
    mods = [ev("jit__lambda(1)", 0, 40 * us), ev("jit__lambda(2)", 50 * us,
                                                 50 * us),
            ev("jit__lambda(2)", 130 * us, 30 * us)]
    dev = plane("/device:TPU:0", **{tr.OPS_LINE: ops,
                                    tr.MODULES_LINE: mods})
    host = plane("/host:CPU", python=[
        ev("bench.engine_run", 0, 100 * us),
        ev("bench.hook", 40 * us, 10 * us),
        ev("bench.wait_arrival", 100 * us, 30 * us),
        ev("bench.engine_run", 130 * us, 30 * us)])
    return [dev, host, plane("/device:TPU:1", **{tr.OPS_LINE: ops[:2]})]


def test_merge_and_subtract():
    assert tr.merge([0, 5, 1], [2, 6, 3]) == [(0, 3), (5, 6)]
    assert tr.union_length(np.array([0, 1, 5.]), np.array([2, 3, 6.])) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == 2
    assert tr.subtract([(0, 2)], []) == 2


def test_stable_names_and_kinds():
    assert tr.stable_name("fusion.123") == "fusion"
    assert tr.stable_name("copy.4.5") == "copy"
    assert tr.classify(GEMM) == ("gemm_16x1024x3072", "gemm")
    assert tr.classify(FLASH) == ("flash_attention_1x24x512x128", "flash")
    assert tr.classify("%all-gather-start.2 = (bf16[8]) all-gather-start("
                       "bf16[2] %x)")[1] == "collective"
    assert tr.classify("%while.16 = (s32[]) while(s32[] %t)") == (
        "while", "control")
    assert tr.classify("%copy.138 = f32[12,8]{1,0} copy(bf16[12,8] %x)") == (
        "copy", "op")


def test_programs_busy_and_gaps():
    red = tr.reduce_planes(synthetic(), chips=1)
    d = red.devices[0]
    assert d.executions == {"prefill": 1, "decode": 2}
    assert list(d.program[:3]) == ["prefill"] * 3
    assert d.program_time("decode") == pytest.approx(80e-6)
    assert d.time(kind="gemm") == pytest.approx(50e-6)
    assert d.time(kind="flash") == pytest.approx(20e-6)
    assert red.window == pytest.approx((0.0, 160e-6))
    assert red.busy_s == pytest.approx(120e-6)
    assert np.allclose(red.gaps(), [(40e-6, 50e-6), (100e-6, 130e-6)])


def test_busy_is_averaged_over_the_chips_used():
    red = tr.reduce_planes(synthetic(), chips=2)
    assert len(red.devices) == 2
    assert red.busy_s == pytest.approx((120e-6 + 30e-6) / 2)


def test_breakdown_names_ops_and_gaps():
    red = tr.reduce_planes(synthetic(), chips=1)
    b = red.breakdown()
    ops = dict(b["device_ops"])
    assert ops["decode:gemm_16x1024x3072"] == pytest.approx(40e-6)
    assert ops["prefill:flash_attention_1x24x512x128"] == pytest.approx(20e-6)
    assert b["device_ops"][0][0] == "decode:gemm_16x1024x3072"
    assert not any(k.endswith(":while") for k in ops)
    assert b["idle_gaps"] == [
        ["host: waiting for an arrival", pytest.approx(30e-6)],
        ["host: in the harness hook", pytest.approx(10e-6)]]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.reduce_planes([plane("/host:CPU")], chips=1)


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on a TPU v5e: phi4-mini at published widths, 12
    slots, two prompts (300 and 700 tokens, buckets 504 and 968) admitted
    and decoded for three steps, profiler on around ``engine.run()``."""
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "phi4_decode_steps.xplane.pb")
    return tr.load(path, chips=1)


def test_recorded_trace_programs(recorded):
    d = recorded.devices[0]
    assert d.executions["prefill"] == 2 and d.executions["decode"] == 3
    # A decode step of phi4-mini at 12 slots: about 85 ms on the chip.
    per_step = d.program_time("decode") / 3
    assert 0.07 < per_step < 0.10
    assert d.program_time("prefill") > 0


def test_recorded_trace_kernels(recorded):
    d = recorded.devices[0]
    names = set(d.name[d.kind == "gemm"])
    # The decode step's seven projections at M = 16 (12 slots, padded),
    # and the prefills' at their bucket edges.
    assert {"gemm_16x3072x3072", "gemm_16x1024x3072", "gemm_16x8192x3072",
            "gemm_16x3072x8192"} <= names
    assert any(n.startswith("gemm_512x") for n in names)
    assert set(d.name[d.kind == "flash"]) == {
        "flash_attention_1x24x512x128", "flash_attention_1x24x1024x128"}
    assert not (d.program[d.kind == "flash"] != "prefill").any()
    assert not (d.program[d.kind == "gemm"] == "other").any()
    # 32 layers x 7 GEMMs in each decode step.
    assert np.sum((d.kind == "gemm") & (d.program == "decode")) == 3 * 32 * 7


def test_recorded_trace_busy_and_gaps(recorded):
    d = recorded.devices[0]
    busy = sum(e - s for s, e in d.busy())
    assert recorded.busy_s == pytest.approx(busy)
    assert tr.union_length(d.start, d.end) <= busy + 1e-9
    assert 0 < busy < recorded.window_s
    gaps = sum(e - s for s, e in recorded.gaps())
    assert busy + gaps == pytest.approx(recorded.window_s)
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert all(g[0].startswith("host: ") for g in b["idle_gaps"])


def test_window_length_cuts_off_what_runs_after_it():
    red = tr.reduce_planes(synthetic(), chips=1, length=120e-6)
    assert red.window == pytest.approx((0.0, 120e-6))
    assert red.busy_s == pytest.approx(90e-6)          # [0,40) + [50,100)
    assert red.devices[0].program_time("decode") == pytest.approx(50e-6)
