"""Every per-layer reader on a synthetic run: it returns a number where it
finds something to read and None where it does not."""
import numpy as np
import pytest

from bench import reference, registry
from bench.peaks import peaks_for
from bench.run import Record
from bench.stats import Served, Step
from bench.tests.test_trace_reduce import synthetic
from bench import trace_reduce

BENCH = registry.load_benchmark()
NAMES = [m["name"] for m in BENCH["per_layer"]]


def record(trace, chips=1):
    d = reference.dims_of(registry.cell("phi4-mini.chat").config)
    steps = [Step(0.1 * (k + 1), int(k % 10 == 0)) for k in range(130)]
    served = []
    for i in range(10):
        a = 10 * i
        n = 30
        idx = np.maximum(a + np.arange(n) - 1, a)
        served.append(Served(i, due=0.1 * a - 0.05, prompt_len=500,
                             output_len=n, phase="window",
                             admitted=0.1 * a, tokens=np.zeros(n, np.int32),
                             delivered=np.array([steps[k].done for k in idx]),
                             steps=idx.tolist(), finished=True))
    return Record(dims=d, chips=chips,
                  peaks=peaks_for("TPU v5 lite"), served=served,
                  steps=steps, t_open=0.0, t_close=10.0,
                  compiles=[5.0, 20.0], trace=trace)


@pytest.mark.parametrize("name", NAMES)
def test_reader_runs(name):
    red = trace_reduce.reduce_planes(synthetic(), chips=1)
    mod = registry.reader(name)
    v = mod.read(record(red))
    untraced = mod.read(record(None))
    if name in ("compiles_in_window", "queue_wait_p90_ms"):
        assert v == untraced and v is not None
    else:
        assert untraced is None
    if name == "collective_share":
        assert v is None                      # one chip: nothing to read
    else:
        assert v is not None and np.isfinite(v) and v >= 0


def test_reader_values():
    red = trace_reduce.reduce_planes(synthetic(), chips=1)
    rec = record(red)
    assert registry.reader("compiles_in_window").read(rec) == 1.0
    assert registry.reader("queue_wait_p90_ms").read(rec) == pytest.approx(
        50.0)
    assert registry.reader("idle_share").read(rec) == pytest.approx(
        100 * 40 / 160)
    assert registry.reader("prefill_share").read(rec) == pytest.approx(
        100 * 40 / 160)
