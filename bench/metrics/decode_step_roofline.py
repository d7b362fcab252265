"""Model (decode): least time of a decode step's required work over its
device time on device 0, averaged per step.  Memory bound at these sizes:
the work is the chip's share of the weights plus the K/V of the positions
each active slot holds, plus the logits (``bench/work.py``)."""
from bench.metrics._common import decode_work

UNIT, LAYER, MOVES = "%", "model", "itl_p50_ms"


def read(rec):
    if rec.trace is None:
        return None
    dev = rec.trace.devices[0]
    n_exec = dev.executions.get("decode", 0)
    w, n_steps = decode_work(rec)
    if not n_exec or not n_steps:
        return None
    least = w.seconds(rec.peaks) / n_steps
    return 100.0 * least / (dev.program_time("decode") / n_exec)
