"""Model (decode step): FLOPs a decode step needs (the active slots'
tokens through every layer and the head, attention over the positions
held), per chip, over its device time on device 0 x peak bf16 FLOP/s,
averaged per step.  The whole step's share of the chip's peak: it bounds
what the kernels' rooflines can give end to end."""
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
    per_step = dev.program_time("decode") / n_exec
    return 100.0 * (w.flops / n_steps) / (per_step * rec.peaks.bf16_flops)
