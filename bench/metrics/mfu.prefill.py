"""Model (prefill): FLOPs of the prompts prefilled in the window (real
tokens, not padding), per chip, over device 0's prefill time x peak bf16
FLOP/s, averaged per execution."""
from bench import work
from bench.metrics._common import prefills_in_window

UNIT, LAYER, MOVES = "%", "model", "ttft_p50_ms"


def read(rec):
    if rec.trace is None:
        return None
    dev = rec.trace.devices[0]
    lens = prefills_in_window(rec)
    n_exec = dev.executions.get("prefill", 0)
    if not lens or not n_exec:
        return None
    flops = sum(work.prefill(rec.dims, n).flops for n in lens) / len(lens)
    per_exec = dev.program_time("prefill") / n_exec
    return 100.0 * flops / rec.chips / (per_exec * rec.peaks.bf16_flops)
