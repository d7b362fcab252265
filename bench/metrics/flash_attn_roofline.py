"""Kernels (Pallas flash attention, prefill): least time of causal
attention over each prompt's real tokens, every layer, per chip, over the
flash kernel's device time on device 0, averaged per prefill execution."""
from bench import work
from bench.metrics._common import prefills_in_window

UNIT, LAYER, MOVES = "%", "kernels", "ttft_p50_ms"


def read(rec):
    if rec.trace is None:
        return None
    dev, d = rec.trace.devices[0], rec.dims
    lens = prefills_in_window(rec)
    n_pre = dev.executions.get("prefill", 0)
    spent = dev.time(kind="flash")
    if not lens or not n_pre or not spent:
        return None
    per = sum(d.layers * work.flash_attention(d, n).seconds(rec.peaks)
              for n in lens) / len(lens)
    return 100.0 * n_pre * per / rec.chips / spent
