"""Kernels (Pallas GEMM, prefill): least time of the seven block GEMMs of
every layer over each prompt's real tokens, per chip, over the GEMM
kernels' device time in the prefill program on device 0, averaged per
execution.  These GEMMs are compute bound, so the share cannot pass 100%
however their operands were staged.  The decode GEMMs are left out: XLA
prefetches their weights into on-chip memory with async copies outside the
kernel, so a kernel's own time leaves out the reads that bound it (the
decode step as a whole is ``decode_step_roofline``)."""
from bench import work
from bench.metrics._common import prefills_in_window

UNIT, LAYER, MOVES = "%", "kernels", "ttft_p50_ms"


def read(rec):
    if rec.trace is None:
        return None
    dev, d, p = rec.trace.devices[0], rec.dims, rec.peaks
    lens = prefills_in_window(rec)
    n_pre = dev.executions.get("prefill", 0)
    spent = dev.time(program="prefill", kind="gemm")
    if not lens or not n_pre or not spent:
        return None
    per = sum(d.layers * work.layer_gemms(d, n).seconds(p)
              for n in lens) / len(lens)
    return 100.0 * n_pre * per / rec.chips / spent
