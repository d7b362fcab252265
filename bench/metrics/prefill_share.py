"""Scheduler: device time of the prefill program on device 0 over the
traced window."""
UNIT, LAYER, MOVES = "%", "scheduler", "itl_p98_ms"


def read(rec):
    if rec.trace is None or not rec.trace.devices[0].executions.get(
            "prefill"):
        return None
    t = rec.trace
    return 100.0 * t.devices[0].program_time("prefill") / t.window_s
