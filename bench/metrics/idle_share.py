"""Device: share of the traced window in which device 0 ran nothing."""
UNIT, LAYER, MOVES = "%", "device", "itl_p50_ms"


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace
    busy = sum(e - s for s, e in t.devices[0].busy())
    return 100.0 * (1.0 - busy / t.window_s)
