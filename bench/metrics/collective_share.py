"""Collectives: device 0's collective time that no other op overlaps,
over the traced window."""
from bench.trace_reduce import merge, subtract

UNIT, LAYER, MOVES = "%", "collectives", "itl_p50_ms"


def read(rec):
    if rec.trace is None or rec.chips < 2:
        return None
    dev = rec.trace.devices[0]
    coll = dev.kind == "collective"
    if not coll.any():
        return None
    alone = subtract(merge(dev.start[coll], dev.end[coll]),
                     merge(dev.start[~coll], dev.end[~coll]))
    return 100.0 * alone / rec.trace.window_s
