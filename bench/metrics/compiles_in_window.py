"""Entry: backend compiles (JAX monitoring events) inside the window."""
UNIT, LAYER, MOVES = "count", "entry", "itl_p98_ms"


def read(rec):
    return float(sum(1 for t in rec.compiles
                     if rec.t_open <= t <= rec.t_close))
