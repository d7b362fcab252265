"""Scheduler: 90th percentile of due -> admitted over the requests due in
the window (harness stamps); one not admitted by the close counts with
close - due, a lower bound."""
import numpy as np

UNIT, LAYER, MOVES = "ms", "scheduler", "ttft_p50_ms"


def read(rec):
    w = [(r.admitted if r.admitted is not None and r.admitted <= rec.t_close
          else rec.t_close) - r.due
         for r in rec.served if rec.t_open <= r.due < rec.t_close]
    return float(np.percentile(w, 90)) * 1e3 if w else None
