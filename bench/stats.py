"""End-to-end serving metrics from the harness's own stamps.

The engine runs with ``sync_every=1``: it blocks on every decode step's
tokens, and its per-step hook is where the harness stamps the host clock.
So ``done[k]``, the stamp at the hook of step k + 1 (or the return of
``run()`` for a call's last step), is when step k's tokens reached the
host: when a streaming server could send them.

A request admitted at step ``a`` gets its prefill token and its first
decoded token together, at ``done[a]``; its i-th token (i >= 1) at
``done[a + i - 1]``.  Its time to first token runs from when it was due to
``done[a]``.  Its inter-token gaps are the differences between consecutive
delivery times from the first decoded token on: each is one decode step as
that request saw it, the prefills admitted at the top of that step
included.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

TAIL = 98           # the inter-token gap percentile judged as the tail
MIN_BEYOND = 10     # distinct steps beyond the tail that make it more than a
                    # run's few largest stalls; a run with fewer says so


@dataclass
class Step:
    """One decode step: when it completed, and how many prefills ran at the
    top of its loop iteration (admissions)."""
    done: float
    admits: int


@dataclass
class Served:
    """One request as the harness saw it.  ``delivered`` holds each token's
    delivery time (the host clock); empty when it was never admitted."""
    index: int
    due: float
    prompt_len: int
    output_len: int
    phase: str
    admitted: Optional[float] = None
    tokens: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    delivered: np.ndarray = field(default_factory=lambda: np.zeros(0))
    steps: List[int] = field(default_factory=list)   # global step per token
    finished: bool = False


def percentile(values, q: float) -> float:
    """numpy's linear-interpolation percentile over all the values."""
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(served: List[Served], steps: List[Step], t_open: float,
               t_close: float) -> Dict:
    """The window's end-to-end metrics and the diagnostics printed beside
    them.  Only tokens delivered in (t_open, t_close] count, and only gaps
    that end there; time to first token is over the requests due in
    [t_open, t_close), a request with no token by the close counting with
    ``t_close - due`` (a lower bound), so it sorts to the slow end."""
    window = t_close - t_open
    n_tokens = 0
    gaps: List[float] = []
    gap_steps: List[int] = []
    for r in served:
        t = r.delivered
        n_tokens += int(np.count_nonzero((t > t_open) & (t <= t_close)))
        if t.size > 2:
            d = np.diff(t[1:])
            ends = t[2:]
            keep = (ends > t_open) & (ends <= t_close)
            gaps += d[keep].tolist()
            gap_steps += [r.steps[i + 2] for i in np.flatnonzero(keep)]
    due = [r for r in served if t_open <= r.due < t_close]
    ttft = [(r.delivered[0] if r.delivered.size and r.delivered[0] <= t_close
             else t_close) - r.due for r in due]
    unserved = sum(1 for r in due
                   if not (r.delivered.size and r.delivered[0] <= t_close))
    in_window = [k for k, s in enumerate(steps) if t_open < s.done <= t_close]
    stalled = {k for k in in_window if steps[k].admits}
    out = {
        "ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else float("nan"),
        "itl_p50_ms": percentile(gaps, 50) * 1e3 if gaps else float("nan"),
    }
    tail = percentile(gaps, TAIL) if gaps else float("nan")
    out[f"itl_p{TAIL}_ms"] = tail * 1e3
    beyond = {k for g, k in zip(gaps, gap_steps) if g > tail}
    diag = {
        "window_s": window,
        "tokens": n_tokens,
        "requests_due": len(due),
        "requests_unserved_at_close": unserved,
        "gaps": len(gaps),
        "steps": len(in_window),
        "stalled_steps": len(stalled),
        "gaps_beyond_tail": int(np.count_nonzero(np.asarray(gaps) > tail)),
        "steps_beyond_tail": len(beyond),
        "stalled_beyond_tail": len(beyond & stalled),
        "tail_thin": len(beyond) < MIN_BEYOND,
    }
    return {"metrics": out, "diag": diag}
