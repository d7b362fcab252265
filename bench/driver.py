"""Open-loop driving of ``ServingEngine`` through its public API.

``ServingEngine.run()`` serves what is queued and returns when queue and
slots are empty.  Its one per-step hook, ``decode_fault(step, guard)``, is
called at the top of every decode attempt; the harness uses it to stamp the
step that just completed and to submit the requests that have fallen due.
When ``run()`` returns idle, the harness waits for the next due time and
calls it again.  At the first hook after the window closes it asks the
engine to stop (``guard.request_stop()``, the engine's drain).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench.loadgen import Planned
from bench.stats import Served, Step


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


@dataclass
class _Call:
    stamps: Dict[int, float] = field(default_factory=dict)  # step -> hook


class OpenLoop:
    """Serves ``planned`` (due times counted from ``t0``) on ``engine``.

    ``prompts[i]`` holds request i's token ids.  ``on_tick(now)``, when
    given, is called at every hook and every pass of the idle loop (the
    traced run starts and stops the profiler from it)."""

    def __init__(self, engine, planned: List[Planned], prompts: List,
                 t0: float, t_close: float, *,
                 on_tick: Optional[Callable[[float], None]] = None):
        self.engine = engine
        self.prompts = prompts
        self.t_close = t_close
        self.on_tick = on_tick
        self.served = [Served(i, t0 + p.due_s, p.prompt_len, p.output_len,
                              p.phase) for i, p in enumerate(planned)]
        self.steps: List[Step] = []
        self.submit_lag: List[float] = []
        self._next = 0                     # next planned request to submit
        self._rid: Dict[int, int] = {}     # engine rid -> planned index
        self._call: Optional[_Call] = None

    def _submit_due(self, now: float) -> None:
        while (self._next < len(self.served)
               and self.served[self._next].due <= now):
            r = self.served[self._next]
            rid = self.engine.submit(self.prompts[self._next],
                                     max_new_tokens=r.output_len)
            self._rid[rid] = self._next
            self.submit_lag.append(now - r.due)
            self._next += 1

    def hook(self, step: int, guard) -> None:
        with _annotate("bench.hook"):
            now = time.perf_counter()
            self._call.stamps[step] = now
            if self.on_tick is not None:
                self.on_tick(now)
            if now >= self.t_close:
                guard.request_stop()
            else:
                self._submit_due(now)

    def _collect(self, call: _Call, end: float, stats: Dict) -> None:
        n = stats["steps"]
        base = len(self.steps)
        done = [call.stamps.get(k + 1, end) for k in range(n)]
        admits = np.zeros(n, int)
        for rid, res in stats["results"].items():
            r = self.served[self._rid[rid]]
            a = res.admit_step
            if a < n:
                admits[a] += 1
            k = np.arange(res.tokens.size)
            idx = np.maximum(a + k - 1, a)
            idx = idx[idx < n]           # a drain leaves no later steps
            r.tokens = res.tokens[:idx.size]
            r.steps = (base + idx).tolist()
            r.delivered = np.asarray([done[i] for i in idx])
            r.admitted = call.stamps.get(a, end)
            r.finished = res.finished and idx.size == res.tokens.size
        self.steps += [Step(d, int(c)) for d, c in zip(done, admits)]

    def run(self) -> None:
        """Serve until the window closes."""
        while True:
            now = time.perf_counter()
            if now >= self.t_close:
                return
            if self.on_tick is not None:
                self.on_tick(now)
            if (self._next < len(self.served)
                    and self.served[self._next].due <= now):
                self._submit_due(now)
                self._call = _Call()
                with _annotate("bench.engine_run"):
                    stats = self.engine.run()
                self._collect(self._call, time.perf_counter(), stats)
                continue
            due = (self.served[self._next].due
                   if self._next < len(self.served) else self.t_close)
            with _annotate("bench.wait_arrival"):
                time.sleep(max(0.0, min(due, self.t_close) - now))
