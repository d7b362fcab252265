"""The serving engine's spans in a JAX profiler trace, on the device's clock.

With ``repro.obs.trace.Tracer(profiler=True)`` installed, the engine's
phases are host events ``engine.<phase>`` in the profiler's trace, their
args as event stats (``rid``, ``step``, ``queue_wait_ms``...); the harness
adds its own ``bench.*`` spans.  This module reads them beside the device
planes that ``bench/trace_reduce.py`` reduces:

* **Alignment.**  The host and the device planes of one trace are not on
  one clock: a program the device sat idle for appears to start about a
  millisecond before the host enqueued it.  Each program execution
  (``XLA Modules``, stat ``run_id``) is paired with the host event that
  enqueued it (``DoEnqueueProgram``, stats ``device_ordinal`` and
  ``run_id``; it follows ``TpuLoadedExecutable::ExecuteLaunch``).  The
  shift put on host times for a device is the largest that leaves no
  program starting before its enqueue: min(program start - enqueue start).
* **Gap names.**  Each idle gap of a device is named by the span that
  covers most of it after alignment; at each instant the innermost span
  counts (the one that began last), and ``bench.engine_run`` names
  nothing, as it holds every engine phase.  Where no span covers a gap, it
  keeps ``trace_reduce``'s name, the program that runs next.
* **Per-step host time.**  ``engine_host_ms_per_step``: the median, over
  the intervals between consecutive ``engine.sync`` spans, of the summed
  ``engine.bookkeeping``, ``engine.upload``, ``engine.admit`` and
  ``engine.decode_dispatch`` time in them, less the ``engine.hook`` time
  (the harness's) inside.  ``admit_wait_p90_ms``: the 90th percentile of
  ``engine.admit``'s ``queue_wait_ms``.
* **The harness's stamp.**  ``bench.hook``'s start (where the harness
  stamps a step done) minus the end of the ``engine.sync`` before it, for
  steps with and without an admission between them.

Run a cell with the tracer installed (the same run as ``bench/run.py``,
whose result line it prints first), then the summary of its trace:

    python3 bench/engine_trace.py --workload <cell> --seed <n> \\
        --seconds <s> --trace 1 [--dump <file.json>]

With ``--trace 0`` it is ``bench/run.py`` with the tracer installed: set
beside ``bench/run.py``'s run of the same seed, it measures what the
tracer costs with the profiler off.  ``--summarize <file.json>`` reads a
dump again.
"""
from __future__ import annotations

import json
import os
import sys
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace_reduce  # noqa: E402

ENQUEUE = "DoEnqueueProgram"
CONTAINER = "bench.engine_run"     # holds every engine phase: names nothing
HOST_MS_SPANS = ("engine.bookkeeping", "engine.upload", "engine.admit",
                 "engine.decode_dispatch")


@dataclass
class HostSpan:
    name: str
    start: float              # seconds, the trace's host clock
    end: float
    stats: Dict = field(default_factory=dict)


@dataclass
class Timeline:
    """What the analysis reads of one trace: device busy intervals and
    program executions (device clock, seconds), the engine's and the
    harness's host spans (host clock), and per device the (program start,
    enqueue start) pairs that align the two."""
    window: Tuple[float, float]
    busy: Dict[int, List[Tuple[float, float]]]
    runs: Dict[int, List[Tuple[str, float, float]]]
    spans: List[HostSpan]
    pairs: Dict[int, List[Tuple[float, float]]]

    def to_json(self) -> str:
        return json.dumps({"window": self.window, "busy": self.busy,
                           "runs": self.runs, "pairs": self.pairs,
                           "spans": [asdict(s) for s in self.spans]})

    @classmethod
    def from_json(cls, text: str) -> "Timeline":
        d = json.loads(text)

        def keyed(m):
            return {int(k): [tuple(x) for x in v] for k, v in m.items()}
        return cls(tuple(d["window"]), keyed(d["busy"]), keyed(d["runs"]),
                   [HostSpan(**s) for s in d["spans"]], keyed(d["pairs"]))


def _stats(event) -> Dict:
    return {k: v for k, v in event.stats}


def host_spans(planes) -> List[HostSpan]:
    """The ``engine.*`` and ``bench.*`` events of a profile's host planes,
    by start."""
    out = [HostSpan(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9, _stats(e))
           for plane in planes
           if trace_reduce._device_index(plane.name) is None
           for ln in plane.lines for e in ln.events
           if e.name.startswith(("engine.", "bench."))]
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def launch_pairs(planes) -> Dict[int, List[Tuple[float, float]]]:
    """Per device index, (program start, enqueue start) of each program
    execution whose enqueue the host planes hold, matched by run id."""
    enqueued: Dict[Tuple[int, int], float] = {}
    starts: Dict[int, Dict[int, float]] = {}
    for plane in planes:
        idx = trace_reduce._device_index(plane.name)
        for ln in plane.lines:
            if idx is not None and ln.name == trace_reduce.MODULES_LINE:
                starts[idx] = {}
                for e in ln.events:
                    st = _stats(e)
                    if "run_id" in st:
                        starts[idx][int(st["run_id"])] = e.start_ns * 1e-9
            elif idx is None:
                for e in ln.events:
                    if e.name != ENQUEUE:
                        continue
                    st = _stats(e)
                    if "run_id" in st and "device_ordinal" in st:
                        key = (int(st["device_ordinal"]), int(st["run_id"]))
                        t = e.start_ns * 1e-9
                        enqueued[key] = min(t, enqueued.get(key, t))
    return {i: [(t, enqueued[(i, r)]) for r, t in sorted(runs.items())
                if (i, r) in enqueued]
            for i, runs in starts.items()}


def timeline(planes, chips: int, length: Optional[float] = None,
             red: Optional[trace_reduce.Reduced] = None) -> Timeline:
    """A profile's planes (``ProfileData.planes``) read over the window
    that ``trace_reduce.reduce_planes`` takes; ``red``, when given, is
    that reduction already made."""
    planes = list(planes)
    if red is None:
        red = trace_reduce.reduce_planes(planes, chips, length)
    pairs = launch_pairs(planes)
    n = len(red.devices)
    return Timeline(red.window,
                    {i: d.busy() for i, d in enumerate(red.devices)},
                    {i: list(d.runs) for i, d in enumerate(red.devices)},
                    host_spans(planes),
                    {i: p for i, p in pairs.items() if i < n})


def load(path: str, chips: int, length: Optional[float] = None
         ) -> Timeline:
    from jax.profiler import ProfileData
    return timeline(ProfileData.from_file(path).planes, chips, length)


def shift(tl: Timeline, dev: int = 0) -> Optional[float]:
    """Seconds added to host times to put them on ``dev``'s clock: the
    largest shift after which no program starts before its enqueue.  None
    where no program could be paired."""
    p = tl.pairs.get(dev)
    if not p:
        return None
    return float(min(d - h for d, h in p))


def gaps(tl: Timeline, dev: int = 0) -> List[Tuple[float, float]]:
    """Idle intervals of one device inside the window (as
    ``trace_reduce.Reduced.gaps``)."""
    out, cur = [], tl.window[0]
    for s, e in tl.busy[dev]:
        if s > cur:
            out.append((cur, min(s, tl.window[1])))
        cur = max(cur, e)
    if cur < tl.window[1]:
        out.append((cur, tl.window[1]))
    return [(s, e) for s, e in out if e > s]


def covered(spans: List[HostSpan], lo: float, hi: float, offset: float
            ) -> Dict[str, float]:
    """Seconds of [lo, hi) (device clock) under each span name, the
    innermost span (the one that began last, or of two that began
    together the one that ends first) counting at each instant;
    ``CONTAINER`` names nothing."""
    inside = [s for s in spans if s.name != CONTAINER
              and s.start + offset < hi and s.end + offset > lo]
    cuts = sorted({lo, hi} | {min(max(t + offset, lo), hi) for s in inside
                              for t in (s.start, s.end)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2 - offset
        over = [s for s in inside if s.start <= mid < s.end]
        if over:
            name = max(over, key=lambda s: (s.start, -s.end)).name
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def _next_program(tl: Timeline, dev: int, t: float) -> str:
    later = [(s, label) for label, s, _ in tl.runs.get(dev, []) if s >= t]
    return min(later)[1] if later else "none"


def named_gaps(tl: Timeline, dev: int = 0) -> Tuple[List, float]:
    """Each idle gap as [name, seconds, start], longest first, and the
    share of the idle time under a named span."""
    off = shift(tl, dev) or 0.0
    bare = trace_reduce.Reduced([], tl.window, [])   # names by next program
    out, named, idle = [], 0.0, 0.0
    for s, e in gaps(tl, dev):
        cov = covered(tl.spans, s, e, off)
        idle += e - s
        named += sum(cov.values())
        name = (max(cov, key=cov.get) if cov
                else bare.host_activity(e, _next_program(tl, dev, e)))
        out.append([name, e - s, s])
    out.sort(key=lambda g: -g[1])
    return out, (named / idle if idle else 1.0)


def _of(tl: Timeline, name: str) -> List[HostSpan]:
    return [s for s in tl.spans if s.name == name]


def _within(tl: Timeline, names, lo: float, hi: float) -> List[HostSpan]:
    return [s for s in tl.spans
            if s.name in names and lo <= s.start and s.end <= hi]


def host_ms_per_step(tl: Timeline) -> List[float]:
    """The engine's host milliseconds in each interval between consecutive
    ``engine.sync`` spans (module docstring)."""
    syncs = _of(tl, "engine.sync")
    out = []
    for a, b in zip(syncs, syncs[1:]):
        busy = sum(s.end - s.start for s in _within(tl, HOST_MS_SPANS,
                                                    a.end, b.start))
        hook = sum(s.end - s.start for s in _within(tl, ("engine.hook",),
                                                    a.end, b.start))
        out.append((busy - hook) * 1e3)
    return out


def admit_waits_ms(tl: Timeline) -> List[float]:
    return [float(s.stats["queue_wait_ms"]) for s in _of(tl, "engine.admit")
            if "queue_wait_ms" in s.stats]


def hook_after_sync_ms(tl: Timeline) -> Dict[str, List[float]]:
    """``bench.hook``'s start minus the end of the ``engine.sync`` before
    it, per step, split by whether an admission ran between the two.  A
    sync with no hook after it in the same ``run()`` (the run's last step)
    counts in neither."""
    out: Dict[str, List[float]] = {"stalled": [], "unstalled": []}
    hooks = _of(tl, "bench.hook")
    starts = np.array([h.start for h in hooks])
    syncs = _of(tl, "engine.sync")
    for k, sy in enumerate(syncs):
        i = int(np.searchsorted(starts, sy.end))
        if i == starts.size:
            continue
        h = hooks[i]
        if ((k + 1 < len(syncs) and syncs[k + 1].start < h.start)
                or _within(tl, ("engine.init",), sy.end, h.start)):
            continue
        stalled = bool(_within(tl, ("engine.admit",), sy.end, h.start))
        out["stalled" if stalled else "unstalled"].append(
            (h.start - sy.end) * 1e3)
    return out


def _pct(v: List[float], q: float) -> Optional[float]:
    return float(np.percentile(v, q)) if v else None


def summary(tl: Timeline, top: int = 10) -> Dict:
    """What a traced run reports of the engine's spans."""
    per_dev = {}
    for dev in sorted(tl.busy):
        g, share = named_gaps(tl, dev)
        s = shift(tl, dev)
        per_dev[dev] = {
            "host_shift_ms": None if s is None else s * 1e3,
            "pairs": len(tl.pairs.get(dev, [])),
            "named_idle_share": share,
            "idle_gaps": [[n, float(t)] for n, t, _ in g[:top]]}
    hook = hook_after_sync_ms(tl)
    return {
        "engine_host_ms_per_step": _pct(host_ms_per_step(tl), 50),
        "admit_wait_p90_ms": _pct(admit_waits_ms(tl), 90),
        "hook_after_sync_ms": {
            k: {"n": len(v), "p50": _pct(v, 50), "p98": _pct(v, 98)}
            for k, v in hook.items()},
        "spans": {n: len(_of(tl, n)) for n in sorted({s.name
                                                      for s in tl.spans})},
        "devices": per_dev}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--dump", help="write the trace's Timeline here")
    ap.add_argument("--summarize", help="summarize a Timeline dump")
    args = ap.parse_args(argv)
    if args.summarize:
        with open(args.summarize) as f:
            print(json.dumps(summary(Timeline.from_json(f.read()))))
        return 0

    from bench import registry, run
    from repro.obs import trace as obs_trace

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    cell = registry.cell(args.workload)
    read: Dict[str, Timeline] = {}
    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_read(trace_dir, chips, length):
        """The run's own reduction, and this module's reading of the same
        file before the run removes it."""
        import glob
        from jax.profiler import ProfileData
        red = reduce_dir(trace_dir, chips, length)
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        read["tl"] = timeline(ProfileData.from_file(path).planes, chips,
                              red=red)
        return red

    def install(engine) -> None:
        """Before warm-up: the tracer is in place for every ``run()``."""
        obs_trace.set_tracer(obs_trace.Tracer(profiler=True))

    trace_reduce.reduce_dir = reduce_and_read
    try:
        result = run.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), prepare_engine=install,
                              log=log)
    except run.NoAccelerator as e:
        log(f"bench: {e}")
        return 2
    finally:
        trace_reduce.reduce_dir = reduce_dir
        obs_trace.set_tracer(None)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    if "tl" in read:
        if args.dump:
            os.makedirs(os.path.dirname(os.path.abspath(args.dump)),
                        exist_ok=True)
            with open(args.dump, "w") as f:
                f.write(read["tl"].to_json())
        print(json.dumps({"engine_trace": summary(read["tl"])}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
