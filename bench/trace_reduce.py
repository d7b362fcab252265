"""Reduction of a JAX profiler trace (``.xplane.pb``) to device time.

For each device plane (``/device:TPU:<i>``) it reads the operations line
(``XLA Ops``; an event's name is the op's HLO text) and the program line
(``XLA Modules``): every operation is an interval on that device, and
belongs to the program execution whose interval holds it.  Control-flow
ops (``while``, ``conditional``, ``call``) span their bodies and are left
out: busy time is the union of the leaf operations.

An op's kind comes from its HLO text: a ``tpu_custom_call`` with a rank-2
result is the Pallas GEMM, one with a rank-4 result the flash-attention
kernel; all-reduce, all-gather, reduce-scatter, collective-permute and
all-to-all are collectives.  A program execution is labelled by what runs
in it: ``prefill`` if it runs the flash-attention kernel, ``decode`` if it
runs the GEMM and no flash attention, ``other`` otherwise (the slot insert,
sampling, cache set-up).

Host spans the harness writes into the same trace (``bench.hook``,
``bench.wait_arrival``, ``bench.engine_run``) name what the host was doing
in each idle gap.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
CONTROL = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("bench.hook", "bench.wait_arrival", "bench.engine_run")
_HEAD = re.compile(r"^%?([^\s=]+) = \w+\[([\d,]*)\]")


@functools.lru_cache(maxsize=None)
def classify(hlo: str) -> tuple:
    """(stable name, kind) of an op from its HLO text.  Kinds: ``gemm``,
    ``flash``, ``collective``, ``control``, ``op``."""
    m = _HEAD.match(hlo)
    short = stable_name(m.group(1) if m else hlo.split(" ")[0].lstrip("%"))
    if 'custom_call_target="tpu_custom_call"' in hlo and m:
        dims = [d for d in m.group(2).split(",") if d]
        if len(dims) == 2:
            k = re.search(r"custom-call\(\w+\[(\d+),(\d+)\]", hlo)
            kdim = f"x{k.group(2)}" if k else ""
            return f"gemm_{dims[0]}x{dims[1]}{kdim}", "gemm"
        if len(dims) == 4:
            return "flash_attention_" + "x".join(dims), "flash"
        return short, "op"
    if COLLECTIVE.search(short):
        return short, "collective"
    if short in CONTROL:
        return short, "control"
    return short, "op"


def stable_name(op: str) -> str:
    """An op's name without XLA's numeric suffixes (``fusion.12`` ->
    ``fusion``), so that names compare across compiles."""
    return re.sub(r"(\.\d+)+$", "", op)


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length of the union of intervals [starts, ends)."""
    return float(sum(e - s for s, e in merge(starts, ends)))


def merge(starts: Iterable[float], ends: Iterable[float]
          ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(zip(starts, ends)):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
             ) -> float:
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


@dataclass
class Device:
    """One device's operations (seconds on the trace's clock) and the
    program each belongs to."""
    name: np.ndarray          # stable op names
    kind: np.ndarray          # "gemm" | "flash" | "collective" | "op"
    start: np.ndarray
    end: np.ndarray
    program: np.ndarray       # "prefill" | "decode" | "other"
    runs: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def executions(self) -> Dict[str, int]:
        """Program executions by label."""
        out: Dict[str, int] = {}
        for label, _, _ in self.runs:
            out[label] = out.get(label, 0) + 1
        return out

    def busy(self) -> List[Tuple[float, float]]:
        """Merged intervals in which a program ran (its operations, and
        the copies and waits between them), or an operation where the
        trace has no program line."""
        if self.runs:
            return merge([s for _, s, _ in self.runs] + list(self.start),
                         [e for _, _, e in self.runs] + list(self.end))
        return merge(self.start, self.end)

    def time(self, program: Optional[str] = None,
             kind: Optional[str] = None) -> float:
        """Summed duration of the ops that match."""
        sel = np.ones(self.name.size, bool)
        if program is not None:
            sel &= self.program == program
        if kind is not None:
            sel &= self.kind == kind
        return float(np.sum(self.end[sel] - self.start[sel]))

    def program_time(self, program: str) -> float:
        """Device time of one program's executions (the program line's
        intervals: an async copy that feeds a kernel counts)."""
        return sum(e - s for label, s, e in self.runs if label == program)


@dataclass
class Reduced:
    devices: List[Device]
    window: Tuple[float, float]            # trace clock, seconds
    host: List[Tuple[str, float, float]]   # (span, start, end)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return float(np.mean([sum(e - s for s, e in d.busy())
                              for d in self.devices]))

    def gaps(self, dev: int = 0) -> List[Tuple[float, float]]:
        """Idle intervals of one device inside the window."""
        busy = self.devices[dev].busy()
        out, cur = [], self.window[0]
        for s, e in busy:
            if s > cur:
                out.append((cur, min(s, self.window[1])))
            cur = max(cur, e)
        if cur < self.window[1]:
            out.append((cur, self.window[1]))
        return [(s, e) for s, e in out if e > s]

    def host_activity(self, t: float, next_program: str) -> str:
        """What the host was doing at trace time t."""
        inner = None
        for name, s, e in self.host:
            if s <= t <= e and (inner is None or name != "bench.engine_run"):
                inner = name
        if inner == "bench.wait_arrival":
            return "host: waiting for an arrival"
        if inner == "bench.hook":
            return "host: in the harness hook"
        # Inside ``engine.run()`` (a call that began before the trace has no
        # span in it): named by the program that runs next.
        return {"prefill": "host: admitting (prefill next)",
                "decode": "host: dispatching a decode"}.get(
                    next_program, f"host: engine ({next_program} next)")

    def breakdown(self, dev: int = 0, top: int = 10) -> Dict:
        d = self.devices[dev]
        tot: Dict[str, float] = {}
        for n, p, s, e in zip(d.name, d.program, d.start, d.end):
            key = f"{p}:{n}"
            tot[key] = tot.get(key, 0.0) + (e - s)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        order = np.argsort(d.start)
        starts = d.start[order]
        gaps = []
        for s, e in self.gaps(dev):
            i = int(np.searchsorted(starts, e))
            nxt = d.program[order[i]] if i < starts.size else "none"
            gaps.append((self.host_activity((s + e) / 2, nxt), e - s))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[k, float(v)] for k, v in ops],
                "idle_gaps": [[k, float(v)] for k, v in gaps[:top]]}


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _device_index(plane_name: str) -> Optional[int]:
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def _label(kinds: np.ndarray) -> str:
    if np.any(kinds == "flash"):
        return "prefill"
    if np.any(kinds == "gemm"):
        return "decode"
    return "other"


def reduce_planes(planes, chips: int,
                  length: Optional[float] = None) -> Reduced:
    """Reduce the planes of one profile (``ProfileData.planes``).  The
    window starts at the trace's first event and lasts ``length`` seconds
    (what runs after it is cut off), or to its last event."""
    devices: Dict[int, Device] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in planes:
        idx = _device_index(plane.name)
        lines = {ln.name: ln for ln in plane.lines}
        if idx is None:
            for ln in plane.lines:
                for n, s, e in _events(ln):
                    if n in HOST_SPANS:
                        host.append((n, s, e))
            continue
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        named = [(classify(n), s, e) for n, s, e in ops]
        named = [x for x in named if x[0][1] != "control"]
        name = np.array([x[0][0] for x in named], object)
        kind = np.array([x[0][1] for x in named], object)
        start = np.array([x[1] for x in named], float)
        end = np.array([x[2] for x in named], float)
        prog = np.full(name.size, "other", object)
        order = np.argsort(start)
        runs = []
        for _, ms, me in mods:
            lo = np.searchsorted(start[order], ms, side="left")
            hi = np.searchsorted(start[order], me, side="right")
            sel = order[lo:hi]
            label = _label(kind[sel])
            prog[sel] = label
            runs.append((label, ms, me))
        devices[idx] = Device(name, kind, start, end, prog, runs)
    devs = [devices[i] for i in sorted(devices)][:chips]
    if not devs:
        raise ValueError("no TPU device plane in the trace")
    lo = min([float(d.start.min()) for d in devs if d.start.size]
             + [s for _, s, _ in host])
    hi = max([float(d.end.max()) for d in devs if d.end.size]
             + [e for _, _, e in host])
    if length is not None:
        hi = lo + length
        for d in devs:
            keep = d.start < hi
            d.name, d.kind, d.program = d.name[keep], d.kind[keep], \
                d.program[keep]
            d.start, d.end = d.start[keep], np.minimum(d.end[keep], hi)
            d.runs = [(p, s, min(e, hi)) for p, s, e in d.runs if s < hi]
    return Reduced(devs, (lo, hi), host)


def load(path: str, chips: int, length: Optional[float] = None) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, chips, length)


def reduce_dir(trace_dir: str, chips: int, length: float) -> Reduced:
    """The trace that ``jax.profiler.start_trace(trace_dir)`` wrote, over
    its first ``length`` seconds (the host's span from start to stop; the
    profiler's own stop and what follows it are cut off)."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {files}")
    return load(files[0], chips, length)
