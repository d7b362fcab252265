"""Arithmetic the per-layer readers share: which steps and admissions fall
in the traced window, and the work they needed (``bench/work.py``)."""
from __future__ import annotations

from typing import List, Tuple

from bench import work


def steps_in_window(rec) -> List[int]:
    return [k for k, s in enumerate(rec.steps)
            if rec.t_open < s.done <= rec.t_close]


def held_per_step(rec) -> dict:
    """step -> positions attended by each slot active in it (the new token
    included): token i >= 1 of a request is decoded at its step with
    prompt_len + i positions."""
    held: dict = {}
    for r in rec.served:
        for i in range(1, len(r.steps)):
            held.setdefault(r.steps[i], []).append(r.prompt_len + i)
    return held


def decode_work(rec) -> Tuple[work.Work, int]:
    """Summed work of the decode steps in the window, per chip, and how
    many steps that is."""
    held = held_per_step(rec)
    ks = [k for k in steps_in_window(rec) if k in held]
    total = work.Work()
    for k in ks:
        total = total + work.decode_step(rec.dims, held[k])
    return total * (1.0 / rec.chips), len(ks)


def prefills_in_window(rec) -> List[int]:
    """Prompt lengths of the requests admitted in the window."""
    return [r.prompt_len for r in rec.served
            if r.steps and rec.t_open < r.admitted <= rec.t_close]
