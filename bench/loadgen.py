"""One general generator of open-loop request schedules, driven by a traffic
mix's parameters (``bench/traffic/<mix>.json``).

Draws are stratified: the N requests of a set take the N mid-quantiles
``(i + 0.5) / N`` of each length distribution and of the exponential
inter-arrival distribution, and the seed only shuffles their order (each
quantity on its own).  Every seed therefore offers the same multiset of work
at the same mean rate; a seed changes which request comes when, and so the
coincidences, and nothing else.

A schedule has two sets, each drawn this way from its own stream:

* ``fill``: arrivals at the cell's rate during the mix's ``fill_seconds``
  before the window opens, so that the window opens with the slots as full
  as the rate keeps them;
* ``window``: the arrivals due during the measured window.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

_NORMAL = NormalDist()


@dataclass(frozen=True)
class Planned:
    """One request of a schedule; ``due_s`` counts from the start of fill."""
    due_s: float
    prompt_len: int
    output_len: int
    phase: str                    # "fill" | "window"


def mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a clipped length distribution, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([_NORMAL.inv_cdf(q) for q in mid_quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def gaps(rate_per_s: float, n: int) -> np.ndarray:
    """The n mid-quantiles of the exponential inter-arrival time, seconds."""
    if rate_per_s <= 0:
        raise ValueError(f"rate must be positive, got {rate_per_s}")
    return -np.log1p(-mid_quantiles(n)) / rate_per_s


def _draw(mix: Dict, n: int, rng: np.random.Generator
          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    return (rng.permutation(gaps(mix["rate_per_s"], n)),
            rng.permutation(lengths(mix["prompt_tokens"], n)),
            rng.permutation(lengths(mix["output_tokens"], n)))


def set_sizes(mix: Dict, seconds: float) -> Tuple[int, int]:
    """(fill, window) request counts: the rate times each phase's length,
    rounded down, so that a set's arrivals end inside its phase."""
    rate = mix["rate_per_s"]
    return (max(1, int(rate * mix["fill_seconds"])),
            max(1, int(rate * seconds)))


def schedule(mix: Dict, seed: int, seconds: float) -> List[Planned]:
    """The fill set, then the window set, in due order.  The window set's
    arrivals start at ``fill_seconds``."""
    if mix["loop"] != "open" or mix["arrivals"] != "poisson":
        raise ValueError(f"unsupported mix: loop {mix['loop']!r}, "
                         f"arrivals {mix['arrivals']!r}")
    n_fill, n_win = set_sizes(mix, seconds)
    out: List[Planned] = []
    for phase, n, start in (("fill", n_fill, 0.0),
                            ("window", n_win, float(mix["fill_seconds"]))):
        rng = np.random.default_rng([seed, 0 if phase == "fill" else 1])
        g, p, o = _draw(mix, n, rng)
        due = start + np.cumsum(g)
        out += [Planned(float(d), int(a), int(b), phase)
                for d, a, b in zip(due, p, o)]
    return out


def all_lengths(mix: Dict, seconds: float) -> Tuple[List[int], int]:
    """Every prompt length a run of this mix offers (the same multiset for
    every seed), and the largest output length."""
    n_fill, n_win = set_sizes(mix, seconds)
    prompts = (lengths(mix["prompt_tokens"], n_fill).tolist()
               + lengths(mix["prompt_tokens"], n_win).tolist())
    return prompts, int(max(lengths(mix["output_tokens"], n_fill).max(),
                            lengths(mix["output_tokens"], n_win).max()))


def prompt_tokens(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index`` of a run: uniform over the vocabulary,
    from the seed."""
    rng = np.random.default_rng([seed, 2, index])
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)

