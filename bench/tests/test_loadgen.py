"""The chat generator: stratified draws, shuffled by the seed."""
from collections import Counter

import numpy as np
import pytest

from bench import loadgen, registry

SEEDS = (0, 1, 2 ** 31 + 12345, 9_000_000_001)


@pytest.fixture(scope="module")
def chat():
    """The chat mix at the rate of the cell that runs it on one chip."""
    return registry.cell("phi4-mini.chat").traffic


def _window(mix, seed, seconds=45.0):
    return [r for r in loadgen.schedule(mix, seed, seconds)
            if r.phase == "window"]


def test_same_multiset_for_every_seed_in_another_order(chat):
    ref = _window(chat, SEEDS[0])
    for seed in SEEDS[1:]:
        got = _window(chat, seed)
        assert Counter(r.prompt_len for r in got) == Counter(
            r.prompt_len for r in ref)
        assert Counter(r.output_len for r in got) == Counter(
            r.output_len for r in ref)
        gaps = np.diff([chat["fill_seconds"]] + [r.due_s for r in got])
        ref_gaps = np.diff([chat["fill_seconds"]] + [r.due_s for r in ref])
        assert np.allclose(np.sort(gaps), np.sort(ref_gaps))
        assert [r.prompt_len for r in got] != [r.prompt_len for r in ref]


def test_same_seed_same_schedule_and_tokens(chat):
    a = loadgen.schedule(chat, 77, 30.0)
    assert a == loadgen.schedule(chat, 77, 30.0)
    assert np.array_equal(loadgen.prompt_tokens(77, 3, 100, 1000),
                          loadgen.prompt_tokens(77, 3, 100, 1000))
    assert not np.array_equal(loadgen.prompt_tokens(77, 3, 100, 1000),
                              loadgen.prompt_tokens(78, 3, 100, 1000))


@pytest.mark.parametrize("seconds", [10.0, 45.0, 51.0])
def test_rate_and_window(chat, seconds):
    rate = chat["rate_per_s"]
    win = _window(chat, 5, seconds)
    fill = [r for r in loadgen.schedule(chat, 5, seconds)
            if r.phase == "fill"]
    assert len(win) == int(rate * seconds)
    assert len(fill) == int(rate * chat["fill_seconds"])
    # The mid-quantile gaps sum to just under n / rate: every arrival of
    # the window set falls inside the window.
    span = win[-1].due_s - chat["fill_seconds"]
    assert 0.85 * len(win) / rate < span <= len(win) / rate
    assert all(r.due_s < chat["fill_seconds"] for r in fill)
    assert all(chat["fill_seconds"] < r.due_s
               <= chat["fill_seconds"] + seconds for r in win)


def test_lengths_clipped_and_centred(chat):
    for key in ("prompt_tokens", "output_tokens"):
        spec = chat[key]
        x = loadgen.lengths(spec, 400)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert x.min() == spec["min"] and x.max() == spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 1
        assert np.all(np.diff(x) >= 0)


def test_all_lengths_is_what_a_run_offers(chat):
    prompts, max_out = loadgen.all_lengths(chat, 45.0)
    sched = loadgen.schedule(chat, 11, 45.0)
    assert Counter(prompts) == Counter(r.prompt_len for r in sched)
    assert max_out == max(r.output_len for r in sched)


def test_refuses_what_it_cannot_generate(chat):
    with pytest.raises(ValueError):
        loadgen.schedule(dict(chat, loop="closed"), 0, 10.0)
    with pytest.raises(ValueError):
        loadgen.lengths(dict(chat["prompt_tokens"], dist="zipf"), 4)
    with pytest.raises(ValueError):
        loadgen.gaps(0.0, 4)
