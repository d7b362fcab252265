"""End-to-end arithmetic over stamped steps."""
import numpy as np
import pytest

from bench.stats import Served, Step, end_to_end, percentile


def _run(durations, n_req=10, admits_at=()):
    """n_req requests all admitted at step 0 and decoding every step;
    step k completes at sum(durations[:k + 1]).  Returns (served, steps)."""
    done = np.cumsum(durations)
    steps = [Step(float(d), int(k in admits_at)) for k, d in enumerate(done)]
    served = []
    for i in range(n_req):
        n = len(durations) + 1                 # prefill token + one per step
        idx = np.maximum(np.arange(n) - 1, 0)
        served.append(Served(i, due=0.0, prompt_len=8, output_len=n,
                             phase="fill", admitted=0.0,
                             tokens=np.zeros(n, np.int32),
                             delivered=done[idx], steps=idx.tolist(),
                             finished=True))
    return served, steps


def test_percentile_is_over_all_gaps():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(101)), 95) == pytest.approx(95)


def test_steady_run():
    served, steps = _run([0.1] * 200)
    m = end_to_end(served, steps, 2.05, 12.05)
    assert m["metrics"]["itl_p50_ms"] == pytest.approx(100.0)
    assert m["metrics"]["itl_p98_ms"] == pytest.approx(100.0)
    assert m["diag"]["tokens"] / m["diag"]["window_s"] == pytest.approx(100.0)
    assert m["diag"]["steps"] == 100 and m["diag"]["stalled_steps"] == 0


def test_stalls_move_throughput_and_the_tail():
    base = [0.1] * 200
    stalled = list(base)
    stall_steps = list(range(30, 90, 6))       # 10 stalls in the window
    for j, k in enumerate(stall_steps):
        stalled[k] = 0.2 + 0.02 * j
    ref = end_to_end(*_run(base), 2.05, 12.05)
    a = ref["metrics"]
    served, steps = _run(stalled, admits_at=stall_steps)
    res = end_to_end(served, steps, 2.05, 12.05)
    b, diag = res["metrics"], res["diag"]
    assert diag["tokens"] < ref["diag"]["tokens"]
    assert b["itl_p98_ms"] > a["itl_p98_ms"]
    assert b["itl_p50_ms"] == pytest.approx(a["itl_p50_ms"])
    # About 11% of the steps stalled, so the tail falls among the stalls.
    assert diag["stalled_steps"] == 10
    assert 0 < diag["steps_beyond_tail"] < 10
    assert diag["stalled_beyond_tail"] == diag["steps_beyond_tail"]
    assert diag["tail_thin"]


@pytest.mark.parametrize("n_stalls,thin", [(8, True), (60, False)])
def test_a_thin_tail_is_flagged(n_stalls, thin):
    """600 steps in the window, 10 gaps each: p98 leaves 120 gaps, 12 steps
    of stalls beyond it when there are that many; with 8 stalls, 8."""
    durations = [0.1] * 620
    stall_steps = list(range(30, 30 + 9 * n_stalls, 9))
    for j, k in enumerate(stall_steps):
        durations[k] = 0.2 + 0.001 * j
    served, steps = _run(durations, admits_at=stall_steps)
    done = np.cumsum(durations)
    diag = end_to_end(served, steps, done[19] + 0.01, done[-1])["diag"]
    assert diag["steps"] == 600 and diag["stalled_steps"] == n_stalls
    assert diag["tail_thin"] == thin
    assert (diag["steps_beyond_tail"] >= 10) != thin
    # Each stalled step holds ten gaps, all beyond the tail.
    assert diag["gaps_beyond_tail"] == 10 * diag["steps_beyond_tail"]


def test_only_the_window_counts():
    served, steps = _run([0.1] * 200)
    # A stall before the window opens changes nothing inside it.
    early = [0.1] * 200
    early[5] = 3.0
    s2, st2 = _run(early)
    a = end_to_end(served, steps, 5.05, 15.05)["metrics"]
    b = end_to_end(s2, st2, 5.05 + 2.9, 15.05 + 2.9)["metrics"]
    for k in ("itl_p50_ms", "itl_p98_ms"):
        assert a[k] == pytest.approx(b[k])


def test_ttft_of_requests_due_in_the_window():
    served, steps = _run([0.1] * 50, n_req=2)
    late = Served(9, due=3.0, prompt_len=8, output_len=4, phase="window")
    on_time = Served(10, due=1.0, prompt_len=8, output_len=4,
                     phase="window", delivered=np.array([1.25, 1.25, 1.35]),
                     steps=[0, 0, 1], finished=False)
    m = end_to_end(served + [late, on_time], steps, 0.5, 4.0)
    # 250 ms for the one served; the unserved one counts close - due.
    assert m["diag"]["requests_due"] == 2
    assert m["diag"]["requests_unserved_at_close"] == 1
    assert m["metrics"]["ttft_p50_ms"] == pytest.approx((250 + 1000) / 2)
