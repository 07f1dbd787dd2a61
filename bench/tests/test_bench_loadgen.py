"""The seeded generator: same seed, same schedule; every seed the same
set of sizes and gaps, in an order and with token ids of its own, spread
evenly over the window."""

import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import loadgen, spec  # noqa: E402

SEED = 2**31 + 77


def chat(rate=1.4):
    return spec.merge(spec.load_mix("chat"),
                      {"arrival": {"rate_per_s": rate}})


def snapshot(s):
    return [(a.rid, a.due_s, a.prompt, a.max_new, a.phase) for a in s.timed]


def test_same_seed_same_schedule():
    a = loadgen.Schedule(chat(), SEED, 51, 151936, 8)
    b = loadgen.Schedule(chat(), SEED, 51, 151936, 8)
    assert snapshot(a) == snapshot(b)
    c = loadgen.Schedule(chat(), SEED + 1, 51, 151936, 8)
    assert snapshot(a) != snapshot(c)


def window(seed, mix=None):
    s = loadgen.Schedule(mix or chat(), seed, 51, 151936, 8)
    return [a for a in s.timed if a.phase == "window"]


def gaps(w):
    """The window's gaps, the one that closes it included."""
    return np.diff([a.due_s for a in w] + [10 + 51])


def test_every_seed_has_the_same_arrivals_and_sizes_and_its_own_tokens():
    w1, w2 = window(1), window(2**33 + 5)
    assert len(w1) == len(w2) == round(1.4 * 51)
    for key in (lambda a: len(a.prompt), lambda a: a.max_new):
        assert sorted(map(key, w1)) == sorted(map(key, w2))
    np.testing.assert_allclose(np.sort(gaps(w1)), np.sort(gaps(w2)),
                               atol=1e-9)
    assert all(a.prompt != b.prompt for a, b in zip(w1, w2))


def test_the_layout_seed_draws_the_order():
    """The run's seed is the layout's seed: another seed, another order
    of the same prompt lengths, output lengths and gaps."""
    def layout(seed):
        w = window(seed)
        return ([len(a.prompt) for a in w], [a.max_new for a in w],
                list(gaps(w)))
    a, b = layout(7), layout(2**31 + 9)
    for x, y in zip(a, b):
        assert x != y
        assert np.allclose(sorted(x), sorted(y))


@pytest.mark.parametrize("seed", [3, 2**31 + 77, 2**40 + 1])
def test_the_order_spreads_work_and_arrivals_evenly(seed):
    """Every quarter of the window holds about a quarter of its arrivals
    and of its prompt tokens, on every seed."""
    w = window(seed)
    lo = min(a.due_s for a in w)
    quarter = [[a for a in w if k <= 4 * (a.due_s - lo) / 51 < k + 1]
               for k in range(4)]
    total = sum(len(a.prompt) for a in w)
    for q in quarter:
        assert abs(len(q) - len(w) / 4) <= 3
        assert abs(sum(len(a.prompt) for a in q) / total - 0.25) < 0.06


def test_spread_order_is_a_permutation():
    for n in (1, 7, 82):
        order = loadgen.spread_order(n, 0.3, loadgen.STEP_GAP)
        assert sorted(order) == list(range(n))


def test_arrivals_fall_in_their_segments_in_order():
    s = loadgen.Schedule(chat(2.0), 5, 30, 1000, 8)
    due = [a.due_s for a in s.timed]
    assert due == sorted(due)
    for a in s.timed:
        lo, hi = {"ramp": (0, 10), "window": (10, 40),
                  "after": (40, 100)}[a.phase]
        assert lo <= a.due_s < hi
    w = [a for a in s.timed if a.phase == "window"]
    gaps = np.diff([a.due_s for a in w])
    assert np.mean(gaps) == pytest.approx(0.5, rel=0.05)


def test_lengths_follow_the_mix_and_its_bounds():
    mix = chat()
    q = loadgen.quantiles(mix["prompt_tokens"], 1001)
    assert q.min() >= 16 and q.max() <= 512
    assert np.median(q) == 128
    s = loadgen.Schedule(mix, 3, 51, 50304, 8)
    assert all(8 <= a.max_new <= 128 for a in s.timed)
    assert all(0 <= t < 50304 for a in s.timed for t in a.prompt)


def test_every_long_arrival_of_the_mixed_mix():
    mix = spec.merge(spec.load_mix("mixed"),
                     {"arrival": {"rate_per_s": 1.1}})
    sizes = set()
    for seed in (11, 12, 2**40 + 3):
        s = loadgen.Schedule(mix, seed, 51, 1000, 8)
        w = [a for a in s.timed if a.phase == "window"]
        longs = [a.rid for a in w if len(a.prompt) >= 1024]
        assert len(longs) == round(len(w) / 20) == 3
        assert all(b - a == 20 for a, b in zip(longs, longs[1:]))
        sizes.add(tuple(sorted(len(a.prompt) for a in w)))
    assert len(sizes) == 1
    assert s.max_tokens() == 1536 + 128


def test_backlog_draws_blocks_of_the_same_sizes():
    s = loadgen.Schedule(spec.load_mix("offline"), 9, 51, 1000, 8)
    assert s.timed == [] and s.waiting_target == 16
    first = [s.next_backlog() for _ in range(loadgen.BACKLOG_BLOCK)]
    second = [s.next_backlog() for _ in range(loadgen.BACKLOG_BLOCK)]
    assert (sorted(len(a.prompt) for a in first)
            == sorted(len(a.prompt) for a in second))
    assert len({a.rid for a in first + second}) == 2 * loadgen.BACKLOG_BLOCK
