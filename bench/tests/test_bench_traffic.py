"""The traffic generator: reproducible from the seed, the same work for
every seed."""
import os
import sys
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.traffic import ClosedLoop, OpenLoop, exact_counts  # noqa: E402

SWITCH = {"loop": "open", "rate_rps": 5.0, "phase_s": 8.0,
          "hot_share": 0.85, "prompt_lens": [64, 320],
          "prompt_shares": [0.8, 0.2], "max_new": 32}
CLOSED = {"loop": "closed", "clients_per_tenant": 4,
          "prompt_lens": [64, 320], "prompt_shares": [0.8, 0.2],
          "max_new": 32}
VOCAB = {"a": 50280, "b": 49155}
BIG_SEED = 2 ** 33 + 12345


def _sig(src):
    return [(a.due_ms, a.app, a.prompt.tobytes()) for a in src.arrivals]


def test_open_loop_same_seed_same_schedule():
    assert _sig(OpenLoop(SWITCH, VOCAB, BIG_SEED, 40e3)) == \
        _sig(OpenLoop(SWITCH, VOCAB, BIG_SEED, 40e3))
    assert _sig(OpenLoop(SWITCH, VOCAB, BIG_SEED, 40e3)) != \
        _sig(OpenLoop(SWITCH, VOCAB, BIG_SEED + 1, 40e3))


def test_open_loop_every_seed_gets_the_same_work():
    for seed in (1, 2, BIG_SEED):
        src = OpenLoop(SWITCH, VOCAB, seed, 40e3)
        assert len(src.arrivals) == 5 * 40
        lens = Counter(len(a.prompt) for a in src.arrivals)
        assert lens == {64: 160, 320: 40}
        for k in range(5):  # each phase: 34 hot, 6 cold arrivals
            ph = [a for a in src.arrivals if 8e3 * k <= a.due_ms < 8e3 * (k + 1)]
            assert sorted(Counter(a.app for a in ph).values()) == [6, 34]
        assert all(a.prompt.max() < VOCAB[a.app] and a.prompt.min() >= 1
                   for a in src.arrivals)


def test_open_loop_hot_tenant_alternates():
    src = OpenLoop(SWITCH, VOCAB, 7, 40e3)
    hot = [Counter(a.app for a in src.arrivals
                   if 8e3 * k <= a.due_ms < 8e3 * (k + 1)).most_common(1)[0][0]
           for k in range(5)]
    assert all(x != y for x, y in zip(hot, hot[1:]))


def test_open_loop_pop_due_in_order():
    src = OpenLoop(SWITCH, VOCAB, 3, 16e3)
    got = src.pop_due(5e3) + src.pop_due(16e3)
    assert [a.due_ms for a in got] == sorted(a.due_ms for a in src.arrivals)
    assert src.next_due_ms() == float("inf")


def test_closed_loop_reproducible_and_stops_at_span():
    def replay(seed):
        src = ClosedLoop(CLOSED, VOCAB, seed, 1000.0)
        out, t = [], 0.0
        while True:
            due = src.pop_due(t)
            if not due and src.next_due_ms() == float("inf"):
                return out
            for a in due:
                out.append((a.app, a.client, a.prompt.tobytes()))
                src.on_done(a, t + 100.0)
            t += 100.0
    assert replay(BIG_SEED) == replay(BIG_SEED)
    got = replay(BIG_SEED)
    assert len(got) == 8 * 10  # 8 clients, replies every 100 ms for 1 s
    assert Counter(a for a, _, _ in got) == {"a": 40, "b": 40}


def test_exact_counts_largest_remainder():
    assert exact_counts(40, [0.85, 0.15]) == [34, 6]
    assert exact_counts(7, [1, 1, 1]) == [3, 2, 2]
    assert sum(exact_counts(13, [0.8, 0.2])) == 13
    assert np.array_equal(exact_counts(20, [0.8, 0.2]), [16, 4])
