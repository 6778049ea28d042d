"""The traffic generator: the same seed gives the same work, every seed
the same amount of it, and the mix is what the traffic file says."""
import json
from collections import Counter

import numpy as np
import pytest

from conftest import BENCH

from bench.load import (arrival_times, rate_segments, serve_schedule,
                        train_rows)

TRAFFIC = {p.stem: json.loads(p.read_text())
           for p in (BENCH / "traffic").glob("*.json")}
SERVE = [k for k, v in TRAFFIC.items() if v["driver"] == "serve"]


@pytest.mark.parametrize("mix", SERVE)
def test_same_seed_same_schedule(mix):
    a = serve_schedule(TRAFFIC[mix], 45.0, 2 ** 33 + 1, 1000)
    b = serve_schedule(TRAFFIC[mix], 45.0, 2 ** 33 + 1, 1000)
    assert [(x.due_s, x.max_new_tokens) for x in a] == \
        [(x.due_s, x.max_new_tokens) for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


@pytest.mark.parametrize("mix", SERVE)
def test_a_schedule_seed_fixes_the_trace(mix):
    """With the mix's `schedule_seed`, seeds differ in token ids only."""
    t = dict(TRAFFIC[mix], schedule_seed=5)
    a = serve_schedule(t, 45.0, 11, 1000)
    b = serve_schedule(t, 45.0, 3_000_000_019, 1000)
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))


@pytest.mark.parametrize("mix", SERVE)
def test_every_seed_gets_the_same_work(mix):
    """Two schedule seeds give the same work in another order: the same
    sizes, the same number of arrivals, other gaps between them."""
    a = serve_schedule(dict(TRAFFIC[mix], schedule_seed=11), 45.0, 7, 1000)
    b = serve_schedule(dict(TRAFFIC[mix], schedule_seed=3_000_000_019),
                       45.0, 7, 1000)
    assert len(a) == len(b) > 0
    assert Counter(len(x.prompt) for x in a) == \
        Counter(len(x.prompt) for x in b)
    assert Counter(x.max_new_tokens for x in a) == \
        Counter(x.max_new_tokens for x in b)
    assert [x.due_s for x in a] != [x.due_s for x in b]


@pytest.mark.parametrize("mix", SERVE)
def test_mix_follows_the_file(mix):
    t = TRAFFIC[mix]
    s = serve_schedule(t, 45.0, 5, 1000)
    n = len(s)
    expect = sum(round(r * (e - b)) for b, e, r in rate_segments(t, 45.0))
    assert n == expect
    w = np.asarray(t["prompt_weights"]) / sum(t["prompt_weights"])
    got = Counter(len(x.prompt) for x in s)
    for length, share in zip(t["prompt_lens"], w):
        assert abs(got[length] - share * n) <= 1
    assert all(0.0 <= x.due_s < 45.0 for x in s)
    assert all(a.due_s <= b.due_s for a, b in zip(s, s[1:]))


def test_bursts_hold_their_rate():
    t = dict(rate_hz=2.0, bursts=dict(start_s=2.0, every_s=8.0,
                                      length_s=2.0, rate_hz=20.0))
    segs = rate_segments(t, 20.0)
    assert segs[:3] == [(0.0, 2.0, 2.0), (2.0, 4.0, 20.0), (4.0, 10.0, 2.0)]
    times = arrival_times(t, 20.0, np.random.default_rng(0))
    in_burst = ((times >= 2) & (times < 4)).sum()
    assert in_burst == 40


def test_train_rows_differ_by_step_and_repeat_by_seed():
    t = TRAFFIC["graph-2k"]
    a = train_rows(t, 50304, 2 ** 40, 0)
    assert a.shape == (t["batch"], t["seq_len"]) and a.dtype == np.int32
    assert (a == train_rows(t, 50304, 2 ** 40, 0)).all()
    assert not (a == train_rows(t, 50304, 2 ** 40, 1)).all()
    assert 1 <= a.min() and a.max() < 50304
