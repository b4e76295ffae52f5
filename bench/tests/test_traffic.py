"""Traffic: a seed fixes the inputs; seeds share the sizes and arrival
times and differ in topics and tokens."""
import json
import os

import numpy as np

from bench.traffic import generator

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = json.load(open(os.path.join(HERE, "..", "traffic", "olmoe-decode.json")))


def _sched(seed, n=40):
    return generator.schedule(TRAFFIC, seed, 50304, n, 0.2)


def test_same_seed_same_traffic():
    a, b = _sched(2 ** 33 + 5), _sched(2 ** 33 + 5)
    assert [r["arrival_s"] for r in a] == [r["arrival_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) and
               x["max_new"] == y["max_new"] for x, y in zip(a, b))


def test_seeds_share_sizes_and_arrivals_and_differ_in_tokens():
    a, b = _sched(1), _sched(2 ** 33 + 2)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert [r["arrival_s"] for r in a] == [r["arrival_s"] for r in b]
    assert not all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    # a spread order, not sorted: short and long requests interleave
    lens = [len(r["prompt"]) for r in a]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)


def test_every_block_of_four_holds_one_size_from_each_quarter():
    s = _sched(9, n=40)
    outs = np.array([r["max_new"] for r in s])
    quarter = np.searchsorted(np.sort(outs)[[10, 20, 30]], outs, side="right")
    for i in range(0, 40, 4):
        assert sorted(quarter[i:i + 4]) == [0, 1, 2, 3]


def test_sizes_within_the_mix():
    s = _sched(3, n=200)
    lens = np.array([len(r["prompt"]) for r in s])
    outs = np.array([r["max_new"] for r in s])
    assert lens.min() >= 32 and lens.max() <= 256 and np.all(lens % 16 == 0)
    assert outs.min() >= 32 and outs.max() <= 128
    assert max(len(r["prompt"]) + r["max_new"] for r in s) <= generator.max_seq(TRAFFIC)
    # log-uniform: as many outputs in [32, 64) as in [64, 128]
    assert abs(np.sum(outs < 64) - np.sum(outs >= 64)) <= 4
    gaps = np.diff([r["arrival_s"] for r in s])
    assert abs(gaps.mean() - 1 / 0.2) < 0.1 / 0.2


def test_saturated_schedule_arrives_at_once():
    s = generator.schedule(TRAFFIC, 4, 50304, 10, float("inf"))
    assert all(r["arrival_s"] == 0.0 for r in s)



def test_arrivals_track_the_offered_rate_through_the_window():
    rate = 0.2
    n = generator.n_requests(TRAFFIC, 51, rate)
    span = TRAFFIC["ramp_s"] + 51
    steady = np.arange(n) / rate
    t = np.array([r["arrival_s"] for r in generator.schedule(TRAFFIC, 7, 50304, n, rate)])
    assert np.max(np.abs(t - steady)[t < span]) < 4 / rate
    in_window = np.sum((t >= TRAFFIC["ramp_s"]) & (t < span))
    assert abs(in_window - 51 * rate) <= 0.3 * 51 * rate
