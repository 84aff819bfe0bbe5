"""The traffic generator: repeatable per seed, the same work for every
seed with other prompt ids, lengths that follow the traffic file's
bounded Pareto."""
import json
import random
from collections import Counter
from pathlib import Path

import pytest

import gen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def load(mix):
    return json.loads((TRAFFIC / f"{mix}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    t = load(mix)
    a = gen.requests(t, 2**31 + 12345, 10.0, 50000)
    b = gen.requests(t, 2**31 + 12345, 10.0, 50000)
    assert a == b


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_reorder_the_same_work(mix):
    # every seed sends the same lengths at the same times; only the
    # prompt ids differ
    t = load(mix)
    a = gen.requests(t, 7, 10.0, 50000)
    b = gen.requests(t, 8, 10.0, 50000)
    assert len(a) == len(b) > 0
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert ([(len(r.prompt), r.max_new) for r in a]
            == [(len(r.prompt), r.max_new) for r in b])
    assert max(r.arrival_s for r in a) <= 10.0 + 1e-9
    assert all(x.arrival_s <= y.arrival_s for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("mix", MIXES)
def test_base_seed_draws_another_path(mix):
    t = load(mix)
    a = gen.requests(t, 7, 10.0, 50000)
    b = gen.requests(dict(t, base_seed=t["base_seed"] + 100), 7, 10.0,
                     50000)
    assert [r.arrival_s for r in a] != [r.arrival_s for r in b]


def test_large_seeds_differ():
    t = load(MIXES[0])
    a = gen.requests(t, 5, 5.0, 50000)
    b = gen.requests(t, 5 + 2**32, 5.0, 50000)
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("lo,hi", [(128, 512), (16, 128), (32, 256),
                                   (32, 128), (256, 2048)])
def test_pareto_mean(lo, hi):
    xs = gen.bounded_pareto(40000, lo=lo, hi=hi, alpha=1.6,
                            rng=random.Random(3))
    assert min(xs) >= lo and max(xs) <= hi
    mean = sum(xs) / len(xs)
    assert mean == pytest.approx(gen.bounded_pareto_mean(lo, hi, 1.6),
                                 rel=0.03)


def test_described_means():
    # the means BENCHMARK.json's cells are described by
    assert gen.bounded_pareto_mean(128, 512, 1.6) == pytest.approx(216, 0.01)
    assert gen.bounded_pareto_mean(16, 128, 1.6) == pytest.approx(31.5, 0.01)
    assert gen.bounded_pareto_mean(32, 256, 1.6) == pytest.approx(63, 0.01)
    assert gen.bounded_pareto_mean(32, 128, 1.6) == pytest.approx(54, 0.01)


@pytest.mark.parametrize("mix", MIXES)
def test_rate_matches_file(mix):
    t = load(mix)
    n = len(gen.arrivals(t, t["rate_per_s"], 400.0))
    assert n / 400.0 == pytest.approx(t["rate_per_s"], rel=0.15)


def test_mmpp_is_bursty():
    t = {"arrival": "mmpp", "base_seed": 4,
         "mmpp": {"modulation": [0.25, 4.0], "dwell_s": [0.5, 0.125]}}
    times = gen.arrivals(t, 10.0, 200.0)
    win = 0.25
    counts = Counter(int(x / win) for x in times)
    c = [counts.get(i, 0) for i in range(int(200 / win))]
    mean = sum(c) / len(c)
    var = sum((x - mean) ** 2 for x in c) / len(c)
    assert var / mean > 2.0          # Poisson counts give about 1


def test_max_seq_fits_the_longest():
    for mix in MIXES:
        t = load(mix)
        rs = gen.requests(t, 1, 10.0, 50000)
        assert all(len(r.prompt) + r.max_new <= gen.max_seq(t) for r in rs)
