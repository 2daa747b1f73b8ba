"""The traffic generator: same seed, same traffic; clips honoured; every
seed draws the same set of sizes."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import gen

MIXES = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("serve_*.json"))
BIG = 2**31 + 977


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_traffic(path):
    mix = json.loads(path.read_text())
    a = gen.ClosedLoop(mix, 1000, BIG).wave(3)
    b = gen.ClosedLoop(mix, 1000, BIG).wave(3)
    assert [(r.prompt, r.max_new) for r in a] == [(r.prompt, r.max_new) for r in b]
    c = gen.ClosedLoop(mix, 1000, BIG + 1).wave(3)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_clips_and_one_set_of_sizes_for_every_seed(path):
    mix = json.loads(path.read_text())
    sets = set()
    for seed in (0, 1, BIG, 2**40 + 3):
        for w in range(3):
            wave = gen.ClosedLoop(mix, 1000, seed).wave(w)
            p = sorted(len(r.prompt) for r in wave)
            a = sorted(r.max_new for r in wave)
            assert len(wave) == mix["clients"]
            assert mix["prompt"]["min"] <= p[0] and p[-1] <= mix["prompt"]["max"]
            assert mix["answer"]["min"] <= a[0] and a[-1] <= mix["answer"]["max"]
            assert all(1 <= t < 1000 for r in wave for t in r.prompt)
            sets.add((tuple(p), tuple(a)))
    assert len(sets) == 1


def test_quantile_lengths_follow_the_median():
    spec = {"median": 100, "sigma": 0.5, "min": 1, "max": 10**6}
    x = gen.quantile_lengths(spec, 101)
    assert x[50] == 100 and np.all(np.diff(x) >= 0)


def test_query_order_cycles_evenly():
    order = gen.query_order(12, BIG, 36)
    assert sorted(order) == sorted(list(range(12)) * 3)
    assert order == gen.query_order(12, BIG, 36)


def test_sample_indices_keeps_the_required_one():
    got = gen.sample_indices(10, 4, BIG, must=7)
    assert got[0] == 7 and len(set(got)) == 4


def test_serve_chat_waves_reach_both_clips():
    """Every wave of ``serve_chat`` holds the clipped longest prompt and
    answer, so each wave is 512 prefill and 383 decode steps."""
    path = Path(__file__).resolve().parents[1] / "traffic/serve_chat.json"
    mix = json.loads(path.read_text())
    for seed in (0, BIG, 2**40 + 3):
        for w in range(3):
            wave = gen.ClosedLoop(mix, 1000, seed).wave(w)
            assert max(len(r.prompt) for r in wave) == mix["prompt"]["max"]
            assert max(r.max_new for r in wave) == mix["answer"]["max"]


def test_sample_indices_differ_by_seed():
    a = gen.sample_indices(1000, 8, BIG, must=0)
    b = gen.sample_indices(1000, 8, BIG + 1, must=0)
    assert a[0] == b[0] == 0 and a[1:] != b[1:]
    assert a == gen.sample_indices(1000, 8, BIG, must=0)
