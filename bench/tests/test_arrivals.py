"""The traffic generator: the same seed gives the same stream, every
seed gives the same work, and the stream keeps its stated shape."""

import os

import numpy as np
import pytest

import arrivals as A
from conftest import BENCH

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def mix_of(name):
    return A.load(os.path.join(BENCH, "traffic", name + ".json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream(name):
    a = A.open_loop(mix_of(name), 2**31 + 5, 30, 1000)
    b = A.open_loop(mix_of(name), 2**31 + 5, 30, 1000)
    assert [r.rid for r in a] == [r.rid for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               and x.max_new == y.max_new for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_schedule_other_content(name):
    a = A.open_loop(mix_of(name), 1, 30, 1000)
    b = A.open_loop(mix_of(name), 2, 30, 1000)
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in b]
    # the gaps are the fixed quantile set, in the schedule's order
    pool = A.gaps(mix_of(name), len(a))
    d = np.diff([r.due for r in a])
    assert np.abs(d[:, None] - pool[None, :]).min(axis=1).max() < 1e-9
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_stated_distribution(name):
    mix = mix_of(name)
    reqs = A.open_loop(mix, 7, 400, 1000)
    # the rate: about rate x seconds requests, all due inside the window
    assert len(reqs) == round(mix["rate_per_s"] * 400)
    assert 0 == reqs[0].due and reqs[-1].due < 400
    gaps = np.diff([r.due for r in reqs])
    assert np.mean(gaps) == pytest.approx(1 / mix["rate_per_s"], rel=0.05)
    user = np.array([len(r.prompt) for r in reqs])
    grid = A.grid_of(mix["prompt"])
    assert set(user) <= set(grid)
    assert grid[0] >= mix["prompt"]["min"] and grid[-1] <= mix["prompt"]["max"]
    # the median survives snapping to within one grid step
    i = grid.index(int(np.median(user)))
    med = mix["prompt"]["median"]
    assert grid[max(0, i - 1)] <= med <= grid[min(len(grid) - 1, i + 1)]
    out = np.array([r.max_new for r in reqs])
    o = mix["output"]
    assert o["min"] <= out.min() and out.max() <= o["max"]
    assert np.median(out) == pytest.approx(o["median"], rel=0.05)
    assert np.all([(r.prompt >= 0).all() and (r.prompt < 1000).all()
                   for r in reqs])

