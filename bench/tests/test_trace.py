"""The trace reduction on a small recorded trace: 30 ms of a TPU v5e
trace of the yi-9b-24l cell (one paged decode program running, cut out
of a recorded ``.xplane.pb`` into ``data/paged_step_excerpt.textproto``
with the benchmark's window span set around it)."""

import os

import numpy as np
import pytest

import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "paged_step_excerpt.textproto")
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        return T.reduce(ProfileData.from_text_proto(f.read()))


def brute_busy(events, lo, hi):
    """Busy time by marking a 1 ns timeline, independently of merged()."""
    t = np.zeros(int(hi - lo) + 1, bool)
    for e in events:
        t[int(max(e.start, lo) - lo):int(min(e.end, hi) - lo)] = True
    return t.sum() * 1e-9


def test_window_and_busy(tr):
    assert tr.n_devices == 1
    assert tr.window_s == pytest.approx(0.03, rel=1e-6)
    busy = tr.mean_busy_s()
    assert 0 < busy <= tr.window_s
    assert busy == pytest.approx(brute_busy(tr.ops[0], *tr.window),
                                 abs=2e-9 * len(tr.ops[0]))


def test_ops_are_attributed_to_their_program(tr):
    mods = {e.module.split("(")[0] for e in tr.ops[0]}
    assert "jit_paged_step" in mods
    kern = [e for e in tr.ops[0] if KERNEL in e.name]
    assert kern and all(e.module.startswith("jit_paged_step") for e in kern)
    secs = tr.op_seconds(lambda e: KERNEL in e.name)
    assert secs == pytest.approx(sum(e.end - e.start for e in kern) * 1e-9)


def test_top_ops_and_idle_gaps(tr):
    top = tr.top_ops()
    assert 0 < len(top) <= 10
    assert all(not k.split("/", 1)[1].startswith("%while") for k, _ in top)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    gaps = tr.idle_gaps()
    assert len(gaps) <= 10
    idle = tr.window_s - tr.mean_busy_s()
    assert sum(v for _, v in gaps) <= idle + 1e-12
    assert all(name.startswith("bench.") or name == "no span"
               for name, _ in gaps)


def test_merged_intervals():
    ev = [T.Event("a", 0, 5), T.Event("b", 3, 8), T.Event("c", 10, 12),
          T.Event("d", 11, 11.5)]
    assert T.merged(ev) == [(0, 8), (10, 12)]
