"""Every per-layer and end-to-end reader of ``bench/metrics/`` on one
fixed ``Run``: the recorded excerpt's trace (``test_trace.py``) under
made-up rounds and requests of ``yi-9b-24l``. The values were read on
the commit before the trace reduction kept the program's spans, and are
pinned exactly: the readers read the same ops from the same trace."""

import json
import os

import numpy as np
import pytest

import peaks
import run
import trace as T
from conftest import BENCH

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "paged_step_excerpt.textproto")


def fixed_run() -> run.Run:
    from jax.profiler import ProfileData
    with open(DATA) as f:
        tr = T.reduce(ProfileData.from_text_proto(f.read()))
    bench = run.load_json(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json"))
    cell, conf, mix = run.find_cell(bench, "yi-9b-24l.chat-poisson")
    rounds = [run.Round(t=0.25 + 0.16 * i, dur=0.156, queued=i % 3,
                        slots=3 + i, emitted=8 * (3 + i) + (i == 1),
                        prefill_tokens=[192] * (i == 1),
                        decode_rows=list(range(60 * i, 60 * i + 8 * (3 + i))),
                        kept_rows=list(range(60 * i, 60 * i + 7 * (3 + i))),
                        shared_pages=int(i == 1))
              for i in range(3)]
    lives = {f"r{i}": run.Life(due=0.1 * i, ids=np.arange(128 + 64 * i),
                               max_new=16 + 4 * i, t_first=0.3 + 0.13 * i,
                               t_last=0.9 + 0.29 * i, tokens=16 + 4 * i)
             for i in range(12)}
    return run.Run(cell=cell, conf=conf, mix=mix, m=run.model(conf),
                   chips=1, seconds=0.75, setup_s=25.625,
                   peak=peaks.peak("TPU v5 lite"), lives=lives,
                   rounds=rounds, drain_end=60.5, compiles_in_window=0,
                   trace=tr)


PINNED = {
    "compiles_in_window": 0,
    "decode_attn_roofline": 34.57620685833738,
    "device_idle_share": 0.06665696666666676,
    "output_tok_s_per_chip": 129.33333333333334,
    "prefill_busy_share": 0.0,
    "prefix_hit_share": 0.6666666666666666,
    "queue_depth_mean": 1.0,
    "setup_s": 25.625,
    "slot_occupancy": 0.16666666666666666,
    "step_mfu": 39.70779222632826,
    "tpot_p90_ms": 40.0,
    "ttft_p90_s": 0.6000000000000001,
}


@pytest.fixture(scope="module")
def fixed():
    return fixed_run()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_reader_reads_as_before(fixed, name):
    assert run.reader(name)(fixed) == PINNED[name]
