"""Operation and byte counts against hand arithmetic, one layer of
the configuration."""

import json
import os

import pytest

import counts as C
import run
from conftest import BENCH


def model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return run.model(json.load(f))


def test_yi_one_layer():
    m = model("yi-9b-24l")
    # per layer: q 4096x4096, k and v 4096x512 each, o 4096x4096, ffn
    # 3x4096x11008; a token multiplies by all 24 layers' weights
    lin = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    assert m.token_params == 24 * lin
    # one decoded token at 1000 rows: 2 flop per weight, 4*32*128 per row
    per = 2 * lin + 4 * 32 * 128 * 1000
    assert C.decode_flops(m, [1000]) == 24 * per + 2 * 4096 * 64000
    # K and V, 4 heads of 128, bf16: 2048 bytes per row and layer;
    # 1000 rows sit in 8 pages of 128
    assert m.kv_row_bytes == 2048
    assert C.paged_attn_bytes(m, [1000], 128) == 8 * 128 * 2048 * 24
    assert C.paged_attn_flops(m, [1000]) == 24 * 4 * 32 * 128 * 1000


def test_yi_prefill():
    m = model("yi-9b-24l")
    lin = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    # a prompt of 3 tokens: causal attention over 1 + 2 + 3 rows, and
    # the head at the last position only
    att = 4 * 32 * 128 * (1 + 2 + 3)
    assert C.prefill_flops(m, 3) == 24 * (2 * lin * 3 + att) \
        + 2 * 4096 * 64000


def test_least_seconds_names_its_bound():
    from peaks import peak
    p = peak("TPU v5 lite")
    assert C.least_seconds(197e12, 1.0, p) == pytest.approx((1.0, "compute"))
    assert C.least_seconds(1.0, 819e9, p) == pytest.approx((1.0, "memory"))
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary")
