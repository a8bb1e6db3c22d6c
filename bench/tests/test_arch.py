"""A model of another architecture joins the benchmark as new files
only: an architecture file ``arch/<name>.py``, a configuration naming it
in ``model.architectures``, and a cell. The toy (``toy_hybrid.py``)
alternates attention and Mamba layers over five layers, so the
program's tree has a scan unit of two and a tail layer, and recurrent
state sits beside the page pool."""

import dataclasses
import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference as R
import run
import weights as W
from conftest import BENCH, ROOT, make_bench_copy, smoke_conf, write_json
from test_run_smoke import run_once

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_hybrid.py")
SEED = 2**31 + 29


def toy_conf() -> dict:
    return {
        "name": "toy", "source": "toy sizes for CPU tests",
        "model": {"architectures": ["ToyHybridForCausalLM"],
                  "hidden_size": 128, "intermediate_size": 256,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "num_hidden_layers": 5, "vocab_size": 512,
                  "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
                  "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
                  "mamba_dt_rank": 8},
        "reduced": {}, "assumed": {}, "deployment": "none",
        "dtype": "bfloat16", "chips": 1,
        "engine": {"max_slots": 4, "max_len": 128, "page_size": 16,
                   "n_pages": 64, "chunk": 4, "pipeline": 0,
                   "attn_impl": "auto"},
        # limits set between the program and the float8 control, as
        # calibrate.py reads them, over 12 seeds (2**31 + 1000 + 7919 k):
        # widest gap, program 0-0.0285, control 0.146-0.396; mean gap,
        # program 0-0.00148, control 0.0108-0.0256
        "check": {"min_requests": 3, "max_requests": 6,
                  "min_served_tokens": 24,
                  "limits": {"max_logit_gap": 0.06,
                             "mean_logit_gap": 0.004}},
    }


def add_toy(root, source: str) -> None:
    """Add the toy's architecture file (``source``, its text), its
    configuration and the cell ``toy.chat`` to a copy of the benchmark."""
    bench = root / "bench"
    with open(bench / "arch" / "toyhybrid.py", "w") as f:
        f.write(source)
    write_json(bench / "configs" / "toy.json", toy_conf())
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy", "source": "toy",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "CPU test"})
    spec["workloads"].append({"name": "toy.chat", "config": "toy",
                              "traffic": "smoke-mix", "chips": 1,
                              "why": "CPU test"})
    write_json(root / "BENCHMARK.json", spec)


def toy_source() -> str:
    with open(TOY) as f:
        return f.read()


@pytest.fixture
def toy_copy(tmp_path):
    root = make_bench_copy(tmp_path)
    add_toy(root, toy_source())
    return root


@pytest.fixture(scope="module")
def toy_model():
    return run.load_module(TOY, "arch_toyhybrid").Model.from_config(
        toy_conf())


def test_toy_cell_runs_and_is_correct(toy_copy):
    res, state, out, err = run_once(toy_copy, "toy.chat", SEED, 3.0)
    assert res["correct"] is True, err
    assert res["attempted"] > 0 and res["failed"] == 0
    assert state.m.program_config("toy").scan_split() == (2, 2, 1)
    # every file the benchmark had is as it was, and BENCHMARK.json only
    # gained entries
    old = [os.path.relpath(os.path.join(d, f), BENCH)
           for d, dirs, files in os.walk(BENCH)
           if "tests" not in d and "__pycache__" not in d for f in files]
    assert old and all(filecmp.cmp(os.path.join(BENCH, p),
                                   os.path.join(toy_copy, "bench", p),
                                   shallow=False) for p in old)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        before = json.load(f)
    with open(os.path.join(toy_copy, "BENCHMARK.json")) as f:
        after = json.load(f)
    for key, was in before.items():
        now = after[key]
        assert (now[:len(was)] if isinstance(was, list) else now) == was


def test_toy_without_its_convolution_reads_not_correct(tmp_path):
    """The check sees the second kind of layer: a reference Mamba layer
    that leaves out its causal convolution fails it."""
    root = make_bench_copy(tmp_path)
    src = toy_source()
    broken = src.replace('causal_conv(xi, f["conv_w"])', "xi")
    assert broken != src
    add_toy(root, broken)
    res, _, _, err = run_once(root, "toy.chat", SEED, 3.0)
    assert res["correct"] is False, err
    assert any(c["value"] > c["limit"] for c in res["check"].values())


def test_toy_reference_matches_the_program_forward(toy_model):
    from repro.models import model as M
    m = toy_model
    m32 = dataclasses.replace(m, dtype="float32")
    cfg = m32.program_config("toy")
    kd = jnp.asarray(W.key_data(SEED))
    top, layers = W.stacked(m, kd)
    assert set(layers["scan"]) == {"0", "1"} and set(layers["tail"]) == {"0"}
    tree = jax.tree.map(lambda a: a.astype(jnp.float32),
                        m.program_tree(top, layers))
    want_shapes = M.param_shapes(cfg)
    assert jax.tree.structure(tree) == jax.tree.structure(want_shapes)
    ids = np.random.default_rng(0).integers(0, m.vocab, 40)
    with jax.default_matmul_precision("highest"):
        want = M.forward(cfg, tree, {"tokens": jnp.asarray(ids)[None]},
                         mode="train")[0][0]
    (got,) = R.logits(m, SEED, [ids], [np.arange(40)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-4 * float(jnp.abs(want).max()))


def test_toy_counts(toy_model):
    import counts as C
    m = toy_model
    # attention layers 0, 2, 4; Mamba layers 1, 3 (inner 256, state 8,
    # dt rank 8, conv 4); a SwiGLU of 128x256 after each
    attn = 2 * 128 * 128 + 2 * 128 * 2 * 32
    mamba = 3 * 128 * 256 + 2 * 256 * (8 + 8)
    ffn = 3 * 128 * 256
    assert m.token_params == 3 * attn + 2 * mamba + 5 * ffn
    assert m.cached_layers == 3
    assert C.paged_attn_bytes(m, [20], 16) == 2 * 16 * (2 * 2 * 32 * 2) * 3
    mixer = 2 * (2 * 4 * 256 + 4 * 256 * 8)
    assert C.decode_flops(m, [10]) == (2 * (m.token_params + 128 * 512)
                                       + mixer + 3 * 4 * 128 * 10)


def test_one_model_class_per_architecture():
    """Two reads of one configuration give equal models, so the
    reference's jitted calls, static in the model, compile once."""
    from conftest import smoke_conf
    assert run.model(smoke_conf()) == run.model(smoke_conf())
