"""The plain reference against the program's own forward pass at a toy
size, both in float32, and the seeded weights both sides are built
from."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference as R
import run
import weights as W
from conftest import smoke_conf

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def model():
    return run.model(smoke_conf())


def test_stacked_weights_are_each_layers_own(model):
    kd = jnp.asarray(W.key_data(SEED))
    top, layers = jax.jit(lambda k: W.stacked(model, k))(kd)
    layers = layers["scan"]["0"]
    for i in range(model.layers):
        one = W.layer(model, kd, i, "attn")
        for k, v in one.items():
            assert np.array_equal(np.asarray(layers[k][i]), np.asarray(v)), k
    assert np.array_equal(np.asarray(top["embed"]),
                          np.asarray(W.top(model, kd)["embed"]))
    assert not np.array_equal(np.asarray(layers["wq"][0]),
                              np.asarray(layers["wq"][1]))


def test_reference_matches_the_program_forward(model):
    from repro.models import model as M
    m32 = dataclasses.replace(model, dtype="float32")
    cfg = m32.program_config("smoke")
    kd = jnp.asarray(W.key_data(SEED))
    tree = model.program_tree(*W.stacked(model, kd))
    tree = jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    ids = np.random.default_rng(0).integers(0, model.vocab, 40)
    with jax.default_matmul_precision("highest"):
        want = M.forward(cfg, tree, {"tokens": jnp.asarray(ids)[None]},
                         mode="train")[0][0]
    pos = np.arange(40)
    (got,) = R.logits(model, SEED, [ids], [pos])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-4 * float(jnp.abs(want).max()))


def test_gaps_and_positions():
    lg = jnp.asarray([[0.0, 2.0, 1.0], [3.0, 1.0, 0.5]])
    assert R.token_gaps(lg, [1, 2]).tolist() == [0.0, 2.5]
    assert R.served_positions(5, 3).tolist() == [4, 5, 6]
    assert R.sequence([1, 2], [7, 8, 9]).tolist() == [1, 2, 7, 8]


def test_control_is_a_lower_precision(model):
    ids = np.random.default_rng(1).integers(0, model.vocab, 64)
    pos = np.arange(64)
    (hi,) = R.logits(model, SEED, [ids], [pos])
    (lo,) = R.logits(model, SEED, [ids], [pos], control=True)
    d = float(jnp.abs(hi - lo).max() / jnp.abs(hi).max())
    assert 1e-3 < d < 0.5
