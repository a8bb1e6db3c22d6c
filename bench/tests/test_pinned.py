"""Numbers read on the commit before the architectures moved into
``arch/``, pinned exactly: the same seed has to give bitwise the same
weights, reference and control logits at the smoke size, and the same
program configuration for ``yi-9b-24l``. All are deterministic on the
CPU, so the tolerance is 0."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference as R
import run
import weights as W
from conftest import BENCH, smoke_conf

SEED = 2**31 + 11


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in map(np.asarray, arrays):
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def model():
    return run.model(smoke_conf())


def test_weights_are_bitwise_the_same(model):
    tree = jax.jit(lambda kd: model.program_tree(*W.stacked(model, kd)))(
        jnp.asarray(W.key_data(SEED)))
    assert digest(jax.tree.leaves(tree)) == (
        "989a8de29ea07f94130bac404a54dd1ad4656d237468c8fd92028129860018f4")


@pytest.mark.parametrize("control,want", [
    (False,
     "b50d1e69ea771f723d3c4967f970209054db55f037718fda5e65def7b422db56"),
    (True,
     "a3e85de411f68a91e664b44c5c899b087500e2b32d39d3f4e920c0fae4e7be74"),
])
def test_reference_logits_are_bitwise_the_same(model, control, want):
    # one sequence inside a padded block, one across two; every seventh
    # position of the long one
    ids = np.random.default_rng(3).integers(0, model.vocab, 600)
    got = R.logits(model, SEED, [ids[:40], ids],
                   [np.arange(40), np.arange(0, 600, 7)], control=control)
    assert digest(got) == want


def test_yi_program_config_is_the_same():
    with open(os.path.join(BENCH, "configs", "yi-9b-24l.json")) as f:
        cfg = run.model(json.load(f)).program_config("yi-9b-24l")
    assert dataclasses.asdict(cfg) == {
        "name": "yi-9b-24l", "family": "dense", "n_layers": 24,
        "d_model": 4096, "n_heads": 32, "n_kv_heads": 4, "d_ff": 11008,
        "vocab_size": 64000, "head_dim": 128, "block_pattern": ("attn",),
        "qkv_bias": False, "qk_norm": False, "rope_kind": "rope",
        "rope_theta": 5000000.0, "sliding_window": 1024,
        "ffn_act": "swiglu", "n_experts": 0, "experts_per_token": 0,
        "d_ff_expert": 0, "capacity_factor": 1.25, "moe_group_size": 1024,
        "ssm_d_state": 16, "ssm_conv_dim": 4, "ssm_expand": 2,
        "ssm_dt_rank": 0, "ssm_chunk": 128, "ssm_fuse": True,
        "xlstm_proj_factor": 2.0, "tie_embeddings": False,
        "embed_inputs": True, "norm_eps": 1e-06,
        "param_dtype": "bfloat16", "q_chunk": 512, "kv_chunk": 1024,
        "long_context_ok": False, "remat": "full", "decode_unroll": False}


def test_missing_architecture_names_its_path():
    conf = smoke_conf()
    conf["model"]["architectures"] = ["NoSuchForCausalLM"]
    with pytest.raises(SystemExit, match=r"arch/nosuch\.py"):
        run.model(conf)
