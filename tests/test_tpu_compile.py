"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

Mosaic refuses what the Pallas interpreter accepts (tile shapes,
in-kernel reshapes, unpartitionable kernels), and the chip's compiler
refuses programs that do not fit its HBM. These tests compile the
serving path's kernels and steps at the published widths of gemma3-4b
(and yi-9b at TP=4) for the chip, so that such a fault fails here and
not on the chip. Nothing runs: a compile that passes says nothing about
results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# gemma3-4b decode widths (configs/gemma3_4b.py): 8 query heads over 4 KV
# heads of 256; 8 slots of 4096 rows
B, H, HKV, DH, SKV = 8, 8, 4, 256, 4096
#: the page size chip_smoke.py serves with
PAGE_SIZE = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with jax's persistent cache off around the
    compiles (entries for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture()
def on_chip(monkeypatch):
    """Steer the kernel routing as a real v5e process would take it:
    Pallas compiled (not interpreted) and v5e tiles."""
    import repro.kernels as K
    from repro.kernels import stores, tuning
    monkeypatch.setattr(K, "on_tpu", lambda: True)
    monkeypatch.setattr(stores, "on_tpu", lambda: True)
    monkeypatch.setattr(tuning, "default_machine", lambda: "tpu_v5e")


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_decode_compiles_for_v5e(one_chip, window):
    from repro.kernels.attention import decode as D
    q = _sds((B, 1, H, DH), one_chip)
    kv = _sds((B, SKV, HKV, DH), one_chip)
    pos = _sds((B,), one_chip, jnp.int32)
    text = _compiled_text(
        lambda q, k, v, p: D.flash_decode(q, k, v, p, bk=512,
                                          window=window),
        q, kv, kv, pos)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_decode_paged_compiles_for_v5e(one_chip, window):
    """The paged kernel reads layer 2 of a three-layer stacked pool."""
    from repro.kernels.attention import decode as D
    n_pages = B * SKV // PAGE_SIZE + 1
    q = _sds((B, 1, H, DH), one_chip)
    pool = _sds((3, n_pages, PAGE_SIZE, HKV, DH), one_chip)
    bt = _sds((B, SKV // PAGE_SIZE), one_chip, jnp.int32)
    pos = _sds((B,), one_chip, jnp.int32)
    text = _compiled_text(
        lambda q, k, v, t, p: D.flash_decode_paged(q, k, v, t, p, layer=2,
                                                   window=window),
        q, pool, pool, bt, pos)
    assert "tpu_custom_call" in text


def test_kv_write_nt_compiles_for_v5e(one_chip):
    from repro.kernels import stores
    cache = _sds((B, SKV, HKV, DH), one_chip)
    row = _sds((B, 1, HKV, DH), one_chip)
    pos = _sds((B,), one_chip, jnp.int32)
    text = _compiled_text(
        lambda c, u, p: stores._kv_write_nt(c, u, p, interpret=False),
        cache, row, pos)
    assert "tpu_custom_call" in text


def test_gemma3_decode_step_fits_one_v5e(one_chip, on_chip):
    """The serve engine's chunked decode step at chip_smoke.py's dense
    shape (4 slots x 576 rows) holds the kernel and fits 16 GB of HBM."""
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve.decode import make_chunked_decode_step
    cfg = get_config("gemma3-4b")
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _sds(s.shape, one_chip, s.dtype), t)
    step = make_chunked_decode_step(cfg, 8, guard=True,
                                    store_flavor="auto")
    c = jax.jit(step, donate_argnums=(1,)).lower(
        put(M.param_shapes(cfg)), put(M.cache_shapes(cfg, 4, 576)),
        _sds((4, 1), one_chip, jnp.int32), _sds((4,), one_chip, jnp.int32),
        put(jax.eval_shape(lambda: jax.random.PRNGKey(0)))).compile()
    assert "tpu_custom_call" in c.as_text()
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.75e9


def test_yi9b_tp4_decode_step_partitions_the_kernel(topo, one_chip,
                                                    on_chip):
    """yi-9b over a (1, 4) mesh: the kernel goes through shard_map (GSPMD
    cannot partition a Mosaic call) and each chip holds a quarter."""
    from jax.sharding import AxisType, Mesh
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve.decode import make_chunked_decode_step
    from repro.utils.sharding import (SERVE_ENGINE_RULES, mesh_axis_sizes,
                                      use_mesh_rules)
    cfg = get_config("yi-9b")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    sizes = mesh_axis_sizes(mesh)

    def laid_out(shapes, specs):
        return jax.tree.map(
            lambda s, p: _sds(s.shape, NamedSharding(mesh, p), s.dtype),
            shapes, specs, is_leaf=lambda x: isinstance(x, P))

    rep = NamedSharding(mesh, P())
    step = make_chunked_decode_step(cfg, 8, guard=True,
                                    store_flavor="auto")

    def traced(*a):
        with use_mesh_rules(mesh, SERVE_ENGINE_RULES):
            return step(*a)

    params = laid_out(M.param_shapes(cfg),
                      M.param_pspecs(cfg, SERVE_ENGINE_RULES, sizes))
    cache = laid_out(M.cache_shapes(cfg, 4, 576),
                     M.cache_pspecs(cfg, SERVE_ENGINE_RULES, sizes, 4, 576))
    c = jax.jit(traced, donate_argnums=(1,)).lower(
        params, cache, _sds((4, 1), rep, jnp.int32),
        _sds((4,), rep, jnp.int32), _sds((2,), rep, jnp.uint32)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert c.memory_analysis().argument_size_in_bytes < 0.3 * 17.7e9


def test_yi9b_paged_step_keeps_the_pool_in_place(one_chip, on_chip):
    """The serving benchmark's paged step (yi-9b cut to 24 layers, 24
    slots, 361 pages of 128, chunk 8; donated and guarded, as the engine
    runs it): the kernel reads the stacked pools where they lie, nothing
    copies, slices or re-stacks a pool, and the temporaries stay well
    under one pool (K+V: 2.27e9 B)."""
    import dataclasses

    from _pool_hlo import pool_moves, pool_shapes
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import pages as PG
    from repro.serve.decode import make_chunked_decode_step
    cfg = dataclasses.replace(get_config("yi-9b"), n_layers=24)
    slots, n_pages, chunk = 24, 361, 8
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _sds(s.shape, one_chip, s.dtype), t)
    cache = PG.paged_cache_shapes(cfg, slots, n_pages, PAGE_SIZE)
    step = make_chunked_decode_step(cfg, chunk, 0.0, attn_impl="auto",
                                    paged=True, guard=True)
    c = jax.jit(step, donate_argnums=(1,)).lower(
        put(M.param_shapes(cfg)), put(cache),
        _sds((slots, 4096 // PAGE_SIZE), one_chip, jnp.int32),
        _sds((slots, 1), one_chip, jnp.int32),
        _sds((slots,), one_chip, jnp.int32),
        _sds((2,), one_chip, jnp.uint32)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "input_output_alias" in text
    assert pool_moves(text, pool_shapes(cfg, cache)) == []
    assert c.memory_analysis().temp_size_in_bytes <= 1.25e9
