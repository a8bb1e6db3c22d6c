"""Integration: end-to-end train driver (loss decreases), serving
generation, compressed-gradient training, and a subprocess mini dry-run
(placeholder-device mesh lower+compile on a reduced config)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def test_train_loss_decreases():
    from repro.launch.train import main
    losses = main(["--arch", "xlstm-125m", "--smoke", "--steps", "30",
                   "--batch", "8", "--seq", "64", "--lr", "3e-3",
                   "--log-every", "10"])
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.05, (first, last)


def test_train_with_compression_runs():
    from repro.launch.train import main
    losses = main(["--arch", "yi-9b", "--smoke", "--steps", "6",
                   "--batch", "4", "--seq", "32", "--compress",
                   "--log-every", "5"])
    assert np.isfinite(losses).all()


def test_checkpoint_resume(tmp_path):
    from repro.launch.train import main
    d = str(tmp_path / "ck")
    main(["--arch", "xlstm-125m", "--smoke", "--steps", "4",
          "--batch", "2", "--seq", "32", "--ckpt-dir", d,
          "--ckpt-every", "2", "--log-every", "10"])
    # resume past end: restores step 4 and exits immediately
    losses = main(["--arch", "xlstm-125m", "--smoke", "--steps", "4",
                   "--batch", "2", "--seq", "32", "--ckpt-dir", d,
                   "--ckpt-every", "2", "--log-every", "10"])
    assert losses == [] or len(losses) <= 4


def test_serve_generate_deterministic():
    import jax
    from repro.configs import get_smoke_config
    from repro.launch.serve import generate
    from repro.models import model as M
    cfg = get_smoke_config("gemma3-4b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                 cfg.vocab_size)
    t1 = generate(cfg, params, prompts, 8)
    t2 = generate(cfg, params, prompts, 8)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert t1.shape == (2, 8)


class _Device:
    device_kind = "test device"

    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("stats, raises", [
    ({"bytes_limit": 4000}, True),      # 4000 B of params: no room
    ({"bytes_limit": 4001}, False),
    (None, False),                      # no limit reported (the CPU)
])
def test_mesh_planning_needs_params_on_one_device(stats, raises):
    """The chunk planner compiles the decode step unsharded: under a mesh
    a model whose parameters fill one device must get an explicit chunk,
    with a message that says so, not an opaque compiler error."""
    from repro.launch.serve import require_one_device_fit
    params = {"w": np.zeros((10, 100), np.float32)}
    if raises:
        with pytest.raises(ValueError, match="--chunk"):
            require_one_device_fit(params, _Device(stats))
    else:
        require_one_device_fit(params, _Device(stats))


@pytest.mark.slow
def test_mini_dryrun_subprocess(tmp_path):
    """Lower+compile a smoke config on a 2x2 placeholder mesh in a fresh
    process (the only place device-count flags are allowed)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.launch.mesh import make_test_mesh
from repro.optim.adamw import OptConfig
from repro.train import step as step_lib
from repro.utils.sharding import TRAIN_RULES, mesh_axis_sizes, use_mesh_rules
from repro.configs.base import ShapeSpec
import repro.models.model as M

cfg = get_smoke_config("yi-9b")
mesh = make_test_mesh((2, 2))
sizes = mesh_axis_sizes(mesh)
shape = ShapeSpec("mini", 64, 4, "train")
fn = step_lib.make_train_step(cfg, OptConfig(), 1)
state_shapes = step_lib.train_state_shapes(cfg)
bshapes = step_lib.batch_shapes(cfg, shape)
named = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                  is_leaf=lambda x: isinstance(x, P))
state_sh = named(step_lib.train_state_pspecs(cfg, TRAIN_RULES, sizes))
batch_sh = named(step_lib.batch_pspecs(cfg, bshapes, TRAIN_RULES, sizes))
with mesh, use_mesh_rules(mesh, TRAIN_RULES):
    c = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None)).lower(
        state_shapes, bshapes).compile()
ma = c.memory_analysis()
print(json.dumps({"ok": True, "temp": int(ma.temp_size_in_bytes)}))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["temp"] > 0


def test_compile_cache_goes_to_env_or_one_fixed_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and is left to jax; without it the
    cache is <checkout>/.jax_cache, the same path on every call."""
    import jax
    from repro.launch import serve
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert serve.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = serve.use_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert serve.use_compile_cache() == path
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
