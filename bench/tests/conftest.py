"""CPU tests of the benchmark: ``pytest bench/tests``.

They run on the CPU at a toy size (``smoke_conf``), with the harness's
look for a chip switched off, so they say nothing about speed.
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

import pytest  # noqa: E402


def smoke_conf() -> dict:
    """A Llama-style decoder far too small to mean anything on a chip."""
    return {
        "name": "smoke", "source": "toy sizes for CPU tests",
        "model": {"architectures": ["LlamaForCausalLM"],
                  "hidden_size": 128, "intermediate_size": 256,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "num_hidden_layers": 2, "vocab_size": 512,
                  "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
                  "attention_bias": True},
        "reduced": {}, "assumed": {}, "deployment": "none",
        "dtype": "bfloat16", "chips": 1,
        "engine": {"max_slots": 4, "max_len": 128, "page_size": 16,
                   "n_pages": 64, "chunk": 4, "pipeline": 0,
                   "attn_impl": "auto"},
        "check": {"min_requests": 3, "max_requests": 6,
                  "min_served_tokens": 24,
                  "limits": {"max_logit_gap": 0.01,
                             "mean_logit_gap": 0.0004}},
    }


SMOKE_MIX = {
    "arrival": "poisson", "rate_per_s": 6.0,
    "prompt": {"median": 32, "sigma": 0.6, "min": 16, "max": 80,
               "grid": 4},
    "output": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
}


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture
def bench_copy(tmp_path):
    return make_bench_copy(tmp_path)


def make_bench_copy(tmp_path):
    """A copy of the benchmark in a fresh checkout, with one added cell
    (``smoke.chat``) and one added per-layer metric
    (``rounds_in_window``), made of new files and new entries only."""
    dst = tmp_path / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    write_json(dst / "configs" / "smoke.json", smoke_conf())
    write_json(dst / "traffic" / "smoke-mix.json", SMOKE_MIX)
    bench["configs"].append({"name": "smoke", "source": "toy",
                             "file": "bench/configs/smoke.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": "smoke.chat", "config": "smoke",
                               "traffic": "smoke-mix", "chips": 1,
                               "why": "CPU test"})
    with open(dst / "metrics" / "rounds_in_window.py", "w") as f:
        f.write('"""Router rounds in the window."""\n\n\n'
                'def read(run):\n    return len(run.rounds)\n')
    bench["per_layer"].append({"name": "rounds_in_window", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "router", "moves": "tpot_p90_ms",
                               "workloads": ["smoke.chat"]})
    write_json(tmp_path / "BENCHMARK.json", bench)
    return tmp_path


def load_run(root):
    """The copy's ``run.py`` as a module, with the chip check off."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(root)))}", os.path.join(root, "bench",
                                                          "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    import jax
    import peaks
    mod.require_chips = lambda n: jax.devices()
    # the CPU has no published peaks: a stand-in, so per-layer readers
    # run; their numbers mean nothing here
    peaks.PEAKS.setdefault("cpu", peaks.Peak(1e12, 1e11, 1e10, "none"))
    return mod
