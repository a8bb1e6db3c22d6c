"""Does a configuration fit one chip? Compile its programs for a
described TPU v5e, without one, and print what XLA says they hold:

    JAX_PLATFORMS=cpu python3 bench/aot_fit.py bench/configs/<config>.json \
        [--prefill 3072] [--n-pages 896]

For the paged decode step (every slot, the configured chunk) and for a
prefill at the longest prompt, one JSON line each with
``memory_analysis()``: argument, output, temporary and alias bytes.
Used to size ``max_slots`` and ``n_pages`` before any chip run; it
compiles, it does not run, so it says nothing about time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def analysis(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k, 0)) for k in keys}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--prefill", type=int, default=0,
                    help="prompt length to compile the prefill at "
                         "(0: the engine's max_len)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page pool to try (0: the configuration's)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import serving
    import weights as W
    from repro.models import model as M
    from repro.serve import pages as P
    from repro.serve.decode import make_chunked_decode_step
    from repro.train.serve import make_prefill_step

    # compile what the chip runs: the Pallas kernels, not their
    # interpreter or the plain-JAX route this CPU process would pick
    import repro.kernels as K
    from repro.kernels import stores, tuning
    K.on_tpu = stores.on_tpu = lambda: True
    tuning.default_machine = lambda: "tpu_v5e"
    jax.config.update("jax_enable_compilation_cache", False)
    with open(args.config) as f:
        conf = json.load(f)
    m = W.Model.from_config(conf)
    cfg = serving.program_config(m, conf["name"])
    e = conf["engine"]
    n_pages = args.n_pages or e["n_pages"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = spec(M.param_shapes(cfg))
    cache = spec(P.paged_cache_shapes(cfg, e["max_slots"], n_pages + 1,
                                      e["page_size"]))
    b = e["max_slots"]
    nb = P.pages_per_slot(e["max_len"], e["page_size"])
    i32 = jnp.int32
    step = make_chunked_decode_step(cfg, e["chunk"], 0.0,
                                    attn_impl=e["attn_impl"], paged=True,
                                    guard=True)
    dec = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, jax.ShapeDtypeStruct((b, nb), i32, sharding=one),
        jax.ShapeDtypeStruct((b, 1), i32, sharding=one),
        jax.ShapeDtypeStruct((b,), i32, sharding=one),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)).compile()
    print(json.dumps({"program": "decode", "config": conf["name"],
                      "n_pages": n_pages, "max_slots": b,
                      "weights_bytes": sum(
                          x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(params)),
                      "kernel": "tpu_custom_call" in dec.as_text(),
                      **analysis(dec)}), flush=True)
    n = args.prefill or e["max_len"]
    pre = jax.jit(make_prefill_step(cfg, cache_len=None)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, n), i32,
                                                sharding=one)}).compile()
    print(json.dumps({"program": "prefill", "config": conf["name"],
                      "tokens": n, **analysis(pre)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
