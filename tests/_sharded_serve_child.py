"""Child process for the 2-device sharded token-identity tests.

Must run under ``XLA_FLAGS=--xla_force_host_platform_device_count=2``
(jax pins the device count at first init, so the parent test cannot
flip it in-process). Serves the same request list twice — unsharded,
then TP-sharded over a (1, 2) mesh — and prints a JSON verdict the
parent asserts on.

Usage: python tests/_sharded_serve_child.py {dense|paged} [attn_impl]

``attn_impl=pallas`` runs the decode kernels (interpret mode on the
CPU) inside the sharded step, where they go through ``shard_map``; the
model then runs in float32, so that the all-reduce's summation order
cannot flip a bf16 near-tie between the two runs' greedy tokens.
"""

import dataclasses
import json
import sys

import jax
import numpy as np


def main() -> None:
    layout = sys.argv[1]
    impl = sys.argv[2] if len(sys.argv) > 2 else None
    assert jax.device_count() == 2, \
        f"need 2 forced host devices, have {jax.device_count()}"
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as M
    from repro.serve import PagedServeEngine, Request, ServeEngine

    cfg = get_smoke_config("yi-9b")     # GQA: 4 q heads over 2 kv heads
    if impl is not None:
        cfg = dataclasses.replace(cfg, param_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=f"r{i}",
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, cfg.vocab_size, 5 + i)),
                    max_new_tokens=4) for i in range(3)]
    cls = ServeEngine if layout == "dense" else PagedServeEngine
    kw = {} if layout == "dense" else {"page_size": 4}
    kw["attn_impl"] = impl
    mesh = make_test_mesh((1, 2))

    base = cls(cfg, params, max_slots=2, max_len=24, chunk=2,
               **kw).run(list(reqs))
    eng = cls(cfg, params, max_slots=2, max_len=24, chunk=2, mesh=mesh,
              **kw)
    sharded = eng.run(list(reqs))
    print(json.dumps({
        "layout": layout,
        "tp": eng.tp,
        "match": all(np.array_equal(base[r.rid], sharded[r.rid])
                     for r in reqs),
        "tokens": {r.rid: sharded[r.rid].tolist() for r in reqs},
    }))


if __name__ == "__main__":
    main()
