"""Serving driver on the continuous-batching engine (repro.serve).

Prompts are prefilled into preallocated KV slots (cache built once at
the full horizon — no ``jnp.pad`` regrow, which used to copy the whole
cache: a system-scale write allocate, DESIGN.md §2) and decoded in
multi-token in-graph chunks: ``ceil(gen/chunk)`` decode dispatches
instead of one per token.

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m --smoke \
      --batch 4 --prompt-len 64 --gen 32

Compiled programs persist across runs: where ``JAX_COMPILATION_CACHE_DIR``
is set jax keeps its cache there, and otherwise :func:`use_compile_cache`
points it at ``.jax_cache`` in the checkout.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from functools import partial

import jax

from repro.configs import get_config, get_smoke_config
from repro.models import model as M
from repro.serve import Request, ServeEngine

#: REPRO_DTYPE_POLICY values -> jax default matmul precision. Set by
#: scripts/launch_env.sh (the config-driven runtime policy block);
#: consumed here so the driver and the env script agree on one table.
_DTYPE_POLICIES = {"bf16": "bfloat16", "tf32": "tensorfloat32",
                   "f32": "highest"}


#: the checkout root (src/repro/launch/serve.py -> three levels up)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to jax, which reads
    it itself. Otherwise the cache goes to ``<checkout>/.jax_cache`` —
    one fixed path, because the path is part of what a later run must
    find again. Call it before the first compile of the process: jax
    settles its cache at that compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def apply_runtime_policy(env: dict | None = None) -> dict:
    """Apply the launch-env runtime policy this process can still honor.

    ``scripts/launch_env.sh`` exports three kinds of policy knobs:
    process-start ones (tcmalloc LD_PRELOAD, XLA step-marker flags,
    TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD) that only the shell can
    apply, and in-process ones this hook picks up — today the dtype
    policy: ``REPRO_DTYPE_POLICY`` in {bf16, tf32, f32} maps to jax's
    default matmul precision. Returns the subset of policy that was
    applied, for the launch banner (an unknown policy value raises —
    a typo'd policy must not silently serve full-precision traffic).
    """
    env = os.environ if env is None else env
    applied = {}
    policy = env.get("REPRO_DTYPE_POLICY", "")
    if policy:
        prec = _DTYPE_POLICIES.get(policy)
        if prec is None:
            raise ValueError(
                f"REPRO_DTYPE_POLICY={policy!r}: expected one of "
                f"{sorted(_DTYPE_POLICIES)}")
        jax.config.update("jax_default_matmul_precision", prec)
        applied["dtype_policy"] = f"{policy} -> {prec}"
    marker = env.get("REPRO_STEP_MARKER", "")
    if marker and "--xla_step_marker_location" not in \
            env.get("XLA_FLAGS", ""):
        # XLA flags are read at backend init; by the time python code
        # runs it is too late to set them. The env script is the right
        # place — flag the miss loudly instead of silently ignoring it.
        applied["step_marker"] = (
            f"REPRO_STEP_MARKER={marker} set but XLA_FLAGS lacks "
            f"--xla_step_marker_location (source scripts/launch_env.sh)")
    return applied


def require_one_device_fit(params, device=None) -> None:
    """Raise unless ``params`` alone fit one device's memory.

    Chunk planning compiles the decode step unsharded, so a model that
    needs its mesh to fit cannot be planned; the caller must pass an
    explicit chunk. A device that reports no memory limit (the CPU) is
    taken to fit.
    """
    device = device or jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    need = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    if limit and need >= limit:
        raise ValueError(
            f"cannot plan the decode chunk under a mesh: the planner "
            f"compiles the step unsharded, and the parameters alone "
            f"({need} B) exceed one {device.device_kind}'s {limit} B. "
            f"Pass chunk= (--chunk N).")


def generate(cfg, params, prompt_tokens, gen_len: int, *,
             temperature: float = 0.0, seed: int = 0,
             chunk: int | None = None, machine: str | None = None,
             mesh=None, replicas: int = 1,
             engine_out: list | None = None,
             fault_tolerant: bool = False,
             pipeline: bool | int = 0):
    """Greedy/temperature batched generation. prompt_tokens: (B, S).

    One slot per prompt; the whole batch is admitted at once (a single
    batched prefill), then decoded in chunks. ``chunk=None`` plans the
    chunk size analytically from the port model (repro.serve.planner);
    under a mesh that needs the model to fit one device
    (:func:`require_one_device_fit`).
    ``mesh`` shards every engine replica over the device mesh
    (params + KV over ``kvheads`` -> TP; ``None`` keeps the bit-exact
    single-device path); ``replicas > 1`` splits the batch across N
    engines behind a round-robin :class:`repro.serve.ReplicaRouter`,
    and ``fault_tolerant=True`` upgrades the router to
    :class:`repro.serve.FaultTolerantRouter` (replica health tracking,
    request rescue, priced degradation — same results on a healthy
    fleet). Pass a list as ``engine_out`` to receive the engine(s)
    (dispatch counters) for inspection. ``pipeline`` enables the
    engines' double-buffered decode dispatch (token streams stay
    byte-identical to the serial rounds).
    """
    import numpy as np

    b, s = prompt_tokens.shape
    if chunk is None and gen_len > 1:
        if mesh is not None:
            require_one_device_fit(params)
        from repro.serve.planner import plan_chunk_size
        chunk = plan_chunk_size(cfg, b, s + gen_len, machine=machine,
                                max_chunk=min(32, gen_len - 1),
                                mesh=mesh).chunk
    replicas = max(1, int(replicas))
    slots = -(-b // replicas)
    # unsharded replicas go one per device (round-robin when there are
    # more replicas than devices); sharded ones all span the mesh
    devs = jax.devices()
    devices = [None if replicas == 1 or mesh is not None
               else devs[i % len(devs)] for i in range(replicas)]
    engines = [ServeEngine(cfg, params, max_slots=slots,
                           max_len=s + gen_len,
                           chunk=min(chunk or 1, max(1, gen_len - 1)),
                           temperature=temperature, seed=seed, mesh=mesh,
                           pipeline=pipeline, device=dev)
               for dev in devices]
    prompts = np.asarray(prompt_tokens)
    reqs = [Request(rid=str(i), prompt=tuple(int(t) for t in prompts[i]),
                    max_new_tokens=gen_len) for i in range(b)]
    if replicas == 1 and not fault_tolerant:
        results = engines[0].run(reqs)
    else:
        from repro.serve import FaultTolerantRouter, ReplicaRouter
        cls = FaultTolerantRouter if fault_tolerant else ReplicaRouter
        results = cls(engines, policy="round_robin",
                      max_queue=max(8, b)).run(reqs)
    if engine_out is not None:
        engine_out.extend(engines)
    import jax.numpy as jnp
    return jnp.stack([jnp.asarray(results[str(i)]) for i in range(b)])


@dataclasses.dataclass
class ServeRun:
    """What :func:`main` served: tokens and the objects that made them."""

    cfg: object
    params: dict
    prompts: jax.Array       # (batch, prompt_len) int32
    tokens: jax.Array        # (batch, gen) int32
    engines: list


def init_params(cfg, key, mesh=None):
    """Seeded parameters, created directly in the mesh layout if given.

    A sharded model may not fit one device, so with a mesh the init is
    one jitted program whose outputs are already laid out for the serve
    engine (``SERVE_ENGINE_RULES``).
    """
    if mesh is None:
        return M.init_params(cfg, key)
    from repro.utils.sharding import (SERVE_ENGINE_RULES, mesh_axis_sizes,
                                      named_shardings)
    shardings = named_shardings(mesh, M.param_pspecs(
        cfg, SERVE_ENGINE_RULES, mesh_axis_sizes(mesh)))
    return jax.jit(partial(M.init_params, cfg),
                   out_shardings=shardings)(key)


def main(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=0,
                    help="decode tokens per dispatch (0 = plan from the "
                         "port model's tier-resolved step cost)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="device mesh spec 'data,model=1,N' "
                         "(default: single-device, no mesh)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the round-robin router "
                         "(default 1: no router)")
    ap.add_argument("--fault-tolerant", action="store_true",
                    help="route through the health-tracking "
                         "FaultTolerantRouter (replica quarantine/eject, "
                         "request rescue, priced degradation)")
    ap.add_argument("--pipeline", type=int, default=0,
                    help="in-flight decode rounds per engine (0 = serial "
                         "dispatch; 2 = double-buffered). Token streams "
                         "are byte-identical either way")
    ap.add_argument("--plan-db", default="",
                    help="path to a repro.serve.plandb JSON database; "
                         "installed before planning so admission plans "
                         "are O(1) DB hits (missing keys fall back to "
                         "online planning, bit-identically)")
    args = ap.parse_args(argv)

    print(f"compile cache: {use_compile_cache()}")
    policy = apply_runtime_policy()
    for k, v in sorted(policy.items()):
        print(f"runtime policy: {k}: {v}")
    if args.plan_db:
        from repro.serve import plandb
        db = plandb.PlanDB.load(args.plan_db)
        plandb.install(db)
        print(f"plan db: {args.plan_db} ({len(db.chunks)} chunk plans, "
              f"{len(db.tiles)} tile plans)")
    from repro.launch.mesh import make_serve_mesh
    mesh = make_serve_mesh(args.mesh)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    # params and prompts must be independent streams: reusing one key for
    # both correlates the prompt ids with the embedding init
    k_params, k_prompts = jax.random.split(key)
    params = init_params(cfg, k_params, mesh)
    prompts = jax.random.randint(k_prompts, (args.batch, args.prompt_len),
                                 0, cfg.vocab_size)
    eng_out: list = []
    t0 = time.time()
    toks = generate(cfg, params, prompts, args.gen,
                    temperature=args.temperature, seed=args.seed,
                    chunk=args.chunk or None, mesh=mesh,
                    replicas=args.replicas, engine_out=eng_out,
                    fault_tolerant=args.fault_tolerant,
                    pipeline=args.pipeline)
    dt = time.time() - t0
    eng = eng_out[0]
    shard = f" tp={eng.tp}" if mesh is not None else ""
    repl = f" x{len(eng_out)} replicas" if len(eng_out) > 1 else ""
    pipe = f" pipeline={eng.pipeline}" if eng.pipeline else ""
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s) — "
          f"{eng.decode_dispatches} decode dispatches "
          f"(chunk={eng.chunk}) + {eng.prefill_dispatches} prefill"
          f"{shard}{repl}{pipe}")
    print("sample:", toks[0, :16].tolist())
    return ServeRun(cfg=cfg, params=params, prompts=prompts, tokens=toks,
                    engines=eng_out)


if __name__ == "__main__":
    main()
