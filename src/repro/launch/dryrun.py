import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory/cost/collective metrics.

The two lines above MUST stay the first statements in this module: jax
locks the platform device count at first init, and the production meshes
need 512 placeholder host devices. Do not fold this into conftest or
pyproject — smoke tests and benches must see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
      --out results/dryrun
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, ModelConfig, ShapeSpec, shapes_for
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim.adamw import OptConfig
from repro.train import serve as serve_lib
from repro.train import step as step_lib
from repro.utils.sharding import (SERVE_FSDP_RULES, SERVE_RULES, TRAIN_RULES,
                                  mesh_axis_sizes, named_shardings,
                                  use_mesh_rules)

COLLECTIVE_RE = re.compile(
    r"""(?P<dtype>[a-z0-9]+)\[(?P<dims>[\d,]*)\][^=]*=\s*
        (?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|
         collective-permute)(?:-start)?\(""",
    re.VERBOSE)


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand/result bytes per collective kind from compiled HLO."""
    from repro.utils.hw import dtype_bytes
    out: dict = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        op = m.group("op")
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * dtype_bytes(m.group("dtype"))
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    return step_lib.batch_shapes(cfg, shape)


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
               donate: bool = True, oc: "OptConfig | None" = None,
               decode_loop: int = 0, serve_variant: str = "resident2d"):
    """Build (jitted_fn, args_shapes) for one (arch x shape x mesh) cell."""
    sizes = mesh_axis_sizes(mesh)
    if shape.kind == "train":
        rules = TRAIN_RULES
        accum = step_lib.default_accum_steps(cfg, shape, sizes)
        oc = oc or OptConfig()
        fn = step_lib.make_train_step(cfg, oc, accum)
        state_shapes = step_lib.train_state_shapes(cfg, oc)
        bshapes = step_lib.batch_shapes(cfg, shape)
        state_sh = named_shardings(mesh, step_lib.train_state_pspecs(
            cfg, rules, sizes, oc))
        batch_sh = named_shardings(
            mesh, step_lib.batch_pspecs(cfg, bshapes, rules, sizes))
        jfn = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                      out_shardings=(state_sh, None),
                      donate_argnums=(0,) if donate else ())
        meta = {"accum_steps": accum, "rules": "train",
                "moments": oc.moments_dtype}
        return jfn, (state_shapes, bshapes), rules, meta

    tp = sizes.get("model", 1)
    fsdp = serve_lib.serve_uses_fsdp(cfg, tp=tp)
    from repro.utils.sharding import SERVE_FSDP_GATHER_RULES
    if not fsdp:
        rules = SERVE_RULES
    elif serve_variant == "gather":
        rules = SERVE_FSDP_GATHER_RULES
    else:
        rules = SERVE_FSDP_RULES
    pshapes = M.param_shapes(cfg)
    p_sh = named_shardings(mesh, M.param_pspecs(cfg, rules, sizes))
    bshapes = step_lib.batch_shapes(cfg, shape)
    batch_sh = named_shardings(
        mesh, step_lib.batch_pspecs(cfg, bshapes, rules, sizes))
    meta = {"serve_fsdp": fsdp, "rules": "serve_fsdp" if fsdp else "serve"}

    if shape.kind == "prefill":
        fn = serve_lib.make_prefill_step(cfg)
        cache_sh = named_shardings(mesh, M.cache_pspecs(cfg, rules, sizes,
                                               shape.global_batch,
                                               shape.seq_len))
        jfn = jax.jit(fn, in_shardings=(p_sh, batch_sh),
                      out_shardings=(None, cache_sh))
        return jfn, (pshapes, bshapes), rules, meta

    # decode
    if decode_loop and cfg.embed_inputs:
        fn = serve_lib.make_decode_loop_step(cfg, decode_loop)
        meta["decode_loop"] = decode_loop
    else:
        fn = serve_lib.make_decode_step(cfg)
    cshapes = M.cache_shapes(cfg, shape.global_batch, shape.seq_len)
    cache_sh = named_shardings(mesh, M.cache_pspecs(cfg, rules, sizes,
                                           shape.global_batch, shape.seq_len))
    jfn = jax.jit(fn, in_shardings=(p_sh, cache_sh, batch_sh, None),
                  out_shardings=(None, cache_sh),
                  donate_argnums=(1,) if donate else ())
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return jfn, (pshapes, cshapes, bshapes, pos), rules, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             cfg: ModelConfig | None = None,
             keep_text: bool = False, oc=None, decode_loop: int = 0,
             serve_variant: str = "resident2d") -> dict:
    """Lower + compile one cell; return the §Dry-run/§Roofline record."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "n_devices": mesh.devices.size}
    t0 = time.time()
    jfn, args, rules, meta = lower_cell(cfg, shape, mesh, oc=oc,
                                        decode_loop=decode_loop,
                                        serve_variant=serve_variant)
    rec.update(meta)
    with mesh, use_mesh_rules(mesh, rules):
        lowered = jfn.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes": int(ma.argument_size_in_bytes +
                          ma.output_size_in_bytes +
                          ma.temp_size_in_bytes -
                          ma.alias_size_in_bytes),
    }
    from repro.core.baseline import normalize_cost_analysis
    ca = normalize_cost_analysis(compiled.cost_analysis())
    rec["cost"] = {"flops": float(ca.get("flops", 0.0)),
                   "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                   "transcendentals": float(ca.get("transcendentals", 0.0))}
    text = compiled.as_text()
    rec["collectives"] = parse_collectives(text)
    rec["hlo_bytes"] = len(text)

    # In-core + WA analysis (the paper's model applied to the compiled
    # artifact) — trip-multiplied accounting for §Roofline.
    from repro.core import portmodel, wa
    from repro.core.machine import MACHINES
    rep = portmodel.analyze(text, MACHINES["tpu_v5e"],
                            n_devices=rec["n_devices"])
    rec["portmodel"] = {
        "tp_cycles": rep.tp_cycles,
        "cp_cycles": rep.cp_cycles,
        "serial_cycles": rep.serial_cycles,
        "flops": rep.flops,
        "bytes_hbm": rep.bytes_hbm,
        "coll_bytes": rep.coll_bytes,
        "bottleneck": rep.bottleneck(),
        "unknown_ops": rep.unknown_ops,
        "n_instrs": rep.n_instrs,
        "trips": {k: v for k, v in sorted(rep.trips_seen.items())[:16]},
        "top_ports": dict(sorted(rep.port_occupation.items(),
                                 key=lambda kv: -kv[1])[:6]),
        "loop_bytes": dict(sorted(rep.loop_bytes.items(),
                                  key=lambda kv: -(kv[1][0] * kv[1][1]))[:12]),
    }
    rec["wa"] = wa.analyze_text_stores(text)
    rec["wa_ratio"] = rec["wa"]["wa_ratio"]
    if keep_text:
        rec["hlo_text"] = text
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for sh in shapes_for(get_config(arch)):
                cells.append((arch, sh.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, sh in cells:
        for mp in meshes:
            tag = f"{arch}_{sh}_{'mp' if mp else 'sp'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (cached)")
                continue
            try:
                rec = run_cell(arch, sh, multi_pod=mp)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                mem = rec["memory"]["peak_bytes"] / 1e9
                print(f"[ok]   {tag}: peak {mem:.2f} GB/dev, "
                      f"flops/dev {rec['cost']['flops']:.3e}, "
                      f"lower {rec['lower_s']}s compile {rec['compile_s']}s",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — sweep must survive
                failures += 1
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
                with open(os.path.join(args.out, tag + ".err"), "w") as f:
                    f.write(traceback.format_exc())
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
