"""Chip smoke run: the serving path at published widths on a TPU.

    python chip_smoke.py             # one chip: gemma3-4b, dense + paged
    python chip_smoke.py --chips 4   # four chips: yi-9b at TP=4 and
                                     # four gemma3-4b replicas

One process, random weights from a seed, the entry points a user calls
(``repro.launch.serve.main`` and ``PagedServeEngine``). Each phase
checks that every request got its tokens as valid ids, that the decode
program holds the Pallas kernel (``tpu_custom_call``), and that the
kernel route agrees with the plain-JAX route on the first decode step's
logits; the sharded phase also holds TP=4 to one device, in float32,
and shows that a planted sharding fault fails that check. Any failed
check exits non-zero. The lines before the last are
smoke timings of this one run, compiles included; they are not
metrics. The last line is one JSON object naming the device.

Without a TPU it exits non-zero before doing anything. The compile cache
goes where ``JAX_COMPILATION_CACHE_DIR`` points, or to ``.jax_cache``
in the checkout.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

#: first-decode-step logits of two routes agree when
#: max|a - b| <= LOGIT_RTOL * max|b|. In bf16 the kernel and the
#: plain-JAX route differ in where attention rounds (8 significant
#: bits) and in reduction order, layer after layer: gemma3-4b on a
#: TPU v5e reads 0.01797 (dense) and 0.01899 (paged), the same in
#: every run. A wrong attention result moves logits by O(1) of max|b|.
LOGIT_RTOL = 0.04
#: the same bound for TP=4 against one device, both in float32 with
#: f32 matmuls ("highest"): the shards change only f32 summation
#: order, so a deviation near the bf16 readings above means the split
#: itself is wrong. A planted fault (KV heads moved one shard over)
#: must read above it.
F32_LOGIT_RTOL = 0.005
#: one chip: this model at its published (layers, d_model, vocab)
ARCH, WIDTHS = "gemma3-4b", (34, 2560, 262144)
#: four chips: a model one v5e cannot hold, at TP over its 4 KV heads,
#: compared with a cut of it that one chip holds in float32
TP_ARCH, TP_MESH, CUT_LAYERS = "yi-9b", "data,model=1,4", 8
REPLICAS = 4
#: page size of the paged phase: 128 rows per (page, Hkv, Dh) DMA
PAGE_SIZE = 128
SEED = 0


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """The device list, or exit non-zero when JAX has no TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform is "
                 f"{devs[0].platform!r}); nothing was run")
    return devs


class CompileClock:
    """Sums XLA backend-compile seconds reported by jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def report(phase: str, clock: CompileClock, t0: float, c0: float,
           **extra) -> None:
    import jax
    dev = jax.devices()[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    fields = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"[smoke timing, not a metric] {phase}: "
          f"device_kind={dev.device_kind!r} "
          f"compile_s={clock.seconds - c0:.2f} "
          f"wall_s={time.perf_counter() - t0:.2f} "
          f"peak_bytes_in_use={peak} {fields}".rstrip(), flush=True)


def check_tokens(results: dict, reqs, vocab: int) -> None:
    for r in reqs:
        toks = np.asarray(results.get(r.rid, ()))
        check(toks.shape == (r.max_new_tokens,),
              f"request {r.rid}: {toks.shape[0] if toks.ndim else 0} of "
              f"{r.max_new_tokens} tokens")
        check(bool(((toks >= 0) & (toks < vocab)).all()),
              f"request {r.rid}: token ids outside [0, {vocab})")


def decode_program(eng) -> str:
    """Compiled text of the engine's own decode step at its shapes."""
    import jax
    args = eng._decode_args() + (jax.random.PRNGKey(SEED),)
    return eng._decode.lower(*args).compile().as_text()


def step_logits(eng, impl: str, cache, tok, pos, block_tables=None):
    """First decode-step logits (B, V) f32 through one attention route."""
    import jax
    from repro.models import model as M

    def fn(params, cache, tok, pos, bt):
        return M.forward(eng.cfg, params, {"tokens": tok}, mode="decode",
                         cache=cache, pos=pos, attn_impl=impl,
                         block_tables=bt)[0][:, 0]
    out = jax.jit(eng._traced(fn))(eng.params, cache, tok, pos,
                                   block_tables)
    return np.asarray(out.astype(np.float32))


def logit_delta(got, ref) -> dict:
    """Relative max deviation and greedy agreement of logits (..., V)."""
    rel = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    return {"rel_max_dlogit": rel, "argmax_agree": agree}


def dense_first_step(eng, prompts, impls=("auto", "ref")):
    """First-step logits per route on a fresh batched prefill."""
    import jax.numpy as jnp
    logits, cache = eng._prefill(eng.params, {"tokens": prompts})
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    pos = jnp.full((prompts.shape[0],), prompts.shape[1], jnp.int32)
    return [step_logits(eng, impl, cache, tok, pos) for impl in impls]


def forced_logits(eng, prompts, stream, *param_sets):
    """Logits (B, T, V) along a given token stream (B, T), one array
    per set of params run through ``eng``'s model.

    Step 0 comes from the prefill and step t from decoding
    ``stream[:, t - 1]``, whatever the route itself would have picked,
    so two routes fed one stream see the same prefix at every step.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    def fn(params, cache, tok, pos):
        logits, _, cache = M.forward(eng.cfg, params, {"tokens": tok},
                                     mode="decode", cache=cache, pos=pos,
                                     attn_impl="auto")
        return logits[:, 0].astype(jnp.float32), cache
    step = jax.jit(eng._traced(fn), donate_argnums=(1,))
    b, s = prompts.shape
    outs = []
    for params in param_sets:
        logits, cache = eng._prefill(params, {"tokens": prompts})
        out = [np.asarray(logits[:, -1].astype(jnp.float32))]
        del logits
        for t in range(1, stream.shape[1]):
            lg, cache = step(params, cache,
                             jnp.asarray(stream[:, t - 1:t], jnp.int32),
                             jnp.full((b,), s + t - 1, jnp.int32))
            out.append(np.asarray(lg))
        outs.append(np.stack(out, axis=1))
    return outs


def first_divergence(got, ref, ref_logits, got_logits) -> list:
    """Per stream, where two greedy streams (B, T) first part.

    None where they never do; else (step, top-2 gap, deviation): the
    reference's logit of its own token less that of the other stream's
    token, and max|got - ref| of the step, both over max|ref| of the
    step. A gap below the deviation marks a near-tie flip.
    """
    out = []
    for i in range(ref.shape[0]):
        steps = np.flatnonzero(got[i] != ref[i])
        if steps.size == 0:
            out.append(None)
            continue
        t = int(steps[0])
        row, scale = ref_logits[i, t], np.abs(ref_logits[i, t]).max()
        gap = (row[ref[i, t]] - row[got[i, t]]) / scale
        dev = np.abs(got_logits[i, t] - row).max() / scale
        out.append((t, float(gap), float(dev)))
    return out


def move_kv_heads(params):
    """The planted fault: every layer's KV heads rolled one shard over."""
    import jax
    import jax.numpy as jnp

    def roll(path, leaf):
        name = getattr(path[-1], "key", None)
        return jnp.roll(leaf, 1, axis=-2) if name in ("wk", "wv", "bk",
                                                      "bv") else leaf
    moved = jax.tree_util.tree_map_with_path(roll, params)
    return jax.device_put(moved, jax.tree.map(lambda a: a.sharding, params))


def requests(prompts, gen: int, prefix: str = "r"):
    from repro.serve import Request
    p = np.asarray(prompts)
    return [Request(rid=f"{prefix}{i}", prompt=tuple(int(t) for t in p[i]),
                    max_new_tokens=gen) for i in range(p.shape[0])]


def serve_args(arch: str, *extra):
    return ["--arch", arch, "--batch", "4", "--prompt-len", "512",
            "--gen", "64", "--seed", str(SEED), *extra]


# --- one chip --------------------------------------------------------------

def dense_phase(clock):
    """gemma3-4b through ``repro.launch.serve.main``, chunk planned."""
    from repro.launch import serve
    t0, c0 = time.perf_counter(), clock.seconds
    run = serve.main(serve_args(ARCH))
    eng = run.engines[0]
    widths = (run.cfg.n_layers, run.cfg.d_model, run.cfg.vocab_size)
    check(widths == WIDTHS,
          f"{ARCH} at (layers, d_model, vocab) = {widths}")
    check_tokens({r.rid: run.tokens[i]
                  for i, r in enumerate(requests(run.prompts, 64))},
                 requests(run.prompts, 64), run.cfg.vocab_size)
    text = decode_program(eng)
    got, ref = dense_first_step(eng, run.prompts)
    delta = logit_delta(got, ref)
    report(f"dense {ARCH} 4x512+64", clock, t0, c0, chunk=eng.chunk,
           **delta)
    return run, text, delta


def paged_phase(clock, params, cfg):
    """8 requests through a 4-slot PagedServeEngine, mid-flight churn."""
    import jax
    from repro.serve import PagedServeEngine
    t0, c0 = time.perf_counter(), clock.seconds
    lens = (128, 1024, 384, 640) * 2
    key = jax.random.PRNGKey(SEED + 1)
    reqs = []
    for i, n in enumerate(lens):
        p = jax.random.randint(jax.random.fold_in(key, i), (1, n), 0,
                               cfg.vocab_size)
        reqs += requests(p, 64, prefix=f"p{i}-")
    eng = PagedServeEngine(cfg, params, max_slots=4, max_len=2048,
                           attn_impl="auto", page_size=PAGE_SIZE)
    for r in reqs[:4]:
        eng.admit(r)
    eng._pre_dispatch()
    _, cache, bt, tok, pos = eng._decode_args()
    delta = logit_delta(step_logits(eng, "auto", cache, tok, pos, bt),
                        step_logits(eng, "ref", cache, tok, pos, bt))
    text = decode_program(eng)
    results = eng.run(reqs[4:])
    check_tokens(results, reqs, cfg.vocab_size)
    report(f"paged {ARCH} 8 reqs 4 slots", clock, t0, c0,
           chunk=eng.chunk, page_size=PAGE_SIZE, **delta)
    return text, delta


def one_chip(clock) -> None:
    run, text, delta = dense_phase(clock)
    check("tpu_custom_call" in text, "dense decode program has no kernel")
    check(delta["rel_max_dlogit"] <= LOGIT_RTOL,
          f"dense kernel vs ref logits: {delta}")
    params, cfg = run.params, run.cfg
    del run
    text, delta = paged_phase(clock, params, cfg)
    check("tpu_custom_call" in text, "paged decode program has no kernel")
    check(delta["rel_max_dlogit"] <= LOGIT_RTOL,
          f"paged kernel vs ref logits: {delta}")


# --- four chips ------------------------------------------------------------

def sharded_phase(clock):
    """yi-9b at TP=4 through main, then a float32 cut vs one device."""
    import jax
    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.mesh import make_serve_mesh
    from repro.models import model as M
    from repro.serve import ServeEngine
    t0, c0 = time.perf_counter(), clock.seconds
    run = serve.main(serve_args(TP_ARCH, "--mesh", TP_MESH, "--chunk", "8"))
    eng = run.engines[0]
    check_tokens({r.rid: run.tokens[i]
                  for i, r in enumerate(requests(run.prompts, 64))},
                 requests(run.prompts, 64), run.cfg.vocab_size)
    spread = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(eng.params)}
    check(max(spread) == eng.mesh.devices.size,
          f"params span {spread} devices")
    check("tpu_custom_call" in decode_program(eng),
          "sharded decode program has no kernel")
    report(f"sharded {TP_ARCH} tp={eng.tp}", clock, t0, c0)
    prompts = run.prompts
    del run, eng

    t0, c0 = time.perf_counter(), clock.seconds
    cfg = dataclasses.replace(get_config(TP_ARCH), n_layers=CUT_LAYERS,
                              param_dtype="float32")
    reqs = requests(prompts, 64)
    kw = dict(max_slots=4, max_len=512 + 64, chunk=8)
    with jax.default_matmul_precision("highest"):
        params = M.init_params(cfg, jax.random.PRNGKey(SEED))
        solo = ServeEngine(cfg, params, **kw)
        out_1 = solo.run(list(reqs))
        ref = np.stack([out_1[r.rid] for r in reqs])
        (ref_lg,) = forced_logits(solo, prompts, ref, params)
        tp = ServeEngine(cfg, params, mesh=make_serve_mesh(TP_MESH), **kw)
        del params, solo        # chip 0 then holds one shard, not the model
        out_s = tp.run(list(reqs))
        check_tokens(out_s, reqs, cfg.vocab_size)
        got = np.stack([out_s[r.rid] for r in reqs])
        got_lg, bad_lg = forced_logits(tp, prompts, ref, tp.params,
                                       move_kv_heads(tp.params))
    delta = logit_delta(got_lg, ref_lg)
    fault = logit_delta(bad_lg, ref_lg)["rel_max_dlogit"]
    agree = float(np.mean(got == ref))
    report(f"{TP_ARCH} {CUT_LAYERS}-layer f32 cut tp={tp.tp} vs unsharded",
           clock, t0, c0, greedy_token_agree=agree,
           first_divergence=first_divergence(got, ref, ref_lg, got_lg),
           fault_rel_max_dlogit=fault, **delta)
    check(delta["rel_max_dlogit"] <= F32_LOGIT_RTOL,
          f"TP={tp.tp} vs unsharded float32 logits: {delta}")
    check(fault > F32_LOGIT_RTOL,
          f"moved KV heads read {fault}, within the bound")


def replica_phase(clock):
    """gemma3-4b behind the router, one replica per device."""
    import jax
    from repro.launch import serve
    from repro.serve import ServeEngine
    t0, c0 = time.perf_counter(), clock.seconds
    run = serve.main(serve_args(ARCH, "--replicas", str(REPLICAS)))
    reqs = requests(run.prompts, 64)
    got = {r.rid: run.tokens[i] for i, r in enumerate(reqs)}
    check_tokens(got, reqs, run.cfg.vocab_size)
    devs = jax.devices()
    for i, eng in enumerate(run.engines):
        held = {d for tree in (eng.params, eng.cache)
                for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
        check(held == {devs[i % len(devs)]},
              f"replica {i} holds arrays on {sorted(map(str, held))}")
    first = run.engines[0]
    solo = ServeEngine(run.cfg, run.params, max_slots=first.max_slots,
                       max_len=first.max_len, chunk=first.chunk,
                       device=devs[0])
    ref = solo.run(list(reqs))
    same = all(np.array_equal(np.asarray(got[r.rid]), ref[r.rid])
               for r in reqs)
    report(f"replicas {ARCH} x{len(run.engines)}", clock, t0, c0,
           chunk=first.chunk, streams_match_one_engine=same)
    check(same, "replica token streams differ from one engine's")


def four_chips(clock) -> None:
    import jax
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX has {len(jax.devices())}")
    sharded_phase(clock)
    replica_phase(clock)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: gemma3-4b dense + paged on one chip; "
                         "4: yi-9b TP=4 and 4 gemma3-4b replicas")
    args = ap.parse_args(argv)
    devs = require_tpu()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    import jax
    from repro.launch.serve import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        if args.chips == 4:
            four_chips(clock)
        else:
            one_chip(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
