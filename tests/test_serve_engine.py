"""Serve-engine behaviour: chunked decode matches the seed per-token
greedy loop token-for-token, admit/evict keeps per-slot streams
independent, donation keeps the decode cache update in place, and the
old `grow`-helper shape collision is pinned as a regression."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serve import Request, ServeEngine, make_chunked_decode_step
from repro.serve.kv_traffic import kv_update_traffic
from repro.train import serve as serve_lib


def _params(cfg, seed=0):
    return M.init_params(cfg, jax.random.PRNGKey(seed))


def _prompts(cfg, b, s, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (b, s), 0, cfg.vocab_size))


def _seed_greedy_loop(cfg, params, prompts, gen):
    """The seed serve loop: batched prefill + one decode step per token
    (cache preallocated at the horizon — the fixed version of the old
    jnp.pad regrow)."""
    b, s = prompts.shape
    prefill = jax.jit(serve_lib.make_prefill_step(cfg, cache_len=s + gen))
    decode = jax.jit(serve_lib.make_decode_step(cfg))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    out = [np.asarray(tok)]
    for i in range(gen - 1):
        lg, cache = decode(params, cache, {"tokens": tok[:, None]},
                           jnp.int32(s + i))
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
    return np.stack(out, axis=1)


def _run_engine(cfg, params, prompts, gen, **kw):
    b = prompts.shape[0]
    eng = ServeEngine(cfg, params, max_slots=b,
                      max_len=prompts.shape[1] + gen, **kw)
    res = eng.run([Request(rid=str(i), prompt=tuple(int(t) for t in prompts[i]),
                           max_new_tokens=gen) for i in range(b)])
    return np.stack([res[str(i)] for i in range(b)]), eng


@pytest.mark.parametrize("arch", ["yi-9b", "gemma3-4b", "xlstm-125m"])
def test_engine_matches_seed_greedy_loop(arch):
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    prompts = _prompts(cfg, 4, 16)
    gen, chunk = 12, 4
    ref = _seed_greedy_loop(cfg, params, prompts, gen)
    got, eng = _run_engine(cfg, params, prompts, gen, chunk=chunk)
    np.testing.assert_array_equal(got, ref)
    # chunked dispatch budget: ceil(gen/chunk) instead of gen-1
    assert eng.decode_dispatches <= math.ceil(gen / chunk)
    assert eng.prefill_dispatches == 1          # batched admit fast path


def test_admit_evict_keeps_streams_independent():
    cfg = get_smoke_config("yi-9b")
    params = _params(cfg)
    rng = np.random.default_rng(0)
    # 3 requests on 2 slots with mixed prompt lengths and budgets:
    # c is admitted mid-flight (per-slot positions) after a retires
    reqs = [Request("a", tuple(rng.integers(0, cfg.vocab_size, 8)), 6),
            Request("b", tuple(rng.integers(0, cfg.vocab_size, 10)), 12),
            Request("c", tuple(rng.integers(0, cfg.vocab_size, 8)), 6)]
    eng = ServeEngine(cfg, params, max_slots=2, max_len=24, chunk=3)
    res = eng.run(list(reqs))
    assert set(res) == {"a", "b", "c"}
    for r in reqs:
        solo = ServeEngine(cfg, params, max_slots=2, max_len=24, chunk=3)
        sres = solo.run([r])
        np.testing.assert_array_equal(
            res[r.rid], sres[r.rid],
            err_msg=f"stream {r.rid} disturbed by batch-mates")


def test_cancel_returns_partial_stream_and_frees_slot():
    """cancel() mid-flight hands back the tokens decoded so far (a
    prefix of the uncancelled stream), frees the slot, and the next
    request served from that slot is undisturbed."""
    cfg = get_smoke_config("yi-9b")
    params = _params(cfg)
    rng = np.random.default_rng(3)
    pa = tuple(int(t) for t in rng.integers(0, cfg.vocab_size, 8))
    pb = tuple(int(t) for t in rng.integers(0, cfg.vocab_size, 8))
    eng = ServeEngine(cfg, params, max_slots=1, max_len=24, chunk=3)
    assert eng.step() == []                     # idle engine: no-op
    eng.admit(Request("a", pa, 12))
    eng.step()                                  # a few tokens in flight
    assert eng.cancel("zzz") is None            # unknown rid
    part = eng.cancel("a")
    assert eng.free_slots() == [0]
    full = ServeEngine(cfg, params, max_slots=1, max_len=24,
                       chunk=3).run([Request("a", pa, 12)])["a"]
    assert 1 <= len(part) < len(full)
    np.testing.assert_array_equal(part, full[:len(part)])
    res = eng.run([Request("b", pb, 6)])
    solo = ServeEngine(cfg, params, max_slots=1, max_len=24,
                       chunk=3).run([Request("b", pb, 6)])
    np.testing.assert_array_equal(res["b"], solo["b"])


def test_decode_cache_update_stays_in_place():
    """Donation: no full-cache-leaf copy of the cache *arguments* in the
    lowered HLO (without donation XLA copies every KV buffer per chunk)."""
    cfg = get_smoke_config("yi-9b")
    b, horizon = 2, 24
    step = make_chunked_decode_step(cfg, 3)
    args = (M.param_shapes(cfg), M.cache_shapes(cfg, b, horizon),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    kv_leaf = jax.tree.leaves(M.cache_shapes(cfg, b, horizon))[0]
    sig = "bf16[" + ",".join(str(d) for d in kv_leaf.shape) + "]"

    def arg_copies(txt):
        return [ln for ln in txt.splitlines()
                if re.search(r"= " + re.escape(sig) + r"\S* copy\(", ln)
                and "%Arg_" in ln]

    donated = jax.jit(step, donate_argnums=(1,)).lower(
        *args).compile().as_text()
    plain = jax.jit(step).lower(*args).compile().as_text()
    assert "input_output_alias" in donated
    assert len(arg_copies(plain)) >= 2      # detector sanity: K and V copied
    assert len(arg_copies(donated)) == 0    # in-place with donation


def test_grow_shape_collision_regression():
    """The old launch/serve.py `grow` matched cache leaves by
    `x.shape[1] == s` / `x.shape[2] == s`: with prompt_len == n_heads the
    mLSTM state (B, H, Dh, Dh) / (R, B, H, Dh, Dh) collides and the heads
    axis got padded. Slot preallocation replaces shape-guessing entirely."""
    cfg = get_smoke_config("xlstm-125m")
    s = cfg.n_heads                            # the colliding prompt length
    gen = 6
    prompts = _prompts(cfg, 2, s)
    params = _params(cfg)
    _, cache = jax.jit(serve_lib.make_prefill_step(cfg))(
        params, {"tokens": jnp.asarray(prompts)})

    def old_grow(x):                           # verbatim old helper
        if x.ndim == 4 and x.shape[1] == s:
            return jnp.pad(x, [(0, 0), (0, gen), (0, 0), (0, 0)])
        if x.ndim == 5 and x.shape[2] == s:
            return jnp.pad(x, [(0, 0), (0, 0), (0, gen), (0, 0), (0, 0)])
        return x
    grown = jax.tree.map(old_grow, cache)
    want = M.cache_shapes(cfg, 2, s + gen)
    mismatched = [g.shape for g, w in zip(jax.tree.leaves(grown),
                                          jax.tree.leaves(want))
                  if g.shape != w.shape]
    assert mismatched, "old grow no longer misfires — update this pin"

    # the engine serves the same shape correctly
    ref = _seed_greedy_loop(cfg, params, prompts, gen)
    got, _ = _run_engine(cfg, params, prompts, gen, chunk=2)
    np.testing.assert_array_equal(got, ref)


def test_recurrent_state_dtype_stable_in_chunk():
    """Mamba conv state comes back in compute dtype; the chunk scan must
    pin the carry to the cache contract (f32) instead of type-erroring."""
    cfg = get_smoke_config("jamba-v0.1-52b")
    params = _params(cfg)
    prompts = _prompts(cfg, 2, 8)
    got, _ = _run_engine(cfg, params, prompts, 6, chunk=3)
    assert got.shape == (2, 6)


def test_temperature_sampling_in_graph():
    cfg = get_smoke_config("yi-9b")
    params = _params(cfg)
    prompts = _prompts(cfg, 2, 8)
    got, eng = _run_engine(cfg, params, prompts, 8, chunk=4,
                           temperature=0.8, seed=3)
    assert got.shape == (2, 8)
    assert eng.decode_dispatches <= math.ceil(8 / 4)
    got2, _ = _run_engine(cfg, params, prompts, 8, chunk=4,
                          temperature=0.8, seed=3)
    np.testing.assert_array_equal(got, got2)   # seeded: reproducible


def test_kv_traffic_donation_delta_positive():
    cfg = get_smoke_config("gemma3-4b")
    rows = kv_update_traffic(cfg, 4, 48)
    assert {r["machine"] for r in rows} >= {"zen4", "golden_cove",
                                            "neoverse_v2"}
    by = {r["machine"]: r for r in rows}
    for r in rows:
        assert r["delta_bytes"] > 0, r         # donation always cheaper
        assert r["copied_bytes"] > r["donated_bytes"]
    # paper ordering on the in-place path: Grace <= SPR <= Zen 4
    assert (by["neoverse_v2"]["donated_bytes"]
            <= by["golden_cove"]["donated_bytes"]
            <= by["zen4"]["donated_bytes"])


def test_zero_and_one_token_budgets():
    cfg = get_smoke_config("yi-9b")
    params = _params(cfg)
    prompts = _prompts(cfg, 2, 8)
    ref = _seed_greedy_loop(cfg, params, prompts, 1)
    got, eng = _run_engine(cfg, params, prompts, 1, chunk=2)
    np.testing.assert_array_equal(got, ref)
    assert eng.decode_dispatches == 0          # prefill already yields tok0
    # zero/negative budgets and over-horizon prompts are rejected clearly
    eng2 = ServeEngine(cfg, params, max_slots=1, max_len=16, chunk=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng2.admit(Request("z", tuple(prompts[0]), 0))
    with pytest.raises(ValueError, match="horizon"):
        eng2.admit(Request("h", tuple(range(12)), 8))
    # out-of-vocab ids are rejected at admission: the jitted embedding
    # gather NaN-fills OOB rows, silently poisoning the whole stream
    with pytest.raises(ValueError, match="prompt ids"):
        eng2.admit(Request("v", (1, cfg.vocab_size, 2), 2))
    with pytest.raises(ValueError, match="prompt ids"):
        eng2.admit(Request("n", (-1, 2, 3), 2))
    assert eng2.free_slots() == [0]            # nothing half-admitted


def test_engine_device_pin_places_params_and_cache():
    """device= pins an unsharded engine to one device and changes no
    token; generate() hands replica i device i (round-robin)."""
    from repro.launch.serve import generate
    cfg = get_smoke_config("yi-9b")
    params = _params(cfg)
    prompts = _prompts(cfg, 2, 6)
    dev = jax.devices()[0]
    base, _ = _run_engine(cfg, params, prompts, 4)
    pinned, _ = _run_engine(cfg, params, prompts, 4, device=dev)
    np.testing.assert_array_equal(base, pinned)
    eng = ServeEngine(cfg, params, max_slots=2, max_len=16, chunk=2,
                      device=dev)
    held = {d for tree in (eng.params, eng.cache)
            for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
    assert held == {dev}
    out: list = []
    generate(cfg, params, prompts, 3, chunk=2, replicas=2, engine_out=out)
    devs = jax.devices()
    assert [e.device for e in out] == [devs[i % len(devs)]
                                       for i in range(2)]
    with pytest.raises(ValueError, match="mesh or a device"):
        ServeEngine(cfg, params, max_slots=2, max_len=16, chunk=2,
                    device=dev, mesh=object())
