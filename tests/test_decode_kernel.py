"""Split-KV flash-decode kernel, MemTier tile autotuner, and the serve
wiring around them: interpret-mode parity against the dense decode
oracle, cross-machine tile divergence (the tuner must actually read the
ladders), planner memoization, and in-place cache updates with the
kernel routed into the serve decode step."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import portmodel
from repro.kernels import tuning, use_pallas
from repro.kernels.attention import decode as D
from repro.kernels.attention import ops as kops
from repro.models import attention as A
from repro.models import model as M
from repro.serve import decode_read_traffic, plan_chunk_size
from repro.serve import planner as planner_lib
from repro.serve.decode import make_chunked_decode_step

PAPER_CPUS = ("zen4", "golden_cove", "neoverse_v2")


# --- kernel parity (interpret mode on CPU) ---------------------------------

def _rand_case(b, skv, h, hkv, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, hkv, dh), jnp.float32)
    return q, k, v


CASES = [
    # (b, skv, h, hkv, dh, window, bk, n_splits, pos)
    (2, 64, 4, 2, 32, None, 32, 1, 40),            # GQA g=2
    (2, 64, 8, 2, 32, None, 16, 2, 63),            # g=4, splits
    (3, 80, 4, 1, 32, None, 32, 2, [3, 40, 79]),   # MQA, Skv % bk != 0
    (2, 96, 4, 4, 64, 24, 32, 3, [10, 90]),        # window, per-slot pos
    (2, 50, 4, 2, 32, 16, 16, 1, 49),              # window, ragged Skv
]


@pytest.mark.parametrize("b,skv,h,hkv,dh,window,bk,ns,pos", CASES)
def test_flash_decode_vs_decode_attention(b, skv, h, hkv, dh, window,
                                          bk, ns, pos):
    q, k, v = _rand_case(b, skv, h, hkv, dh)
    pos = jnp.asarray(pos, jnp.int32)
    got = D.flash_decode(q, k, v, pos, window=window, bk=bk, n_splits=ns,
                         interpret=True)
    ref = A.decode_attention(q, k, v, pos, window=window)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_flash_decode_multi_token():
    """Sq>1: query tokens at pos..pos+Sq-1, causal among themselves."""
    b, skv, h, hkv, dh, sq, pos0 = 2, 64, 4, 2, 32, 3, 17
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, sq, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, hkv, dh), jnp.float32)
    got = D.flash_decode(q, k, v, jnp.int32(pos0), bk=16, n_splits=2,
                         interpret=True)
    ref = A.dense_causal_attention(q, k[:, :pos0 + sq], v[:, :pos0 + sq],
                                   q_offset=pos0)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_ref_decode_bounded_matches_dense():
    """The occupancy-bounded oracle == the dense path whenever the bound
    covers every slot's position (the router's contract)."""
    q, k, v = _rand_case(2, 64, 4, 2, 32, seed=2)
    pos = jnp.asarray([5, 30], jnp.int32)
    ref = A.decode_attention(q, k, v, pos)
    for kv_len in (31, 48, 64):
        got = D.ref_decode(q, k, v, pos, kv_len=kv_len)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_ops_routing_and_bounds():
    q, k, v = _rand_case(2, 64, 4, 2, 32, seed=3)
    pos = jnp.asarray([9, 21], jnp.int32)
    ref = A.decode_attention(q, k, v, pos)
    # every impl, with and without an occupancy bound, same numerics
    for impl in ("ref", "auto", "pallas"):
        for kv_len in (None, 22, 40):
            got = kops.flash_decode(q, k, v, pos, impl=impl, kv_len=kv_len)
            np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{impl}/{kv_len}")
    with pytest.raises(ValueError, match="unknown impl"):
        use_pallas("cuda")


def test_decode_attention_impl_routes_through_ops():
    q, k, v = _rand_case(2, 48, 4, 2, 32, seed=4)
    pos = jnp.int32(30)
    ref = A.decode_attention(q, k, v, pos)
    got = A.decode_attention(q, k, v, pos, impl="pallas")
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    got = A.decode_attention(q, k, v, pos, impl="auto", kv_len=31)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# --- paged kernel parity (interpret mode on CPU) ---------------------------

def _paginate(k, v, ps, seed=7, n_extra=3):
    """Scatter a dense (B, Skv, Hkv, Dh) cache into a shared page pool.

    Physical pages are assigned through a *permuted* (out-of-order)
    block table, extra unmapped pages and the partial-last-page tail are
    filled with garbage, so parity only holds if the kernel really
    gathers through the table and masks by logical position.
    """
    b, skv, hkv, dh = k.shape
    nb = -(-skv // ps)
    rng = np.random.default_rng(seed)
    n_pages = b * nb + n_extra
    perm = rng.permutation(n_pages)[:b * nb].reshape(b, nb)
    kp = rng.standard_normal((n_pages, ps, hkv, dh)).astype(np.float32)
    vp = rng.standard_normal((n_pages, ps, hkv, dh)).astype(np.float32)
    kd, vd = np.asarray(k), np.asarray(v)
    for i in range(b):
        for j in range(nb):
            rows = min(ps, skv - j * ps)        # partial last page: the
            kp[perm[i, j], :rows] = kd[i, j * ps:j * ps + rows]
            vp[perm[i, j], :rows] = vd[i, j * ps:j * ps + rows]
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(perm, jnp.int32)


PAGED_CASES = [
    # (b, skv, h, hkv, dh, window, ps, n_splits, pos)
    (2, 64, 4, 2, 32, None, 16, 1, 40),            # GQA g=2
    (2, 64, 8, 2, 32, None, 8, 2, 63),             # g=4, splits
    (3, 80, 4, 1, 32, None, 16, 2, [3, 40, 79]),   # MQA, ragged pos
    (2, 96, 4, 4, 64, 24, 16, 3, [10, 90]),        # sliding window
    (2, 50, 4, 2, 32, 16, 16, 1, 49),              # partial last page
]


@pytest.mark.parametrize("b,skv,h,hkv,dh,window,ps,ns,pos", PAGED_CASES)
def test_flash_decode_paged_vs_dense(b, skv, h, hkv, dh, window, ps, ns,
                                     pos):
    q, k, v = _rand_case(b, skv, h, hkv, dh)
    pos = jnp.asarray(pos, jnp.int32)
    kp, vp, bt = _paginate(k, v, ps)
    got = D.flash_decode_paged(q, kp, vp, bt, pos, window=window,
                               n_splits=ns, interpret=True)
    ref = A.decode_attention(q, k, v, pos, window=window)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_flash_decode_paged_multi_token():
    """Sq>1 against the page pool: causal among the query tokens."""
    b, skv, h, hkv, dh, sq, pos0 = 2, 64, 4, 2, 32, 3, 17
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, sq, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, hkv, dh), jnp.float32)
    kp, vp, bt = _paginate(k, v, 8)
    got = D.flash_decode_paged(q, kp, vp, bt, jnp.int32(pos0),
                               n_splits=2, interpret=True)
    ref = A.dense_causal_attention(q, k[:, :pos0 + sq], v[:, :pos0 + sq],
                                   q_offset=pos0)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_ref_decode_paged_matches_dense():
    """The pure-JAX paged oracle (the serve path off-TPU) is exact."""
    q, k, v = _rand_case(2, 64, 4, 2, 32, seed=2)
    pos = jnp.asarray([5, 30], jnp.int32)
    kp, vp, bt = _paginate(k, v, 8)
    got = D.ref_decode_paged(q, kp, vp, bt, pos)
    ref = A.decode_attention(q, k, v, pos)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_paged_ops_routing_and_page_bound():
    """ops.flash_decode_paged: every impl, with and without a kv_len
    occupancy bound (sliced at page granularity), same numerics."""
    q, k, v = _rand_case(2, 64, 4, 2, 32, seed=3)
    pos = jnp.asarray([9, 21], jnp.int32)
    kp, vp, bt = _paginate(k, v, 8)
    ref = A.decode_attention(q, k, v, pos)
    for impl in ("ref", "auto", "pallas"):
        for kv_len in (None, 22, 40):
            got = kops.flash_decode_paged(q, kp, vp, bt, pos, impl=impl,
                                          kv_len=kv_len)
            np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{impl}/{kv_len}")


@pytest.mark.parametrize("layer", [1, 2])
def test_flash_decode_paged_reads_its_layer_of_the_stack(layer):
    """Layer-stacked pools (R, P, page, Hkv, Dh): the kernel, its ref
    twin and the routed entry read the pages of layer ``layer`` where
    they lie, and nothing of the other layers (which hold garbage)."""
    q, k, v = _rand_case(2, 64, 8, 2, 32, seed=4)
    pos = jnp.asarray([20, 39], jnp.int32)
    kp, vp, bt = _paginate(k, v, 8)
    rng = np.random.default_rng(3)
    ks = rng.standard_normal((3,) + kp.shape).astype(np.float32)
    vs = rng.standard_normal((3,) + vp.shape).astype(np.float32)
    ks[layer], vs[layer] = kp, vp
    ks, vs, lyr = jnp.asarray(ks), jnp.asarray(vs), jnp.int32(layer)
    ref = A.decode_attention(q, k, v, pos)
    for ns in (1, 2):
        got = D.flash_decode_paged(q, ks, vs, bt, pos, layer=lyr,
                                   n_splits=ns, interpret=True)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    got = D.ref_decode_paged(q, ks, vs, bt, pos, layer=lyr)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    for impl in ("ref", "pallas"):
        got = kops.flash_decode_paged(q, ks, vs, bt, pos, layer=lyr,
                                      impl=impl, kv_len=40)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5,
                                   err_msg=impl)
    got = A.decode_attention(q, ks, vs, pos, block_tables=bt, layer=lyr)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_paged_dead_table_columns_are_masked():
    """Columns past a slot's live pages may hold *any* valid page id
    (the engine maps them to the scratch page; recycled tables may
    alias other slots' pages) — logical-position masking must zero
    them regardless of what they point at."""
    q, k, v = _rand_case(2, 64, 4, 2, 32, seed=5)
    pos = jnp.asarray([9, 21], jnp.int32)       # live pages: 2 and 3
    kp, vp, bt = _paginate(k, v, 8)
    rng = np.random.default_rng(11)
    bad = np.asarray(bt).copy()
    for i, live in enumerate([2, 3]):
        bad[i, live:] = rng.integers(0, kp.shape[0], bad.shape[1] - live)
    ref = A.decode_attention(q, k, v, pos)
    for ns in (1, 2):
        got = D.flash_decode_paged(q, kp, vp, jnp.asarray(bad, jnp.int32),
                                   pos, n_splits=ns, interpret=True)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# --- MemTier-driven autotuner ----------------------------------------------

def test_autotuned_tiles_differ_across_machines():
    """The acceptance pin: tiling must be derived from the ladders, so at
    least two registered machines must disagree — for both kernels."""
    shape = dict(s=4096, dh=64, h=8, hkv=8)
    flash = {name: tuning.flash_tiles(name, **shape)
             for name in ("tpu_v5e", *PAPER_CPUS)}
    assert len({(p.bq, p.bk) for p in flash.values()}) >= 2, flash
    dshape = dict(skv=4096, dh=64, h=8, hkv=2, batch=4)
    dec = {name: tuning.decode_tiles(name, **dshape)
           for name in ("tpu_v5e", *PAPER_CPUS)}
    assert len({(p.bk, p.n_splits) for p in dec.values()}) >= 2, dec


def test_autotuner_reads_the_ladder_not_constants():
    """A 128 MB-VMEM TPU keeps its score tile on-chip; the paper CPUs
    spill it to a cache level — and the many-core sockets shard the KV
    stream over splits while single-core machines must not."""
    tpu = tuning.flash_tiles("tpu_v5e", s=4096, dh=64, h=8, hkv=8)
    z4 = tuning.flash_tiles("zen4", s=4096, dh=64, h=8, hkv=8)
    assert tpu.home_tier == "VMEM"
    assert z4.home_tier in ("L1", "L2")
    assert z4.ws_bytes < tpu.ws_bytes      # pushed to a smaller tile
    tpu_d = tuning.decode_tiles("tpu_v5e", skv=4096, dh=64, h=8, hkv=2,
                                batch=4)
    z4_d = tuning.decode_tiles("zen4", skv=4096, dh=64, h=8, hkv=2,
                               batch=4)
    assert tpu_d.n_splits == 1             # one core drives the grid
    assert z4_d.n_splits > 1               # 96-core socket shards KV


def test_autotuned_defaults_replace_hardcoded_512s():
    """ops.flash_attention with no explicit tiles must run the autotuned
    plan (pinned by numerics parity at a shape where 512 won't divide)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 4, 160, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 160, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 160, 32), jnp.float32)
    out = kops.flash_attention(q, k, v, impl="pallas")
    ref = kops.flash_attention(q, k, v, impl="ref")
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_fit_block_snaps_to_largest_divisor():
    """Autotuned tiles must snap to *large* divisors of s, and the raw
    kernel must accept its own defaults at lengths the 512s divided."""
    assert tuning.fit_block(1024, 1536) == 768
    assert tuning.fit_block(256, 1000) == 250      # gcd would give 8
    assert tuning.fit_block(512, 512) == 512
    assert tuning.fit_block(64, 7) == 7
    from repro.kernels.attention import flash as F
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (1, 1, 1536, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, 1536, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, 1536, 16), jnp.float32)
    out = F.flash_attention(q, k, v, interpret=True)   # default tiles
    from repro.kernels.attention import ref as R
    np.testing.assert_allclose(out, R.attention(q, k, v),
                               rtol=2e-5, atol=2e-5)


def test_reported_plan_matches_executed_plan():
    """decode_read_traffic / planner must price the tiling the kernel
    path actually runs: tuned at the occupancy bound, not the horizon."""
    from repro.serve.kv_traffic import bounded_decode_plan
    cfg = get_smoke_config("yi-9b")
    batch, max_len, occ = 4, 2048, 65
    plan, bound = bounded_decode_plan(cfg, batch, max_len, occ, "zen4")
    executed = tuning.decode_tiles(
        "zen4", skv=occ, dh=cfg.head_dim_eff, h=cfg.n_heads,
        hkv=cfg.n_kv_heads, batch=batch, dtype=cfg.param_dtype)
    assert (plan.bk, plan.n_splits) == (executed.bk, executed.n_splits)
    assert bound == min(-(-occ // executed.bk) * executed.bk, max_len)
    row = decode_read_traffic(cfg, batch, max_len, occ,
                              machines=("zen4",))[0]
    assert row["bk"] == executed.bk
    assert row["split_read_bytes"] == pytest.approx(
        bound / max_len * row["dense_read_bytes"])


# --- planner memoization + kernel pricing ----------------------------------

def test_plan_chunk_size_memoized(monkeypatch):
    cfg = get_smoke_config("yi-9b")
    planner_lib.clear_plan_cache()
    calls = {"n": 0}
    real = portmodel.compare

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(planner_lib.portmodel, "compare", counting)
    p1 = plan_chunk_size(cfg, 2, 32)
    assert calls["n"] == 1
    p2 = plan_chunk_size(cfg, 2, 32)
    assert calls["n"] == 1                  # repeat admission: O(1) hit
    assert p2 is p1
    # a different shape is a different key, not a stale hit
    plan_chunk_size(cfg, 2, 48)
    assert calls["n"] == 2


def test_plan_kernel_pricing_occupancy_bounded():
    """With an occupancy bound the planner re-prices the KV stream: the
    kernel-path step can only get cheaper, and the dense table rides
    along for reporting."""
    cfg = get_smoke_config("yi-9b")
    planner_lib.clear_plan_cache()
    dense = plan_chunk_size(cfg, 4, 256)
    kern = plan_chunk_size(cfg, 4, 256, occupancy=32)
    assert kern.occupancy == 32 and dense.occupancy is None
    assert kern.per_machine_dense is not None
    for name, t in kern.per_machine.items():
        assert t <= kern.per_machine_dense[name] + 1e-15, name
    assert kern.t_step_seconds <= dense.t_step_seconds + 1e-15


def test_decode_read_traffic_ratio_gt1_on_paper_cpus():
    """Acceptance: dense/split KV-read ratio > 1 on all three paper CPUs
    (and exactly 1 only when the cache is full)."""
    cfg = get_smoke_config("yi-9b")
    rows = {r["machine"]: r
            for r in decode_read_traffic(cfg, 4, 512, 64)}
    for name in PAPER_CPUS:
        assert rows[name]["read_ratio"] > 1, rows[name]
        assert rows[name]["split_read_bytes"] < rows[name]["dense_read_bytes"]
    full = decode_read_traffic(cfg, 4, 512, 512)
    assert all(r["read_ratio"] == 1.0 for r in full)


# --- serve decode step with the kernel routed in ---------------------------

def test_serve_chunked_decode_in_place_with_kernel():
    """HLO check: routing the split-KV kernel into the serve chunked
    decode step must not break cache donation — the per-token KV
    dynamic-update-slice still happens in place."""
    cfg = get_smoke_config("yi-9b")
    b, horizon = 2, 24
    step = make_chunked_decode_step(cfg, 2, attn_impl="pallas",
                                    kv_len=horizon)
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
    assert "input_output_alias" in donated
    assert len(arg_copies(donated)) == 0    # in-place with donation
    assert "dynamic-update-slice" in donated


def test_chunked_decode_kernel_path_token_parity():
    """The kernel-routed chunked decode emits the same tokens as the
    dense path (greedy, per-slot positions)."""
    cfg = get_smoke_config("yi-9b")
    b, horizon, n = 2, 24, 3
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    cache = M.init_cache(cfg, b, horizon)
    tok = jnp.asarray([[3], [7]], jnp.int32)
    pos = jnp.asarray([0, 4], jnp.int32)
    dense = make_chunked_decode_step(cfg, n)
    routed = make_chunked_decode_step(cfg, n, attn_impl="auto",
                                      kv_len=pos.max().item() + n)
    t0, _, _ = jax.jit(dense)(params, cache, tok, pos, key)
    t1, _, _ = jax.jit(routed)(params, M.init_cache(cfg, b, horizon),
                               tok, pos, key)
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))
