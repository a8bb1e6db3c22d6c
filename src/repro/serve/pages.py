"""Paged KV cache: a fixed pool of physical pages, per-slot block
tables, refcounted prefix sharing, and copy-on-write.

The dense engine (repro.serve.engine) preallocates every slot at the
full decode horizon, so memory scales with ``slots x horizon`` no
matter how short the live requests are, and every admission zero-fills
a horizon's worth of cache rows — a system-scale write allocate. Here
the KV buffers are cut into fixed-size **pages** (vLLM-style): each
attention layer's K/V leaf becomes a physical pool ``(P, page, Hkv,
Dh)`` shared by all slots, and a per-slot **block table** maps logical
page ``i`` (cache rows ``i*page .. (i+1)*page-1``) to whichever
physical page holds it. Three WA-evasion-flavored consequences:

* **Memory scales with live tokens** — a slot holds exactly
  ``ceil(occupancy / page)`` pages, not a horizon.
* **Admission skips the zero-fill** — a recycled page is overwritten
  in place (stale rows are masked by position, exactly like the dense
  cache's unwritten horizon); only the pool's one-time init pays a
  zero store. The never-zero-a-page-you-overwrite rule is the paper's
  never-move-bytes-you-don't-need lesson applied to stores.
* **Common prefixes are shared** — full prompt pages are
  content-addressed (a hash chain over page token tuples), so two
  requests with the same system prompt map the same physical pages
  and admission copies zero pages; a divergent write to a shared page
  triggers copy-on-write (:meth:`PagePool.prepare_write`).

:class:`PagePool` is pure host-side bookkeeping (refcounts, free list,
prefix index); the device-side steps (:func:`make_paged_insert_step`,
:func:`make_page_copy_step`) are built here and jitted by the engine.
Pricing for the new traffic classes (page-gather reads, CoW copies,
recycled-vs-zero-fill admission) lives in ``repro.serve.kv_traffic``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.model import kv_leaf_flags
from repro.serve.slots import SLOT_AXIS


def pages_per_slot(max_len: int, page_size: int) -> int:
    """Block-table width: logical pages covering the decode horizon."""
    return math.ceil(max_len / page_size)


def paged_cache_shapes(cfg: ModelConfig, max_slots: int, n_pages: int,
                       page_size: int) -> dict:
    """ShapeDtypeStruct tree of the paged decode cache.

    KV leaves become physical pools ``(n_pages, page, Hkv, Dh)``
    (scan-stacked ``(R, n_pages, page, Hkv, Dh)``) — their size is set
    by the *pool*, not by ``slots x horizon``. Recurrent leaves keep
    the dense slot-batched shapes.
    """
    flags = kv_leaf_flags(cfg)
    kv = M.cache_shapes(cfg, n_pages, page_size)
    slot = M.cache_shapes(cfg, max_slots, 1)
    return jax.tree.map(lambda f, a, b: a if f else b, flags, kv, slot)


def init_paged_cache(cfg: ModelConfig, max_slots: int, n_pages: int,
                     page_size: int) -> dict:
    """Zero-filled paged cache matching :func:`paged_cache_shapes`.

    This is the pool's *one-time* zero store; recycled pages are never
    re-zeroed (:class:`PagePool` hands them out stale, admission
    overwrites them in place).
    """
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_shapes(cfg, max_slots, n_pages,
                                           page_size))


def paged_cache_pspecs(cfg: ModelConfig, rules: dict, mesh_sizes: dict,
                       max_slots: int, n_pages: int,
                       page_size: int) -> dict:
    """PartitionSpec tree matching :func:`paged_cache_shapes`.

    KV pool leaves ``(P, page, Hkv, Dh)`` keep the pool and page dims
    resident (every shard must see every block-table entry; the gather
    is the kernel's job) and put the TP split on ``kvheads`` — the
    ``batch``/``kv_seq`` rules are masked out so the dense cache rules
    can never claim the pool dims. Recurrent leaves keep their dense
    slot-batched specs.
    """
    flags = kv_leaf_flags(cfg)
    pool_rules = dict(rules, batch=(), kv_seq=())
    kv = M.cache_pspecs(cfg, pool_rules, mesh_sizes, n_pages, page_size)
    slot = M.cache_pspecs(cfg, rules, mesh_sizes, max_slots, 1)
    return jax.tree.map(lambda f, a, b: a if f else b, flags, kv, slot)


def paged_kv_bytes(cfg: ModelConfig, n_pages: int, page_size: int) -> int:
    """Total bytes of the KV page pools (fig8's peak-memory quantity)."""
    flags = kv_leaf_flags(cfg)
    shapes = M.cache_shapes(cfg, n_pages, page_size)
    tot = 0
    for f, s in zip(jax.tree.leaves(flags), jax.tree.leaves(shapes)):
        if f:
            tot += math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
    return tot


def dense_kv_bytes(cfg: ModelConfig, max_slots: int, max_len: int) -> int:
    """KV bytes of the dense slot cache at the same shapes (baseline)."""
    flags = kv_leaf_flags(cfg)
    shapes = M.cache_shapes(cfg, max_slots, max_len)
    tot = 0
    for f, s in zip(jax.tree.leaves(flags), jax.tree.leaves(shapes)):
        if f:
            tot += math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
    return tot


class PoolExhausted(RuntimeError):
    """Raised by :meth:`PagePool.allocate` when no page can be handed out.

    A ``RuntimeError`` subclass so existing callers keep working; the
    fault-tolerant serve path (``repro.serve.health``) catches it
    specifically and treats admission-time exhaustion as a transient,
    retryable overload signal — pages come back as requests retire.
    """


class PagePool:
    """Host-side physical page allocator with refcounted prefix sharing.

    Every page has a refcount: one per block-table entry holding it,
    plus one when the prefix index retains it as shareable. Full
    prompt pages are registered under a content hash chain —
    ``key_i = (key_{i-1}, tokens of page i)`` — so a later admission
    with the same prompt prefix maps the same physical pages
    (:meth:`match_prefix`, zero copies). Retained pages survive their
    last holder (an index cache) and are evicted LRU only when the
    free list runs dry, which is also where **recycling** happens:
    reallocated pages keep their stale contents (stale rows are masked
    by position), skipping the zero-fill a dense admission pays.

    Writes go through :meth:`prepare_write`: an exclusively-held page
    is written in place; a shared one is copy-on-wrote to a fresh page
    (the caller performs the device copy). ``stats`` counts the events
    fig8 gates on (shared maps, CoW copies, recycled vs fresh
    allocations, evictions).
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError("n_pages and page_size must be >= 1")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.refcount = [0] * self.n_pages
        self._free = list(range(self.n_pages - 1, -1, -1))  # pop() -> 0,1,..
        self._used = [False] * self.n_pages   # ever allocated (recycling)
        self._chains: dict = {}               # chain key -> phys page
        self._page_key: dict = {}             # phys page -> chain key
        self._retained: dict = {}             # phys -> key, LRU order
        self.stats = {"shared_maps": 0, "cow_copies": 0,
                      "fresh_allocs": 0, "recycled_allocs": 0,
                      "evictions": 0}

    # -- allocation ---------------------------------------------------------
    def available(self) -> int:
        """Pages an ``allocate`` call could hand out right now."""
        evictable = sum(1 for p in self._retained if self.refcount[p] == 1)
        return len(self._free) + evictable

    def allocate(self, n: int) -> list:
        """Take ``n`` exclusive pages (refcount 1 each), recycling
        stale pages and evicting index-retained ones LRU if needed."""
        out = []
        for _ in range(int(n)):
            if self._free:
                p = self._free.pop()
            else:
                p = self._evict_retained()
            self.refcount[p] = 1
            key = "recycled_allocs" if self._used[p] else "fresh_allocs"
            self.stats[key] += 1
            self._used[p] = True
            out.append(p)
        return out

    def _evict_retained(self) -> int:
        for p in list(self._retained):        # insertion order = LRU
            if self.refcount[p] == 1:         # only the index holds it
                self._unregister(p)
                self.refcount[p] = 0
                self.stats["evictions"] += 1
                return p
        raise PoolExhausted(
            f"page pool exhausted ({self.n_pages} pages, none evictable)")

    def release(self, pages) -> None:
        """Drop one reference per page; refcount-0 pages go back to the
        free list (still registered pages stay retained instead)."""
        for p in pages:
            p = int(p)
            if self.refcount[p] <= 0:
                raise RuntimeError(f"release of unheld page {p}")
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self._unregister(p)
                self._free.append(p)

    # -- prefix sharing -----------------------------------------------------
    @staticmethod
    def _chain(prev, tokens) -> tuple:
        return (prev, tuple(tokens))

    def match_prefix(self, prompt) -> list:
        """Physical pages of the longest registered full-page prefix of
        ``prompt``; takes one reference per matched page (the caller
        owns them as the head of its block table)."""
        ps = self.page_size
        out, key = [], None
        for i in range(len(prompt) // ps):
            key = self._chain(key, prompt[i * ps:(i + 1) * ps])
            p = self._chains.get(key)
            if p is None:
                break
            out.append(p)
        for p in out:
            self.refcount[p] += 1
            if p in self._retained:           # refresh LRU recency
                k = self._retained.pop(p)
                self._retained[p] = k
        self.stats["shared_maps"] += len(out)
        return out

    def register_prefix(self, prompt, chain_pages) -> None:
        """Register a request's *full* prompt pages as shareable.

        ``chain_pages`` are the request's block-table head in logical
        order (matched + fresh). The index takes its own reference on
        each newly registered page, so the prefix stays shareable
        after the request retires — until pool pressure evicts it.
        """
        ps = self.page_size
        key = None
        for i, p in enumerate(chain_pages):
            key = self._chain(key, prompt[i * ps:(i + 1) * ps])
            if key in self._chains:
                continue                      # already shared
            self._chains[key] = p
            self._page_key[p] = key
            self._retained[p] = key
            self.refcount[p] += 1

    def _unregister(self, p: int) -> None:
        key = self._page_key.pop(p, None)
        if key is not None:
            self._chains.pop(key, None)
        self._retained.pop(p, None)

    # -- sharing / CoW ------------------------------------------------------
    def fork(self, pages) -> None:
        """Share every page of a live request with a clone (refcount++
        including partial pages — first divergent write CoWs)."""
        for p in pages:
            self.refcount[int(p)] += 1

    def prepare_write(self, phys: int) -> tuple:
        """Exclusive page for an in-place write: ``(page, copied)``.

        An exclusively-held page comes straight back. A page retained
        only by the prefix index is unregistered (its content is about
        to change) and written in place. A page with other live
        holders is copy-on-wrote: a fresh page is allocated, the
        caller's reference moves to it, and the caller must device-copy
        the old contents before writing (``copied=True``).
        """
        phys = int(phys)
        rc = self.refcount[phys]
        retained = phys in self._retained
        if rc <= 0:
            raise RuntimeError(f"prepare_write on unheld page {phys}")
        if rc == 1 and not retained:
            return phys, False
        if rc == 2 and retained:
            self._unregister(phys)
            self.refcount[phys] -= 1
            return phys, False
        new = self.allocate(1)[0]
        self.refcount[phys] -= 1
        self.stats["cow_copies"] += 1
        return new, True

    # -- invariants ---------------------------------------------------------
    def check_conservation(self, tables) -> None:
        """Assert pool conservation against the live block tables.

        ``tables`` is an iterable of per-request page lists. Every
        page's refcount must equal its live holders plus its index
        retention; free pages must be unheld and refcount 0; every
        page must be either free or referenced. Raises AssertionError
        with the offending page on violation.
        """
        held: dict = {}
        for t in tables:
            for p in t:
                held[int(p)] = held.get(int(p), 0) + 1
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("duplicate pages on the free list")
        for p in range(self.n_pages):
            want = held.get(p, 0) + (1 if p in self._retained else 0)
            if self.refcount[p] != want:
                raise AssertionError(
                    f"page {p}: refcount {self.refcount[p]} != "
                    f"{held.get(p, 0)} holders + "
                    f"{int(p in self._retained)} retained")
            if p in free and (self.refcount[p] != 0 or p in held):
                raise AssertionError(f"page {p} free but referenced")
            if p not in free and self.refcount[p] == 0:
                raise AssertionError(f"page {p} leaked (unreferenced, "
                                     "not free)")


# ---------------------------------------------------------------------------
# Device-side steps (jitted by the engine)
# ---------------------------------------------------------------------------

def make_paged_insert_step(cfg: ModelConfig, page_size: int):
    """Build ``insert(cache, one, slot, phys, logical) -> cache``.

    ``one`` is a batch-1 prefill cache built at *exactly* the prompt
    length (``make_prefill_step(cfg, cache_len=None)`` — no horizon
    zero-fill). Its KV rows are cut into pages and scattered to the
    ``phys`` physical pages named by the ``logical`` page indices
    (shared prefix pages are simply omitted from both arrays — zero
    copies for shared content). Recurrent leaves are slot-inserted as
    in the dense engine. Donate ``cache`` at the jit boundary.
    """
    flags = kv_leaf_flags(cfg)
    ps = int(page_size)

    def insert(cache, one, slot, phys, logical):
        out = {}
        for part, axis in SLOT_AXIS.items():
            if part not in cache:
                continue

            def upd(big, small, iskv, a=axis):
                if not iskv:
                    return jax.lax.dynamic_update_slice_in_dim(
                        big, small.astype(big.dtype), slot, axis=a)
                if a == 0:       # tail: small (1, S, Hkv, Dh)
                    s = small.shape[1]
                    npg = -(-s // ps)
                    rows = jnp.pad(small, [(0, 0), (0, npg * ps - s),
                                           (0, 0), (0, 0)])
                    rows = rows.reshape((npg, ps) + small.shape[2:])
                    return big.at[phys].set(
                        rows[logical].astype(big.dtype))
                # scan: small (R, 1, S, Hkv, Dh)
                s = small.shape[2]
                npg = -(-s // ps)
                rows = jnp.pad(small, [(0, 0), (0, 0), (0, npg * ps - s),
                                       (0, 0), (0, 0)])
                rows = rows.reshape((small.shape[0], npg, ps)
                                    + small.shape[3:])
                return big.at[:, phys].set(
                    rows[:, logical].astype(big.dtype))

            out[part] = jax.tree.map(upd, cache[part], one[part],
                                     flags[part])
        return out

    return insert


def make_page_copy_step(cfg: ModelConfig):
    """Build ``copy(cache, src, dst) -> cache`` — the CoW device copy.

    Copies physical page ``src`` to ``dst`` in every KV leaf (all
    attention layers, K and V); recurrent leaves pass through. ``src``
    and ``dst`` are traced scalars, so one compilation serves every
    copy. Donate ``cache`` at the jit boundary.
    """
    flags = kv_leaf_flags(cfg)

    def copy(cache, src, dst):
        out = {}
        for part, axis in SLOT_AXIS.items():
            if part not in cache:
                continue

            def upd(big, iskv, a=axis):
                if not iskv:
                    return big
                if a == 0:
                    return big.at[dst].set(big[src])
                return big.at[:, dst].set(big[:, src])

            out[part] = jax.tree.map(upd, cache[part], flags[part])
        return out

    return copy


def make_slot_copy_step(cfg: ModelConfig):
    """Build ``copy(cache, src, dst) -> cache`` for recurrent leaves.

    A fork shares KV via the block table, but slot-batched recurrent
    state (mamba/xLSTM) must be duplicated into the clone's slot row.
    KV page pools pass through untouched. Donate ``cache``.
    """
    flags = kv_leaf_flags(cfg)

    def copy(cache, src, dst):
        out = {}
        for part, axis in SLOT_AXIS.items():
            if part not in cache:
                continue

            def upd(big, iskv, a=axis):
                if iskv:
                    return big
                row = jax.lax.dynamic_slice_in_dim(big, src, 1, axis=a)
                return jax.lax.dynamic_update_slice_in_dim(big, row, dst,
                                                           axis=a)

            out[part] = jax.tree.map(upd, cache[part], flags[part])
        return out

    return copy
