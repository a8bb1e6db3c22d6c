"""Continuous-batching serve engine: fixed KV slots, admit/evict per
decode round, chunked in-graph decode.

Life of a request: it waits in the pending queue until a slot frees,
is prefilled (batch=1, cache built directly at the full horizon) and
inserted into its slot in place, then decodes along with every other
active slot — each at its own position — in multi-token chunks. When its
budget is spent it retires and the slot is free for the next admission;
the big slot cache is never reallocated, regrown, or recompiled as the
batch composition changes.

Two cache layouts share the engine skeleton:

- :class:`ServeEngine` — dense per-slot KV: every slot owns a
  ``max_len`` stripe of the cache, zero-filled to the horizon at
  admission regardless of how much of it the request will use.
- :class:`PagedServeEngine` — paged KV (repro.serve.pages): attention
  KV lives in fixed-size physical pages mapped through per-slot block
  tables. Pages are allocated lazily as positions advance, identical
  prompt prefixes share pages by refcount (copy-on-write on first
  divergent write), and retiring a request returns its pages without
  any zero-fill — recycled pages keep stale rows, masked by position,
  which is the serve-scale write-allocate-evasion story (DESIGN.md).

Numerical caveat: slots are independent streams for every per-row mixer
(attention, mamba, xLSTM). MoE blocks with finite capacity couple rows
through expert capacity — serve MoE configs with a generous
``capacity_factor`` if bit-exact per-request streams matter (and note
prefix sharing reuses KV computed in a *different* prefill batch, so
shared-prefix determinism also assumes dense FFNs).
"""

from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.attention.ops import validate_tp_heads
from repro.models import model as M
from repro.serve import pages as pages_lib
from repro.serve.decode import make_chunked_decode_step
from repro.serve.planner import plan_chunk_size
from repro.serve.slots import make_insert_step
from repro.serve.spans import OFF, note, span
from repro.serve.staging import PromptStager
from repro.train import serve as serve_lib
from repro.utils.sharding import (SERVE_ENGINE_RULES, mesh_axis_sizes,
                                  named_shardings, tp_degree,
                                  use_mesh_rules)


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request: prompt token ids and a token budget.

    ``deadline_s`` is an optional completion budget in seconds
    *relative to submission* (virtual-clock seconds under the load
    harness). Engines ignore it; the fault-tolerant router
    (repro.serve.health) sheds queued requests and cancels active ones
    once their budget is spent. ``None`` means no deadline.
    """

    rid: str
    prompt: tuple                 # prompt token ids
    max_new_tokens: int
    deadline_s: float | None = None


@dataclasses.dataclass
class _Slot:
    rid: str
    remaining: int                # tokens still owed to this request
    out: list                     # tokens emitted so far


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unconsumed decode round (pipelined mode).

    ``toks``/``ok`` are *device* arrays — touching them with
    ``np.asarray`` is the readback the pipeline defers. ``entries``
    snapshots which slot objects the round decoded and how many of its
    tokens each one keeps (``take``); the identity of the ``_Slot``
    reference is what lets a later consume skip rounds belonging to a
    stream that was quarantined in an earlier buffered round.
    """

    toks: object                  # (B, chunk) device int32
    ok: object | None             # (B,) device bool, or None (no guard)
    entries: list                 # [(slot index, _Slot, take)]
    chunk: int                    # chunk size this round was decoded at


class ServeEngine:
    """Continuous-batching engine over ``max_slots`` preallocated KV slots.

    ``chunk`` tokens are decoded per dispatch; when omitted the chunk size
    is planned analytically from the port model's tier-resolved per-step
    cost (repro.serve.planner). Prefill compiles once per distinct prompt
    length (jit's own shape-keyed cache); decode and slot-insert compile
    exactly once. ``run(requests)`` drives admit -> decode-chunk -> retire
    rounds until every request has its tokens.

    Subclass hooks (`PagedServeEngine` overrides all five): `_make_plan`
    prices the chunk, `_build_state` allocates the cache and jits the
    dispatch steps, `_insert_prefilled` lands one prefilled request in a
    slot, `_pre_dispatch` runs host-side bookkeeping before each chunk,
    `_dispatch` issues it, `_release_slot` retires a slot.
    """

    paged = False

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 max_len: int, chunk: int | None = None,
                 temperature: float = 0.0, seed: int = 0,
                 machine: str | None = None,
                 attn_impl: str | None = None,
                 kv_len: int | None = None,
                 store_flavor: str = "auto",
                 mesh=None, rules: dict | None = None,
                 nonfinite_guard: bool = True,
                 pipeline: bool | int = 0,
                 stage_depth: int = 8,
                 device=None):
        assert cfg.embed_inputs, "serve engine needs a token-id model"
        if device is not None and mesh is not None:
            raise ValueError("pass a mesh or a device, not both")
        self.cfg, self.params = cfg, params
        self.max_slots, self.max_len = max_slots, max_len
        self.temperature = float(temperature)
        # pipelined (double-buffered) dispatch: True -> depth 2, an int
        # sets the in-flight round bound explicitly, 0/False keeps the
        # historical serial step. See step()/sync() for the contract.
        self.pipeline = 2 if pipeline is True else max(0, int(pipeline))
        self._inflight: deque = deque()   # _InFlight records, oldest first
        self._tok_dev = None              # device (B,1) next-token feed
        self._ok_dev = None               # device (B,) flags, last dispatch
        # async H2D prompt staging (repro.serve.staging): stage() ahead
        # of admission, admit() takes the already-resident array
        self.stager = PromptStager(depth=stage_depth, device=device)
        # the non-finite guard makes every decode chunk also return a
        # per-slot isfinite flag (serve.decode guard=): a slot whose
        # logits went NaN/inf is quarantined — removed from its slot
        # with its pre-chunk tokens parked on ``self.quarantined`` —
        # instead of silently self-feeding garbage or poisoning the
        # batch. One cheap jit-fused reduce per in-graph step.
        self.nonfinite_guard = bool(nonfinite_guard)
        self.quarantined: list = []   # (rid, tokens-so-far) pairs
        # attn_impl routes decode attention through the split-KV kernel
        # suite; kv_len is a static occupancy bound for the engine's
        # lifetime (no request may decode past it) — when set, the
        # planner prices the occupancy-bounded kernel step instead of
        # the dense full-horizon one.
        self.attn_impl, self.kv_len = attn_impl, kv_len
        # store_flavor picks the KV-writer store path
        # (repro.kernels.stores): "auto" records the per-machine
        # selection on the plan but executes NT kernels only on a real
        # TPU, so off-TPU serving keeps the standard XLA path.
        self.store_flavor = store_flavor
        # mesh=None keeps the single-device path bit-for-bit: every
        # sharding hook below is behind the mesh guard. With a mesh,
        # params/cache are device_put against param_pspecs/cache_pspecs
        # under ``rules`` (SERVE_ENGINE_RULES by default: kvheads -> TP,
        # kv_seq resident), the step functions trace with the ambient
        # mesh+rules installed (sc() constraints go live), and the
        # planner prices the per-shard KV stream + per-step collective.
        # device pins an unsharded engine (params, cache, staged prompts)
        # to one device — one replica per chip behind the router
        self.mesh, self.device = mesh, device
        self.rules = (rules if rules is not None else SERVE_ENGINE_RULES) \
            if mesh is not None else None
        self._mesh_sizes = mesh_axis_sizes(mesh) if mesh is not None else {}
        self.tp = tp_degree(self._mesh_sizes, self.rules)
        if mesh is not None:
            validate_tp_heads(cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim_eff, self.tp,
                              page_size=getattr(self, "page_size", None))
            self.params = jax.device_put(
                params, named_shardings(mesh, M.param_pspecs(cfg, self.rules,
                                                    self._mesh_sizes)))
        elif device is not None:
            self.params = jax.device_put(params, device)
        if chunk is None:
            self.plan = self._make_plan(machine)
            chunk = self.plan.chunk
        else:
            self.plan = None     # explicit chunk: no analytic plan made
        self.chunk = max(1, int(chunk))
        self._build_state()
        self._key = jax.random.PRNGKey(seed)
        self.slots: list = [None] * max_slots
        self._tok = np.zeros((max_slots, 1), np.int32)
        self._pos = np.zeros((max_slots,), np.int32)
        self._last_ok = np.ones((max_slots,), bool)
        self.decode_dispatches = 0
        self.prefill_dispatches = 0

    # -- layout hooks -------------------------------------------------------
    def _make_plan(self, machine):
        """Analytic chunk plan for this cache layout."""
        return plan_chunk_size(self.cfg, self.max_slots, self.max_len,
                               machine=machine, occupancy=self.kv_len,
                               store_flavor=self.store_flavor,
                               mesh=self.mesh, rules=self.rules)

    def _traced(self, fn):
        """Install the engine's mesh+rules around ``fn`` for jit tracing.

        jit calls the wrapped function once per trace (including the
        per-prompt-length prefill retraces), so the thread-local
        ``use_mesh_rules`` context is live exactly when the model's
        ``sc()`` constraints are staged. ``mesh=None`` returns ``fn``
        untouched — the unsharded engine traces the very same function
        object it always did.
        """
        if self.mesh is None:
            return fn
        mesh, rules = self.mesh, self.rules

        def wrapped(*a, **kw):
            with mesh, use_mesh_rules(mesh, rules):
                return fn(*a, **kw)
        return wrapped

    def _shard_cache(self, cache, pspecs):
        """Commit a fresh cache to its mesh layout or its device."""
        if self.mesh is not None:
            return jax.device_put(cache, named_shardings(self.mesh, pspecs))
        if self.device is not None:
            return jax.device_put(cache, self.device)
        return cache

    def _donate(self) -> tuple:
        """Cache-donation argnums for the decode jit, mode-dependent.

        Serial mode donates the cache: the KV update happens in place,
        one buffer, minimal traffic. Pipelined mode must NOT donate —
        donating a buffer that is still being produced by the previous
        in-flight round forces the runtime to block the *enqueue* until
        the producer completes (measured on this backend: a donated
        chained dispatch serializes entirely), which would silently
        turn the pipeline back into the serial loop. Double-buffering
        therefore pays the classic price: two cache buffers alive and a
        copy-on-update round, in exchange for enqueues that never wait.
        """
        return () if self.pipeline else (1,)

    def _make_decode(self):
        """Jit the chunked decode step for the current ``self.chunk``."""
        return jax.jit(
            self._traced(make_chunked_decode_step(
                self.cfg, self.chunk, self.temperature,
                attn_impl=self.attn_impl, kv_len=self.kv_len,
                store_flavor=self.store_flavor,
                guard=self.nonfinite_guard)),
            donate_argnums=self._donate())

    def set_chunk(self, chunk: int) -> None:
        """Re-plan the decode chunk size mid-flight (degraded mode).

        Only the chunked decode step is re-jitted — the cache, the
        slots, and every in-flight stream are untouched, so the next
        ``step()`` simply decodes ``chunk`` tokens per dispatch. Used
        by the fault-tolerant router's priced degradation
        (``repro.serve.health``): a smaller chunk shortens each round
        (lower per-round latency under deadline pressure) at the cost
        of amortizing dispatch overhead over fewer tokens. Repeated
        sizes hit jit's compilation cache.
        """
        chunk = max(1, int(chunk))
        if chunk == self.chunk:
            return
        self.chunk = chunk
        self._decode = self._make_decode()

    def _build_state(self):
        """Allocate the cache and jit the per-layout dispatch steps."""
        self.cache = self._shard_cache(
            M.init_cache(self.cfg, self.max_slots, self.max_len),
            M.cache_pspecs(self.cfg, self.rules, self._mesh_sizes,
                           self.max_slots, self.max_len)
            if self.mesh is not None else None)
        self._decode = self._make_decode()
        self._insert = jax.jit(self._traced(make_insert_step(self.cfg)),
                               donate_argnums=(0,))
        # jit retraces per prompt length/batch shape on its own — one
        # wrapper serves every admission path
        self._prefill = jax.jit(self._traced(serve_lib.make_prefill_step(
            self.cfg, cache_len=self.max_len,
            store_flavor=self.store_flavor)))

    def _insert_prefilled(self, slot: int, one, prompt) -> int:
        """Land one prefilled (batch-1) request cache in ``slot``;
        returns the prompt tokens mapped from a prefix index (none)."""
        with span("insert"):
            self.cache = self._insert(self.cache, one, jnp.int32(slot))
        return 0

    def _release_slot(self, i: int) -> None:
        """Retire slot ``i`` and free whatever it held."""
        self.slots[i] = None

    def _pre_dispatch(self) -> None:
        """Host-side bookkeeping before a chunk (no-op for dense slots)."""

    def _host_dev(self, arr):
        """Ship one mutable host array to device for a dispatch.

        ``jnp.asarray`` of an aligned numpy buffer may be *zero-copy*
        on CPU, so the enqueued computation reads the live host memory.
        Serial rounds are safe (the readback at the end of the step
        completes the dispatch before any bookkeeping mutates
        ``_pos``/``_tok``), but pipelined rounds mutate both right
        after the enqueue while the round is still in flight — ship a
        snapshot copy instead, or the eager position advance races the
        device reads (observed as timing-dependent stream corruption).
        """
        return jnp.asarray(arr.copy() if self.pipeline else arr)

    def _tok_input(self):
        """Next-token feed for the coming dispatch.

        Serial rounds (and the first pipelined round after a sync)
        ship the host-side ``self._tok``; chained pipelined rounds
        feed the previous round's last-token *device* slice directly,
        so the dispatch never waits for a readback.
        """
        return self._tok_dev if self._tok_dev is not None \
            else self._host_dev(self._tok)

    def _decode_args(self):
        """Positional args of one decode dispatch (before the PRNG key)."""
        return (self.params, self.cache, self._tok_input(),
                self._host_dev(self._pos))

    def _dispatch_raw(self, sub):
        """Enqueue one chunked decode; returns device (toks, ok|None).

        Purely asynchronous: the result arrays are *futures* (jax async
        dispatch) and nothing here blocks on device work. ``self.cache``
        advances to the round's output cache immediately — later
        dispatches, admissions, and page copies chain on it in enqueue
        order. In pipelined mode the last-token slice becomes the next
        round's device-side token feed.
        """
        with span("dispatch", slots=self._n_active, chunk=self.chunk,
                  ctx_tokens=self._ctx_tokens):
            out = self._decode(*self._decode_args(), sub)
        if self.nonfinite_guard:
            toks, self.cache, _, ok = out
        else:
            toks, self.cache, _ = out
            ok = None
        if self.pipeline:
            self._tok_dev = toks[:, self.chunk - 1:self.chunk]
        return toks, ok

    def _dispatch(self, sub):
        """Issue one chunked decode over all slots; returns its (B,
        chunk) tokens on the device, and keeps its per-slot finite
        flags there for the readback."""
        toks, self._ok_dev = self._dispatch_raw(sub)
        return toks

    def _n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def _ctx_tokens(self) -> int:
        return int(sum(int(self._pos[i]) for i, s in enumerate(self.slots)
                       if s is not None))

    def _held_tokens(self) -> int:
        return sum(len(s.out) for s in self.slots if s is not None)

    # -- admission ----------------------------------------------------------
    def free_slots(self) -> list:
        """Indices of slots with no active request."""
        return [i for i, s in enumerate(self.slots) if s is None]

    def _sample_first(self, logits):
        """First output token from the prefill's last-prompt-token logits."""
        with span("first_token"):
            if self.temperature > 0.0:
                self._key, sub = jax.random.split(self._key)
                tok = jax.random.categorical(sub, logits / self.temperature,
                                             axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            return np.asarray(tok, np.int32)

    def _check_request(self, req: Request, prompt_len: int) -> None:
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1 "
                f"(got {req.max_new_tokens})")
        horizon = self.max_len if self.kv_len is None \
            else min(self.max_len, self.kv_len)
        if prompt_len + req.max_new_tokens - 1 > horizon:
            raise ValueError(
                f"request {req.rid}: prompt {prompt_len} + "
                f"{req.max_new_tokens} new tokens exceeds the slot "
                f"horizon {horizon}")
        # out-of-vocab ids don't fail loudly downstream: the jitted
        # embedding gather fills OOB rows with NaN, which poisons the
        # whole stream (and trips the non-finite guard). Reject at
        # admission, where the rid is still attached to the cause.
        if req.prompt and (min(req.prompt) < 0
                           or max(req.prompt) >= self.cfg.vocab_size):
            raise ValueError(
                f"request {req.rid}: prompt ids must be in "
                f"[0, {self.cfg.vocab_size})")

    def stage(self, req: Request) -> bool:
        """Prefetch one pending request's prompt to device (async H2D).

        Called ahead of admission — by ``run()``'s look-ahead, the
        router's ``submit()``, or a rescue replay — so that when a slot
        frees the prompt tokens are already device-resident and
        ``admit()`` skips the host→device copy. Purely an optimization:
        bit-identical whether or not the prompt was staged. Sharded
        engines decline (the jitted prefill shards its own host input);
        returns True iff a new async copy was issued.
        """
        if self.mesh is not None:
            return False
        with span("stage", rid=req.rid, tokens=len(req.prompt)) as sp:
            issued = self.stager.stage(req.rid,
                                       tuple(int(t) for t in req.prompt))
            note(sp, issued=int(issued))
        return issued

    def admit(self, req: Request, slot: int | None = None) -> int:
        """Prefill one request and insert it into a free slot, in place.

        Concurrent with any in-flight pipelined rounds: the prefill and
        slot-insert enqueue *behind* the dispatched decodes, so the
        in-flight writes to this slot's (now retired) stripe or pages
        happen-before the insert in device order — the insert wins.
        The device-side token feed is patched in place so the chained
        dispatch picks up the admission's first token.
        """
        if slot is None:
            free = self.free_slots()
            if not free:
                raise RuntimeError("no free slot")
            slot = free[0]
        assert self.slots[slot] is None, f"slot {slot} busy"
        with span("admit", rid=req.rid, slot=slot,
                  prompt_tokens=len(req.prompt)) as sp:
            prompt = np.asarray(req.prompt, np.int32)
            s = prompt.shape[0]
            self._check_request(req, s)
            prompt_t = tuple(int(t) for t in prompt)
            tokens = prompt[None, :] if self.mesh is not None \
                else self.stager.take(req.rid, prompt_t)
            with span("prefill", tokens=s):
                logits, one = self._prefill(self.params, {"tokens": tokens})
            self.prefill_dispatches += 1
            tok0 = int(self._sample_first(logits[:, -1])[0])
            hit = self._insert_prefilled(slot, one, prompt_t)
            self.slots[slot] = _Slot(rid=req.rid,
                                     remaining=req.max_new_tokens - 1,
                                     out=[tok0])
            self._tok[slot, 0] = tok0
            if self._tok_dev is not None:
                # keep the chained device feed coherent with the host copy
                self._tok_dev = self._tok_dev.at[slot, 0].set(tok0)
            self._pos[slot] = s
            note(sp, prefix_hit_tokens=hit, emitted=1)
        return slot

    def admit_batch(self, reqs: list) -> None:
        """Admit a full batch at once (all slots free, equal prompt lens).

        One batched prefill builds the whole slot cache directly — the
        fast path for the launch driver's fixed-shape batch. Paged
        engines always take the per-request path (admission is where
        prefix matching happens). Falls back to per-request admission
        otherwise.
        """
        lens = {len(r.prompt) for r in reqs}
        if (self.paged or len(reqs) != self.max_slots or len(lens) != 1
                or any(s is not None for s in self.slots)):
            for r in reqs:
                self.admit(r)
            return
        s = lens.pop()
        prompts = np.stack([np.asarray(r.prompt, np.int32) for r in reqs])
        for r in reqs:
            self._check_request(r, s)
        logits, self.cache = self._prefill(self.params, {"tokens": prompts})
        self.prefill_dispatches += 1
        tok0 = self._sample_first(logits[:, -1])
        for i, r in enumerate(reqs):
            self.slots[i] = _Slot(rid=r.rid, remaining=r.max_new_tokens - 1,
                                  out=[int(tok0[i])])
            self._tok[i, 0] = tok0[i]
            self._pos[i] = s

    def drain_quarantined(self) -> list:
        """Return and clear the (rid, tokens-so-far) quarantine list.

        Populated by ``step()`` when the non-finite guard trips; the
        router (``repro.serve.health``) drains it every round to rescue
        the streams on a healthy replica by replaying prompt + prefix.
        """
        out, self.quarantined = self.quarantined, []
        return out

    def cancel(self, rid: str):
        """Abort an active request; returns its tokens so far, or None.

        On the paged engine this is the page-recycling fast path: the
        request's pages go straight back to the pool (no zero-fill, no
        cache traffic at all) and the next admission may recycle them.
        """
        if self._inflight:
            self.sync()          # materialize the stream before returning it
        self.stager.discard(rid)
        for i, st in enumerate(self.slots):
            if st is not None and st.rid == rid:
                out = np.asarray(st.out, np.int32)
                self._release_slot(i)
                return out
        return None

    # -- decode -------------------------------------------------------------
    def step(self) -> list:
        """One decode round: a single chunked dispatch over all slots.

        Returns the requests retired this round as (rid, tokens) pairs.
        With ``pipeline`` enabled the dispatch is double-buffered —
        round N+1 is enqueued while round N's tokens are still in
        flight, and the host only blocks on readback when a stream
        actually retires (or the in-flight bound is hit). Retirement
        and admission timing are identical to the serial step, so token
        streams are byte-for-byte the same in both modes.
        """
        with span("decode") as sp:
            held = self._held_tokens() if sp is not OFF else 0
            q0 = len(self.quarantined)
            retired = self._step_pipelined() if self.pipeline \
                else self._step_serial()
            note(sp, retired=len(retired), emitted=lambda: (
                self._held_tokens() - held
                + sum(len(t) for _, t in retired)
                + sum(len(t) for _, t in self.quarantined[q0:])))
        return retired

    def _step_serial(self) -> list:
        retired = []
        for i, st in enumerate(self.slots):
            if st is not None and st.remaining <= 0:   # 1-token budgets:
                # the prefill already yielded their only token
                retired.append((st.rid, np.asarray(st.out, np.int32)))
                self._release_slot(i)
        if all(s is None for s in self.slots):
            return retired
        self._pre_dispatch()
        self._key, sub = jax.random.split(self._key)
        toks = self._dispatch(sub)
        self.decode_dispatches += 1
        with span("readback"):
            toks = np.asarray(toks)
            if self._ok_dev is not None:
                self._last_ok = np.asarray(self._ok_dev)
        for i, st in enumerate(self.slots):
            if st is None:
                continue
            if not bool(self._last_ok[i]):
                # non-finite logits this chunk: quarantine the request
                # (tokens-so-far, pre-chunk — the chunk's output is
                # garbage) instead of letting it self-feed NaNs. The
                # slot frees immediately; the router decides whether
                # the stream is rescued or reported failed.
                self.quarantined.append(
                    (st.rid, np.asarray(st.out, np.int32)))
                self._release_slot(i)
                continue
            take = min(self.chunk, st.remaining)
            st.out.extend(int(t) for t in toks[i, :take])
            st.remaining -= take
            self._tok[i, 0] = toks[i, self.chunk - 1]
            self._pos[i] += self.chunk
            if st.remaining <= 0:
                retired.append((st.rid, np.asarray(st.out, np.int32)))
                self._release_slot(i)
        return retired

    def _step_pipelined(self) -> list:
        """Double-buffered decode round: enqueue now, read back later.

        The host bookkeeping that *can* run without token values does
        run eagerly — ``remaining`` is decremented and positions advance
        at dispatch time (both are pure arithmetic), so the next round's
        page allocation and retirement *decisions* never wait on the
        device. Only two things force a sync: a stream finishing (its
        tokens must be materialized to be returned) and the in-flight
        bound (consume the oldest round — by then it has been computing
        behind the newer dispatches, so the readback is nearly free).
        Syncing at the retirement round keeps slot-free timing — and
        therefore admission order and the PRNG split sequence —
        identical to the serial step.
        """
        retired = []
        for i, st in enumerate(self.slots):
            if st is not None and st.remaining <= 0:   # 1-token budgets
                self.sync()
                st = self.slots[i]      # sync may have quarantined it
                if st is not None and st.remaining <= 0:
                    retired.append((st.rid, np.asarray(st.out, np.int32)))
                    self._release_slot(i)
        if all(s is None for s in self.slots):
            self.sync()
            return retired
        self._pre_dispatch()
        self._key, sub = jax.random.split(self._key)
        toks, ok = self._dispatch_raw(sub)
        self.decode_dispatches += 1
        entries, will_retire = [], False
        for i, st in enumerate(self.slots):
            if st is None:
                continue
            take = min(self.chunk, st.remaining)
            entries.append((i, st, take))
            st.remaining -= take
            self._pos[i] += self.chunk
            will_retire = will_retire or st.remaining <= 0
        self._inflight.append(_InFlight(toks, ok, entries, self.chunk))
        if will_retire:
            self.sync()
            for i, st in enumerate(self.slots):
                if st is not None and st.remaining <= 0:
                    retired.append((st.rid, np.asarray(st.out, np.int32)))
                    self._release_slot(i)
        else:
            while len(self._inflight) > self.pipeline:
                self._consume_oldest()
        return retired

    def _consume_oldest(self) -> None:
        """Read back the oldest in-flight round and apply its bookkeeping.

        This is the only place pipelined mode touches device results:
        tokens land on each stream's ``out``, the host-side next-token
        feed catches up, and guard trips quarantine exactly as the
        serial step would have — with the one difference that rounds
        dispatched *after* a poisoned one are skipped for that stream
        (their token-0 self-feed output is garbage by construction).
        """
        rec = self._inflight.popleft()
        with span("readback"):
            toks = np.asarray(rec.toks)
            oks = np.asarray(rec.ok) if rec.ok is not None else None
        for i, st, take in rec.entries:
            if self.slots[i] is not st:
                continue            # stream quarantined in an earlier round
            if oks is not None and not bool(oks[i]):
                self._last_ok[i] = False
                self.quarantined.append(
                    (st.rid, np.asarray(st.out, np.int32)))
                self._release_slot(i)
                continue
            st.out.extend(int(t) for t in toks[i, :take])
            self._tok[i, 0] = toks[i, rec.chunk - 1]

    def sync(self) -> None:
        """Drain every in-flight round's deferred host bookkeeping.

        After a sync the engine is exactly where the serial step would
        be: every emitted token is host-resident, the next dispatch
        rebuilds its token feed from ``self._tok``, and quarantine
        lists are complete. Cheap when nothing is in flight.
        """
        while self._inflight:
            self._consume_oldest()
        self._tok_dev = None

    def stats(self) -> dict:
        """Dispatch counters, rounds in flight and the stager's counts.

        Where the time between dispatches goes is read from a profiler
        trace of the ``serve.*`` spans (``repro.serve.spans``).
        """
        return {"decode_dispatches": self.decode_dispatches,
                "prefill_dispatches": self.prefill_dispatches,
                "pipeline": self.pipeline,
                "in_flight": len(self._inflight),
                "staging": self.stager.stats()}

    def snapshot(self, checkpointer, step: int) -> bool:
        """Snapshot the served params without stalling the stream.

        Hands the param tree to the async checkpointer
        (``repro.checkpoint``) with ``skip_if_busy=True``: if the
        previous background write is still running the snapshot is
        *skipped* (returns False) instead of blocking the decode loop
        on disk. In-flight pipelined rounds are untouched — params are
        never donated, so the device-to-host copy the checkpointer
        takes does not synchronize the decode stream.
        """
        return checkpointer.save(step, {"params": self.params},
                                 skip_if_busy=True)

    def run(self, requests: list) -> dict:
        """Serve a request list to completion: {rid: (n_tokens,) int32}."""
        pending = deque(requests)
        results: dict = {}
        first = True
        while pending or any(s is not None for s in self.slots):
            if pending and self.free_slots():
                if first and len(pending) >= self.max_slots:
                    batch = [pending.popleft()
                             for _ in range(self.max_slots)]
                    self.admit_batch(batch)
                else:
                    for slot in self.free_slots():
                        if not pending:
                            break
                        self.admit(pending.popleft(), slot)
            first = False
            # look-ahead prompt staging: the next few pending prompts
            # start their H2D copies now, overlapped with the decode
            # rounds below (already-staged rids just refresh, no copy)
            for r in list(pending)[:self.stager.depth]:
                self.stage(r)
            for rid, toks in self.step():
                results[rid] = toks
        return results


class PagedServeEngine(ServeEngine):
    """Paged-KV serve engine: block tables, prefix sharing, CoW forks.

    Attention KV leaves are physical page pools of ``n_pages + 1`` pages
    of ``page_size`` rows (the extra page is a write-off scratch page:
    unmapped table entries point at it, so stale rows of free slots and
    the overshoot writes of retiring slots land somewhere harmless and
    position-masked). Per-slot block tables live on the host
    (``block_tables``, -1 = unmapped) and are re-shipped each dispatch —
    a few KiB against the MiB-scale KV traffic they steer.

    What the dense engine zero-fills eagerly, this engine allocates
    lazily: pages appear only when a slot's position advances into them
    (`_pre_dispatch`), admissions map shared prompt prefixes instead of
    copying them (``share_prefixes``), `fork` clones a stream for the
    cost of its recurrent state plus refcounts, and retirement returns
    pages with their stale contents intact — recycling skips the
    zero-fill a dense admission would pay, which is exactly the
    write-allocate traffic the MemTier pricing in
    ``serve.kv_traffic`` charges for.
    """

    paged = True

    def __init__(self, cfg: ModelConfig, params, *, page_size: int = 8,
                 n_pages: int | None = None, share_prefixes: bool = True,
                 **kw):
        self.page_size = int(page_size)
        self.pages_per_slot = pages_lib.pages_per_slot(
            kw["max_len"], self.page_size)
        # dense-equivalent capacity by default: sharing and laziness can
        # only ever need fewer pages than one-stripe-per-slot
        self.n_pages = int(n_pages) if n_pages is not None \
            else kw["max_slots"] * self.pages_per_slot
        self.share_prefixes = bool(share_prefixes)
        super().__init__(cfg, params, **kw)

    # -- layout hooks -------------------------------------------------------
    def _make_plan(self, machine):
        return plan_chunk_size(self.cfg, self.max_slots, self.max_len,
                               machine=machine, occupancy=self.kv_len,
                               store_flavor=self.store_flavor,
                               page_size=self.page_size,
                               mesh=self.mesh, rules=self.rules)

    def _make_decode(self):
        return jax.jit(
            self._traced(make_chunked_decode_step(
                self.cfg, self.chunk, self.temperature,
                attn_impl=self.attn_impl, kv_len=self.kv_len,
                store_flavor=self.store_flavor, paged=True,
                guard=self.nonfinite_guard)),
            donate_argnums=self._donate())

    def _build_state(self):
        cfg, ps = self.cfg, self.page_size
        self.pool = pages_lib.PagePool(self.n_pages, ps)
        self._scratch = self.n_pages          # physical index of scratch
        self.cache = self._shard_cache(
            pages_lib.init_paged_cache(cfg, self.max_slots,
                                       self.n_pages + 1, ps),
            pages_lib.paged_cache_pspecs(cfg, self.rules, self._mesh_sizes,
                                         self.max_slots, self.n_pages + 1,
                                         ps)
            if self.mesh is not None else None)
        self.block_tables = np.full(
            (self.max_slots, self.pages_per_slot), -1, np.int32)
        self._decode = self._make_decode()
        self._page_insert = jax.jit(
            self._traced(pages_lib.make_paged_insert_step(cfg, ps)),
            donate_argnums=(0,))
        self._page_copy = jax.jit(
            self._traced(pages_lib.make_page_copy_step(cfg)),
            donate_argnums=(0,))
        self._slot_copy = jax.jit(
            self._traced(pages_lib.make_slot_copy_step(cfg)),
            donate_argnums=(0,))
        # prefill at *exactly* the prompt length: no horizon zero-fill —
        # fresh pages get real rows, recycled pages keep stale ones
        self._prefill = jax.jit(self._traced(serve_lib.make_prefill_step(
            cfg, cache_len=None, store_flavor=self.store_flavor)))
        self.gather_pages = 0                 # live pages read, summed
                                              # over dispatches (fig8)

    def _insert_prefilled(self, slot: int, one, prompt) -> int:
        ps = self.page_size
        s = len(prompt)
        npg = -(-s // ps)
        with span("insert") as sp:
            shared = self.pool.match_prefix(prompt) \
                if self.share_prefixes else []
            fresh = self.pool.allocate(npg - len(shared))
            held = list(shared) + list(fresh)
            if self.share_prefixes:
                # full prompt pages become matchable by later admissions
                self.pool.register_prefix(prompt, held[:s // ps])
            self.block_tables[slot, :] = -1
            self.block_tables[slot, :npg] = held
            # always dispatched: recurrent leaves need their slot row
            # even when every KV page of the prompt is shared
            self.cache = self._page_insert(
                self.cache, one, jnp.int32(slot),
                jnp.asarray(np.asarray(fresh, np.int32)),
                jnp.arange(len(shared), npg, dtype=jnp.int32))
            note(sp, fresh_pages=len(fresh), shared_pages=len(shared))
        return len(shared) * ps

    def _release_slot(self, i: int) -> None:
        held = [int(p) for p in self.block_tables[i] if p >= 0]
        self.pool.release(held)
        self.block_tables[i, :] = -1
        self.slots[i] = None

    def _pre_dispatch(self) -> None:
        """Make every page the coming chunk will write exist and be ours.

        For each active slot: allocate the pages its next
        ``min(chunk, remaining)`` positions will touch, and
        copy-on-write any that are shared (prefix index, forks). After
        this, the in-graph scatter can never land on a page another
        holder can see. Overshoot writes past ``remaining`` hit either
        an exclusively-held page (rows masked after retirement) or the
        scratch page — never an allocated shared one.
        """
        ps, pps = self.page_size, self.pages_per_slot
        allocated = copies = 0
        with span("pre_dispatch") as sp:
            for i, st in enumerate(self.slots):
                if st is None:
                    continue
                p0 = int(self._pos[i])
                take = min(self.chunk, st.remaining)
                l_lo = min(p0 // ps, pps - 1)
                l_hi = min((p0 + take - 1) // ps, pps - 1)
                for lg in range(l_lo, l_hi + 1):
                    phys = int(self.block_tables[i, lg])
                    if phys < 0:
                        self.block_tables[i, lg] = self.pool.allocate(1)[0]
                        allocated += 1
                        continue
                    page, copied = self.pool.prepare_write(phys)
                    if copied:
                        self.cache = self._page_copy(
                            self.cache, jnp.int32(phys), jnp.int32(page))
                        copies += 1
                    self.block_tables[i, lg] = page
            live = self.block_tables[[i for i, st in enumerate(self.slots)
                                      if st is not None]]
            self.gather_pages += int((live >= 0).sum())
            note(sp, pages_allocated=allocated, cow_copies=copies)

    def _decode_args(self):
        # ``bt`` is a fresh temporary (np.where allocates), so it may
        # zero-copy alias safely; ``_pos`` is live host state and needs
        # the pipelined snapshot copy (see ``_host_dev``)
        bt = np.where(self.block_tables < 0, self._scratch,
                      self.block_tables).astype(np.int32)
        return (self.params, self.cache, jnp.asarray(bt),
                self._tok_input(), self._host_dev(self._pos))

    # -- paged-only surface -------------------------------------------------
    def fork(self, rid: str, new_rid: str,
             max_new_tokens: int | None = None) -> int:
        """Clone an active stream into a free slot, copy-on-write.

        The clone maps the same physical pages (refcounted); only the
        slot-batched recurrent state (mamba/xLSTM) is copied on device.
        Divergent writes trigger per-page CoW at the next
        `_pre_dispatch`. Returns the clone's slot index.
        """
        if self._inflight:
            self.sync()      # clone from materialized host-side state
        src = next((i for i, st in enumerate(self.slots)
                    if st is not None and st.rid == rid), None)
        if src is None:
            raise KeyError(f"no active request {rid!r}")
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot")
        dst = free[0]
        self.pool.fork([int(p) for p in self.block_tables[src] if p >= 0])
        self.block_tables[dst] = self.block_tables[src]
        self.cache = self._slot_copy(self.cache, jnp.int32(src),
                                     jnp.int32(dst))
        st = self.slots[src]
        self.slots[dst] = _Slot(
            rid=new_rid,
            remaining=st.remaining if max_new_tokens is None
            else max_new_tokens,
            out=list(st.out))
        self._tok[dst] = self._tok[src]
        self._pos[dst] = self._pos[src]
        return dst

    def check_pool(self) -> None:
        """Assert page-conservation invariants over the live block tables."""
        self.pool.check_conservation(
            [[int(p) for p in self.block_tables[i] if p >= 0]
             for i, st in enumerate(self.slots) if st is not None])
