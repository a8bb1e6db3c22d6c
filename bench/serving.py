"""The benchmark's one adapter to the serving program.

Everything the harness knows of the program's internals is here: how a
configuration file becomes the program's ``ModelConfig`` and parameter
tree, which engine and router serve it, and how emitted tokens are read
after each router round (the retired list, plus each engine slot's
request id and ``len(out)``), since the engine has no token-emission
event of its own.
"""

from __future__ import annotations

import gc

import jax
import numpy as np

import weights as W

#: the router's queue bound: open-loop traffic queues without refusal
QUEUE_BOUND = 1 << 30


def program_config(m: W.Model, name: str):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=name, family="dense", n_layers=m.layers,
                       d_model=m.d, n_heads=m.heads, n_kv_heads=m.kv_heads,
                       head_dim=m.head_dim, d_ff=m.ffn, vocab_size=m.vocab,
                       qkv_bias=m.qkv_bias, rope_theta=m.rope_theta,
                       norm_eps=m.eps, param_dtype=m.dtype)


def program_tree(top: dict, layers: dict) -> dict:
    """The program's parameter tree (``repro.models.model``) from the
    benchmark's leaves: one scanned block repeated over every layer."""
    mixer = {k: layers[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
             if k in layers}
    block = {"ln1": layers["ln1"], "mixer": mixer, "ln2": layers["ln2"],
             "ffn": {k: layers[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"tok_embed": top["embed"], "scan": {"0": block}, "tail": {},
            "final_norm": top["final_norm"], "lm_head": top["lm_head"]}


class Server:
    """One ``PagedServeEngine`` on one chip behind a ``ReplicaRouter``,
    as the configuration file's ``engine`` entry describes."""

    def __init__(self, conf: dict, seed: int):
        from repro.models import model as M
        from repro.serve import PagedServeEngine, ReplicaRouter
        self.m = W.Model.from_config(conf)
        self.cfg = program_config(self.m, conf["name"])
        e = conf["engine"]
        self.page_size, self.chunk = e["page_size"], e["chunk"]
        self._make = self._param_maker(M)
        self.engine = PagedServeEngine(
            self.cfg, self._make(W.key_data(seed)),
            max_slots=e["max_slots"], max_len=e["max_len"], chunk=e["chunk"],
            page_size=e["page_size"], n_pages=e["n_pages"],
            attn_impl=e["attn_impl"], pipeline=e["pipeline"],
            temperature=0.0)
        self.router = ReplicaRouter([self.engine], max_queue=QUEUE_BOUND)

    def _param_maker(self, M):
        """One jitted call: the seed's weights in the program's tree."""
        m = self.m
        want = M.param_shapes(self.cfg)
        got = jax.eval_shape(lambda kd: program_tree(*W.stacked(m, kd)),
                             jax.ShapeDtypeStruct((2,), np.uint32))
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("benchmark weights do not match the program's "
                             "parameter tree")
        return jax.jit(lambda kd: program_tree(*W.stacked(m, kd)))

    # -- the entry the window drives ----------------------------------------
    def submit(self, req) -> None:
        from repro.serve import Request
        self.router.submit(Request(rid=req.rid,
                                   prompt=tuple(int(t) for t in req.prompt),
                                   max_new_tokens=req.max_new))

    def step(self) -> list:
        """One router round; the (rid, tokens) pairs it retired."""
        return self.router.step()

    def busy(self) -> bool:
        return self.router.busy()

    # -- what the harness reads ---------------------------------------------
    def active(self) -> list:
        """(rid, tokens emitted so far) of every occupied engine slot."""
        return [(s.rid, len(s.out)) for s in self.engine.slots
                if s is not None]

    def queued(self) -> int:
        return len(self.router.queues[0])

    def pool_stats(self) -> dict:
        return dict(self.engine.pool.stats)

    # -- set-up and tear-down -----------------------------------------------
    def warm_up(self, reqs: list, rng) -> int:
        """Serve one request of every prompt length ``reqs`` holds, each
        for one decode round, so that every program the window runs is
        compiled and loaded. The ids are fresh draws from ``rng``.
        Returns the requests served."""
        from arrivals import Req
        lengths = sorted({len(r.prompt) for r in reqs})
        for i, n in enumerate(lengths):
            self.submit(Req(rid=f"warm{i}", due=None,
                            prompt=rng.integers(0, self.m.vocab, n, np.int32),
                            max_new=self.chunk + 1))
        while self.busy():
            self.step()
        jax.block_until_ready(self.engine.cache)
        return len(lengths)

    def drop_params(self) -> None:
        """Free the weights, keeping the engine's programs and cache."""
        self.engine.params = None
        gc.collect()

    def reseed(self, seed: int) -> None:
        self.engine.params = self._make(W.key_data(seed))

    def close(self) -> None:
        """Free every device array the program holds."""
        self.engine.params = self.engine.cache = None
        self.router = self.engine = None
        gc.collect()


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
